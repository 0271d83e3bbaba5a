"""strategy.run / distribute_datasets_from_function / InputContext tests.

The custom-training-loop surface (TF's run-then-reduce idiom,
keras:src/backend/tensorflow/trainer.py:134 / SURVEY.md D15-L4) on the
TPU-native strategy: run lowers to one shard_map program, per-replica results
come back stacked on a leading replica axis, reduce folds them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_dist as td
from tpu_dist.parallel.strategy import InputContext


class TestStrategyRun:
    def test_per_replica_loss_and_reduce(self, eight_devices):
        strategy = td.MirroredStrategy()
        x = np.arange(32, dtype=np.float32).reshape(32, 1)
        xb = strategy.distribute_batch(x)

        def replica_loss(batch):
            return (batch ** 2).mean()

        out = strategy.run(replica_loss, args=(xb,))
        assert out.shape == (8,)
        expected = (x ** 2).reshape(8, 4).mean(axis=1)
        np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)
        total = strategy.reduce("mean", out)
        np.testing.assert_allclose(float(total), (x ** 2).mean(), rtol=1e-6)

    def test_collective_inside_fn(self, eight_devices):
        strategy = td.MirroredStrategy()
        x = np.arange(16, dtype=np.float32)
        xb = strategy.distribute_batch(x)

        def fn(batch):
            # Cross-replica mean — every replica returns the same value.
            return jax.lax.pmean(batch.sum(), strategy.data_axis)

        out = strategy.run(fn, args=(xb,))
        assert out.shape == (8,)
        np.testing.assert_allclose(np.asarray(out),
                                   np.full(8, x.sum() / 8), rtol=1e-6)

    def test_replicated_args(self, eight_devices):
        strategy = td.MirroredStrategy()
        w = np.float32(3.0)

        def fn(scale):
            return scale * 2.0

        out = strategy.run(fn, args=(w,))
        assert out.shape == (8,)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 6.0))

    def test_pytree_outputs_and_kwargs(self, eight_devices):
        strategy = td.MirroredStrategy()
        x = np.ones((8, 2), np.float32)
        xb = strategy.distribute_batch(x)

        def fn(batch, *, scale):
            return {"sum": batch.sum() * scale, "batch2": batch * 2}

        out = strategy.run(fn, args=(xb,), kwargs={"scale": 10.0})
        assert out["sum"].shape == (8,)
        np.testing.assert_allclose(np.asarray(out["sum"]), np.full(8, 20.0))
        # Per-replica array outputs stack as [replicas, local_batch, ...].
        assert out["batch2"].shape == (8, 1, 2)

    def test_gradient_step_matches_full_batch(self, eight_devices):
        # The canonical custom loop (TF guidance: scale per-replica loss by
        # 1/num_replicas, then all-reduce SUM). Here the all-reduce is
        # implicit: differentiating w.r.t. the REPLICATED w makes the SPMD
        # transpose psum the cotangents across replicas, so every replica
        # returns the full global gradient — no explicit collective needed.
        strategy = td.MirroredStrategy()
        w = jnp.asarray(2.0)
        x = np.arange(8, dtype=np.float32)
        xb = strategy.distribute_batch(x)
        n = strategy.num_replicas_in_sync

        def replica_grad(w, batch):
            return jax.grad(
                lambda w: ((w * batch) ** 2).mean() / n)(w)

        out = strategy.run(replica_grad, args=(w, xb))
        g_ref = jax.grad(lambda w: ((w * jnp.asarray(x)) ** 2).mean())(w)
        # Every replica already holds the global grad; reduce is a no-op mean.
        np.testing.assert_allclose(np.asarray(out), np.full(8, float(g_ref)),
                                   rtol=1e-6)
        g = strategy.reduce("mean", out)
        np.testing.assert_allclose(float(g), float(g_ref), rtol=1e-6)

    def test_fn_sees_local_shard_not_global_batch(self, eight_devices):
        # Regression guard for the silent-missharding failure mode: fn must
        # receive this replica's 2-element shard, never the global batch.
        strategy = td.MirroredStrategy()
        x = np.arange(16, dtype=np.float32)
        xb = strategy.distribute_batch(x)
        seen = {}

        def fn(batch):
            seen["shape"] = batch.shape
            return batch.sum()

        out = strategy.run(fn, args=(xb,))
        assert seen["shape"] == (2,)
        # Per-replica sums are DISTINCT (each saw only its own slice).
        np.testing.assert_allclose(
            np.asarray(out), x.reshape(8, 2).sum(axis=1))

    def test_rejects_call_under_jit(self, eight_devices):
        # Under an outer trace the arguments' shardings are invisible, which
        # would silently hand every replica the full batch — run() must
        # refuse instead.
        strategy = td.MirroredStrategy()
        x = np.arange(16, dtype=np.float32)
        xb = strategy.distribute_batch(x)
        step = jax.jit(lambda b: strategy.run(lambda t: t.sum(), args=(b,)))
        with pytest.raises(ValueError, match="under a jax transformation"):
            step(xb)

    def test_repeated_calls_hit_program_cache(self, eight_devices):
        strategy = td.MirroredStrategy()
        x = np.arange(16, dtype=np.float32)
        xb = strategy.distribute_batch(x)

        def fn(batch):
            return batch.mean()

        strategy.run(fn, args=(xb,))
        assert len(strategy._run_cache) == 1
        strategy.run(fn, args=(strategy.distribute_batch(x + 1),))
        assert len(strategy._run_cache) == 1  # same fn/structure/sharding

    def test_inline_lambda_hits_cache(self, eight_devices):
        # The natural TF-port pattern: a fresh lambda every loop iteration
        # must not recompile (keyed on code + closure values, not identity).
        strategy = td.MirroredStrategy()
        x = np.arange(16, dtype=np.float32)

        def step(b):
            return b.sum()

        for i in range(3):
            strategy.run(lambda b: step(b),
                         args=(strategy.distribute_batch(x + i),))
        assert len(strategy._run_cache) == 1

    def test_bound_methods_of_different_instances_do_not_collide(
            self, eight_devices):
        # Bound methods share __code__/__closure__ with `self` in neither;
        # the cache key must include the receiver or instance B silently
        # gets instance A's compiled program.
        strategy = td.MirroredStrategy()
        x = np.arange(16, dtype=np.float32)
        xb = strategy.distribute_batch(x)

        class Scaler:
            def __init__(self, s):
                self.s = s

            def step(self, batch):
                return batch.sum() * self.s

        a, b = Scaler(1.0), Scaler(10.0)
        out_a = strategy.reduce("sum", strategy.run(a.step, args=(xb,)))
        out_b = strategy.reduce("sum", strategy.run(b.step, args=(xb,)))
        np.testing.assert_allclose(float(out_a), x.sum())
        np.testing.assert_allclose(float(out_b), 10 * x.sum())
        # Mutating a (hashable-attr) receiver must recompile, not serve the
        # stale program with the old value baked in.
        a.s = 3.0
        out_a2 = strategy.reduce("sum", strategy.run(a.step, args=(xb,)))
        np.testing.assert_allclose(float(out_a2), 3 * x.sum())

    def test_reduce_pytree_outputs(self, eight_devices):
        # The documented run-then-reduce idiom must work on dict outputs.
        strategy = td.MirroredStrategy()
        x = np.arange(16, dtype=np.float32)
        xb = strategy.distribute_batch(x)

        def fn(batch):
            return {"sum": batch.sum(), "pair": (batch.mean(), batch.max())}

        out = strategy.run(fn, args=(xb,))
        red = strategy.reduce("sum", out)
        np.testing.assert_allclose(float(red["sum"]), x.sum())
        red_m = strategy.reduce("mean", out)
        np.testing.assert_allclose(float(red_m["pair"][0]), x.mean())


class TestDistributeDatasetsFromFunction:
    def test_input_context_fields(self, eight_devices):
        strategy = td.MirroredStrategy()
        seen = {}

        def dataset_fn(ctx):
            seen["ctx"] = ctx
            # TF's contract: batch to the PER-REPLICA size; the wrapper
            # draws one element per local replica and stacks them.
            x = np.arange(64, dtype=np.float32).reshape(64, 1)
            return td.data.Dataset.from_tensor_slices(
                (x, np.zeros(64, np.int64))).batch(
                ctx.get_per_replica_batch_size(32))

        dist = strategy.distribute_datasets_from_function(dataset_fn)
        ctx = seen["ctx"]
        assert ctx.num_input_pipelines == 1 and ctx.input_pipeline_id == 0
        assert ctx.num_replicas_in_sync == 8
        assert ctx.get_per_replica_batch_size(32) == 4
        with pytest.raises(ValueError, match="not divisible"):
            ctx.get_per_replica_batch_size(33)
        xb, yb = next(iter(dist))
        # Effective global batch = per-replica 4 x 8 replicas, and each
        # replica's shard is exactly one dataset element (TF consumption).
        assert xb.shape == (32, 1)
        assert len(xb.sharding.device_set) == 8
        np.testing.assert_array_equal(
            np.asarray(xb).ravel(), np.arange(32, dtype=np.float32))

    def test_experimental_alias(self, eight_devices):
        strategy = td.MirroredStrategy()
        assert (strategy.experimental_distribute_datasets_from_function
                == strategy.distribute_datasets_from_function)

    def test_uneven_replicas_per_pipeline_raises(self, eight_devices,
                                                 monkeypatch):
        # ADVICE r2: flooring 8 replicas // 3 pipelines would silently
        # mis-size the global batch; the wrapper must reject instead.
        # (r4: pipelines follow the data-axis process structure —
        # input_shard_info — not raw process_count, so the fault is
        # simulated at that seam.)
        strategy = td.MirroredStrategy()
        monkeypatch.setattr(type(strategy), "input_shard_info",
                            lambda self: (3, 0))
        with pytest.raises(ValueError,
                           match="divisible by the input-pipeline count"):
            strategy.distribute_datasets_from_function(
                lambda ctx: td.data.Dataset.range(8))

    def test_feeds_fit(self, eight_devices):
        strategy = td.MirroredStrategy()

        def dataset_fn(ctx):
            rng = np.random.default_rng(ctx.input_pipeline_id)
            labels = rng.integers(10, size=256)
            x = np.zeros((256, 12, 12, 1), np.float32)
            x[np.arange(256), :, labels] = 1.0
            return td.data.Dataset.from_tensor_slices(
                (x, labels.astype(np.int64))).batch(
                ctx.get_per_replica_batch_size(32)).repeat()

        from tpu_dist.models import Dense, Flatten, Sequential
        from tpu_dist.ops import (Adam, SparseCategoricalAccuracy,
                                  SparseCategoricalCrossentropy)

        with strategy.scope():
            model = Sequential([Flatten(), Dense(10)],
                               input_shape=(12, 12, 1))
            model.compile(loss=SparseCategoricalCrossentropy(from_logits=True),
                          optimizer=Adam(learning_rate=0.05),
                          metrics=[SparseCategoricalAccuracy()])
        dist = strategy.distribute_datasets_from_function(dataset_fn)
        hist = model.fit(dist, epochs=3, steps_per_epoch=8, verbose=0)
        assert hist.history["accuracy"][-1] > 0.8
