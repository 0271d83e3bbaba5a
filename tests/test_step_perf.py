"""Overlap-aware step execution: bucketed gradient all-reduce, the latency
cost model, double-buffered host->device input, and the fused SGD kernel.

Covers the contracts the step-time work leans on:

* ``partition_buckets`` — deterministic reverse-topological packing with
  exact boundary behavior (the schedule every rank must derive
  identically; rank-divergent packing is the SC201 deadlock the
  ``bucket_order_divergent`` fixture pins);
* ``bucketed_all_reduce`` — numerics parity with the fused all-reduce
  under the real 8-device mesh;
* trainer schedule parity — fused vs bucketed vs prefetched fits produce
  allclose losses (observed bit-identical on this workload), with no
  retraces (``_cache_size() == 1``) and knob changes invalidating the
  compiled step;
* ``DevicePrefetcher`` — hit/miss accounting, error propagation, and
  teardown with NO leaked producer threads, including mid-epoch
  ``StopTraining`` (the preemption-drain path lands in the same
  ``finally``);
* the latency cost model — link-spec mesh parsing, launch-count pricing,
  and the non-overlappable comm-tail overlap rule;
* ``fused_sgd_apply`` — interpret-mode allclose parity with the
  reference SGD tree_map math for all momentum/nesterov configs.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist.data import Dataset
from tpu_dist.data.pipeline import DevicePrefetcher
from tpu_dist.models import Dense, Sequential
from tpu_dist.parallel import MirroredStrategy, collectives
from tpu_dist.parallel.collectives import ReduceOp, partition_buckets
from tpu_dist.training.callbacks import LambdaCallback, StopTraining


def _leaked_prefetch_threads():
    return [t for t in threading.enumerate()
            if "device-prefetch" in t.name and t.is_alive()]


def _tree():
    # Leaf order (tree_leaves, dict keys sorted): a=64 B, b=16 B, c=400 B.
    return {"a": jnp.zeros((4, 4), jnp.float32),
            "b": jnp.zeros((4,), jnp.float32),
            "c": jnp.zeros((100,), jnp.float32)}


class TestPartitionBuckets:
    def test_reverse_topological_one_leaf_per_tiny_bucket(self):
        # bucket_bytes=1: every leaf flushes alone, last leaf first —
        # gradients for the LAST layers are ready FIRST in backward order.
        assert partition_buckets(_tree(), 1) == [[2], [1], [0]]

    def test_zero_bucket_bytes_is_one_fused_bucket(self):
        assert partition_buckets(_tree(), 0) == [[2, 1, 0]]

    def test_boundary_flushes_at_capacity(self):
        # 400 B (c) >= 80 flushes alone; then b (16) + a (64) reach 80
        # exactly and flush together.
        assert partition_buckets(_tree(), 80) == [[2], [1, 0]]

    def test_every_leaf_assigned_exactly_once(self):
        for bb in (0, 1, 64, 80, 1 << 20):
            flat = [i for b in partition_buckets(_tree(), bb) for i in b]
            assert sorted(flat) == [0, 1, 2], f"bucket_bytes={bb}"

    def test_empty_tree(self):
        assert partition_buckets({}, 64) == []

    def test_deterministic(self):
        assert (partition_buckets(_tree(), 80)
                == partition_buckets(_tree(), 80))


class TestBucketedAllReduce:
    @pytest.mark.parametrize("bucket_bytes", [0, 1, 64])
    @pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.MEAN])
    def test_matches_fused_all_reduce(self, eight_devices, op,
                                      bucket_bytes):
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(eight_devices), ("data",))
        tree = {"w": jnp.arange(16.0).reshape(4, 4),
                "b": jnp.arange(4.0) + 1.0}

        def bucketed(t):
            return collectives.bucketed_all_reduce(
                t, "data", op, bucket_bytes=bucket_bytes)

        def fused(t):
            return collectives.all_reduce(t, "data", op)

        kw = dict(mesh=mesh, in_specs=({"w": P(), "b": P()},),
                  out_specs={"w": P(), "b": P()})
        outs = []
        for fn in (bucketed, fused):
            mapped = jax.shard_map(fn, check_vma=False, **kw)
            outs.append(jax.jit(mapped)(tree))
        for k in tree:
            np.testing.assert_allclose(outs[0][k], outs[1][k],
                                       rtol=1e-6, atol=0)


def _fit_losses(*, bucket_bytes=0, prefetch=0, epochs=3, steps=6,
                batch=32):
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (steps * batch, 8)).astype(np.float32)
    y = rng.integers(4, size=steps * batch).astype(np.int64)
    m = Sequential([Dense(16, activation="relu"), Dense(4)],
                   input_shape=(8,))
    m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy",
              gradient_bucket_bytes=bucket_bytes,
              prefetch_to_device=prefetch)
    m.strategy = MirroredStrategy()
    ds = Dataset.from_tensor_slices((x, y)).batch(batch)
    h = m.fit(ds, epochs=epochs, steps_per_epoch=steps, verbose=0, seed=9)
    return [float(v) for v in h.history["loss"]], m


class TestTrainerSchedules:
    def test_bucketed_and_prefetch_loss_parity(self, eight_devices):
        fused, _ = _fit_losses()
        bucketed, mb = _fit_losses(bucket_bytes=64)
        prefetched, mp = _fit_losses(prefetch=2)
        np.testing.assert_allclose(bucketed, fused, rtol=0, atol=1e-5)
        np.testing.assert_allclose(prefetched, fused, rtol=0, atol=1e-5)
        # One compiled program per schedule across the whole run.
        assert mb._trainer._train_step._cache_size() == 1
        assert mp._trainer._train_step._cache_size() == 1
        assert not _leaked_prefetch_threads()

    def test_bucket_knob_change_invalidates_compiled_step(self,
                                                          eight_devices):
        _, m = _fit_losses(bucket_bytes=64, epochs=1)
        step = m._trainer._train_step
        m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy",
                  gradient_bucket_bytes=128)
        rng = np.random.default_rng(3)
        x = rng.normal(0, 1, (64, 8)).astype(np.float32)
        y = rng.integers(4, size=64).astype(np.int64)
        m.fit(Dataset.from_tensor_slices((x, y)).batch(32), epochs=1,
              steps_per_epoch=2, verbose=0, seed=9)
        assert m._trainer._train_step is not step

    def test_defaults_are_off(self):
        m = Sequential([Dense(2)], input_shape=(2,))
        m.compile(optimizer="sgd", loss="mse")
        assert m.gradient_bucket_bytes == 0
        assert m.prefetch_to_device == 0

    def test_knob_validation(self):
        m = Sequential([Dense(2)], input_shape=(2,))
        with pytest.raises(ValueError):
            m.compile(optimizer="sgd", loss="mse",
                      gradient_bucket_bytes=-1)
        with pytest.raises(ValueError):
            m.compile(optimizer="sgd", loss="mse", prefetch_to_device=-1)

    def test_stop_training_mid_epoch_tears_down_prefetcher(
            self, eight_devices):
        # The preemption-drain/StopTraining path reaches fit's finally with
        # the producer thread possibly mid-device_put; teardown must leave
        # no live producer behind.
        rng = np.random.default_rng(3)
        x = rng.normal(0, 1, (256, 8)).astype(np.float32)
        y = rng.integers(4, size=256).astype(np.int64)
        m = Sequential([Dense(4)], input_shape=(8,))
        m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy",
                  prefetch_to_device=3)
        m.strategy = MirroredStrategy()

        def stop(step, logs):
            if step >= 2:
                raise StopTraining("drain now")

        m.fit(Dataset.from_tensor_slices((x, y)).batch(32), epochs=4,
              steps_per_epoch=8, verbose=0, seed=9,
              callbacks=[LambdaCallback(on_batch_end=stop)])
        assert not _leaked_prefetch_threads()
        assert m._trainer._prefetcher is None


class TestDevicePrefetcher:
    def test_yields_all_batches_in_order_then_stops(self):
        pf = DevicePrefetcher(iter(range(5)), depth=2)
        assert list(pf) == [0, 1, 2, 3, 4]
        with pytest.raises(StopIteration):
            next(pf)
        pf.close()
        assert pf.closed

    def test_counts_hits_and_misses(self):
        import time

        pf = DevicePrefetcher(iter(range(4)), depth=4)
        time.sleep(0.2)  # producer fills the queue
        consumed = list(pf)
        assert consumed == [0, 1, 2, 3]
        assert pf.hits >= 1
        assert pf.hits + pf.misses == 4
        pf.close()

    def test_producer_error_propagates(self):
        def gen():
            yield 1
            raise RuntimeError("storage gone")

        pf = DevicePrefetcher(gen(), depth=2)
        assert next(pf) == 1
        with pytest.raises(RuntimeError, match="storage gone"):
            while True:
                next(pf)
        pf.close()
        assert not _leaked_prefetch_threads()

    def test_close_mid_stream_joins_producer(self):
        pf = DevicePrefetcher(iter(range(10_000)), depth=2)
        assert next(pf) == 0
        pf.close()
        assert pf.closed
        assert not _leaked_prefetch_threads()
        pf.close()  # idempotent

    def test_depth_validation(self):
        with pytest.raises(ValueError):
            DevicePrefetcher(iter(()), depth=0)


class TestLatencyCostModel:
    def test_parse_mesh_unchanged_contract(self):
        from tpu_dist.analysis import costmodel

        assert costmodel.parse_mesh("data=8,model=4") == {
            "data": 8, "model": 4}

    def test_parse_mesh_links(self):
        from tpu_dist.analysis import costmodel

        axes, links = costmodel.parse_mesh_links("data=8:90:1.5,model=4")
        assert axes == {"data": 8, "model": 4}
        assert set(links) == {"data"}
        assert links["data"].bandwidth_gbps == 90.0
        assert links["data"].latency_us == 1.5
        # Link suffixes are accepted and dropped by the sizes-only parser.
        assert costmodel.parse_mesh("data=8:90:1.5") == {"data": 8}

    def test_parse_mesh_links_rejects_bad_specs(self):
        from tpu_dist.analysis import costmodel

        for bad in ("data=8:0:1", "data=8:10:-1", "data=8:a", "data=8:1:2:3"):
            with pytest.raises(ValueError):
                costmodel.parse_mesh_links(bad)

    def test_estimate_latency_launch_count_and_tail(self):
        from tpu_dist.analysis import costmodel

        link = costmodel.LinkSpec(bandwidth_gbps=1.0, latency_us=10.0)
        mk = lambda b, mult: costmodel.CollectiveCost(
            op="psum", axes=("data",), axis_size=8, payload_bytes=b,
            multiplier=mult, bytes=b * mult, shape=(b // 4,),
            dtype="float32")
        # Two sites, one launch each: each pays 10 us latency + wire time.
        est = costmodel.estimate_latency(
            0, [mk(1000, 1), mk(1000, 1)], links={"data": link})
        assert est.launches == 2
        assert est.comm_s == pytest.approx(2 * (10e-6 + 1000 / 1e9))
        # No compute to hide behind: the whole comm is tail.
        assert est.comm_tail_s == pytest.approx(est.comm_s)
        assert est.step_latency_s == pytest.approx(est.comm_s)

    def test_estimate_latency_overlap_hides_all_but_last_site(self):
        from tpu_dist.analysis import costmodel

        link = costmodel.LinkSpec(bandwidth_gbps=1.0, latency_us=10.0)
        mk = lambda b: costmodel.CollectiveCost(
            op="psum", axes=("data",), axis_size=8, payload_bytes=b,
            multiplier=1, bytes=b, shape=(b // 4,), dtype="float32")
        big_compute = int(1e12)  # 10 ms at the 100 TFLOP/s default
        est = costmodel.estimate_latency(
            big_compute, [mk(1000), mk(2000)], links={"data": link})
        last_site = 10e-6 + 2000 / 1e9
        # Everything before the final launch site overlaps with compute.
        assert est.comm_tail_s == pytest.approx(last_site)
        assert est.overlapped_s == pytest.approx(est.comm_s - last_site)
        assert est.step_latency_s == pytest.approx(
            est.compute_s + last_site)

    def test_scan_multiplies_launch_count(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from tpu_dist.analysis import costmodel

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

        def body(x):
            def step(c, _):
                return jax.lax.psum(c, "data"), None

            out, _ = jax.lax.scan(step, x, None, length=5)
            return out

        kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
        mapped = jax.shard_map(body, check_vma=False, **kw)
        closed = jax.make_jaxpr(mapped)(jnp.zeros((4,)))
        report = costmodel.analyze_jaxpr(closed, entry="scan_probe")
        assert report.latency.launches == 5

    def test_analyze_jaxpr_reports_latency_json(self):
        from tpu_dist.analysis import costmodel

        closed = jax.make_jaxpr(
            lambda a, b: jnp.dot(a, b))(jnp.zeros((8, 16)),
                                        jnp.zeros((16, 4)))
        report = costmodel.analyze_jaxpr(closed, entry="dot_probe")
        # 2*M*N*K flops for the dot, no collectives -> pure compute.
        assert report.latency.flops >= 2 * 8 * 16 * 4
        assert report.latency.comm_s == 0.0
        payload = report.to_json()
        assert {"compute_s", "comm_s", "comm_tail_s", "step_latency_s",
                "launches", "flops"} <= set(payload["latency"])


class TestCalibration:
    def test_calibrate_emits_loadable_spec(self, tmp_path):
        import json

        from tpu_dist.analysis import costmodel

        spec = costmodel.calibrate(axis_names=("data", "model"),
                                   payload_bytes=(1 << 12, 1 << 15),
                                   matmul_dim=64, repeats=1)
        assert set(spec["links"]) == {"data", "model"}
        assert spec["flops_per_s"] > 0
        assert spec["device_count"] >= 1
        for entry in spec["links"].values():
            assert entry["bandwidth_gbps"] > 0
            assert entry["latency_us"] >= 0
        p = tmp_path / "cal.json"
        p.write_text(json.dumps(spec))
        links, flops = costmodel.load_links(str(p))
        assert flops == pytest.approx(spec["flops_per_s"])
        assert links["data"].bandwidth_gbps == pytest.approx(
            spec["links"]["data"]["bandwidth_gbps"])

    def test_load_links_tolerates_missing_fields(self, tmp_path):
        import json

        from tpu_dist.analysis import costmodel

        p = tmp_path / "cal.json"
        p.write_text(json.dumps({"links": {"data": {}}}))
        links, flops = costmodel.load_links(str(p))
        assert flops is None
        assert links["data"].bandwidth_gbps == (
            costmodel.DEFAULT_LINK_BANDWIDTH_GBPS)

    def test_flops_per_s_scales_compute_estimate(self):
        from tpu_dist.analysis import costmodel

        closed = jax.make_jaxpr(
            lambda a, b: jnp.dot(a, b))(jnp.zeros((64, 64)),
                                        jnp.zeros((64, 64)))
        base = costmodel.analyze_jaxpr(closed, entry="dot")
        slow = costmodel.analyze_jaxpr(closed, entry="dot",
                                       flops_per_s=1e9)
        assert slow.latency.flops == base.latency.flops
        assert slow.latency.compute_s == pytest.approx(
            base.latency.flops / 1e9)
        # flops_per_s=None is the pre-calibration default, bit-unchanged.
        again = costmodel.analyze_jaxpr(closed, entry="dot",
                                        flops_per_s=None)
        assert again.latency.compute_s == base.latency.compute_s


class TestFusedSGDKernel:
    def _params(self):
        rng = np.random.default_rng(0)
        return {
            "w": jnp.asarray(rng.normal(size=(17, 5)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(5,)).astype(np.float32)),
            "s": jnp.asarray(rng.normal(size=()).astype(np.float32)),
        }

    @pytest.mark.parametrize("momentum,nesterov",
                             [(0.0, False), (0.9, False), (0.9, True)])
    def test_interpret_parity_with_reference_sgd(self, momentum, nesterov):
        from tpu_dist.ops.optimizers import SGD
        from tpu_dist.ops.pallas_kernels import fused_sgd_apply

        params = self._params()
        grads = jax.tree_util.tree_map(lambda p: p * 0.3 + 0.1, params)
        ref = SGD(learning_rate=0.05, momentum=momentum, nesterov=nesterov)
        ref_p, ref_state = ref.update(grads, ref.init(params), params)
        vel = (None if momentum == 0.0
               else jax.tree_util.tree_map(jnp.zeros_like, params))
        new_p, new_v = fused_sgd_apply(
            params, grads, vel, learning_rate=0.05, momentum=momentum,
            nesterov=nesterov, interpret=True)
        for a, b in zip(jax.tree_util.tree_leaves(ref_p),
                        jax.tree_util.tree_leaves(new_p)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        if momentum != 0.0:
            for a, b in zip(jax.tree_util.tree_leaves(ref_state),
                            jax.tree_util.tree_leaves(new_v)):
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_fused_flag_off_tpu_matches_plain_path_under_jit(self):
        from tpu_dist.ops.optimizers import SGD

        params = self._params()
        grads = jax.tree_util.tree_map(lambda p: p * 0.3 + 0.1, params)
        fused = SGD(learning_rate=0.05, momentum=0.9, fused=True)
        plain = SGD(learning_rate=0.05, momentum=0.9)
        fp, _ = jax.jit(fused.update)(grads, fused.init(params), params)
        pp, _ = jax.jit(plain.update)(grads, plain.init(params), params)
        for a, b in zip(jax.tree_util.tree_leaves(fp),
                        jax.tree_util.tree_leaves(pp)):
            np.testing.assert_allclose(a, b, rtol=0, atol=0)

    def test_scheduled_lr_keeps_jnp_path(self):
        from tpu_dist.ops import schedules
        from tpu_dist.ops.optimizers import SGD

        sched = schedules.ExponentialDecay(
            initial_learning_rate=0.1, decay_steps=10, decay_rate=0.9)
        fused = SGD(learning_rate=sched, fused=True)
        plain = SGD(learning_rate=sched)
        params = self._params()
        grads = jax.tree_util.tree_map(lambda p: p * 0.5, params)
        fp, fst = fused.update(grads, fused.init(params), params)
        pp, pst = plain.update(grads, plain.init(params), params)
        for a, b in zip(jax.tree_util.tree_leaves(fp),
                        jax.tree_util.tree_leaves(pp)):
            np.testing.assert_allclose(a, b, rtol=0, atol=0)
        assert int(fst.step) == int(pst.step) == 1


class TestFusedAdamKernel:
    def _params(self):
        rng = np.random.default_rng(1)
        return {
            "w": jnp.asarray(rng.normal(size=(17, 5)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(5,)).astype(np.float32)),
            "s": jnp.asarray(rng.normal(size=()).astype(np.float32)),
        }

    def test_interpret_parity_with_reference_adam(self):
        # Multi-step: bias correction changes the scale every step, so
        # parity over several updates pins the traced-scale plumbing, not
        # just the t=1 special case.
        from tpu_dist.ops.optimizers import Adam
        from tpu_dist.ops.pallas_kernels import fused_adam_apply

        ref = Adam(learning_rate=0.02)
        params = self._params()
        state = ref.init(params)
        f_params = params
        f_mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        f_nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        for step in range(1, 4):
            grads = jax.tree_util.tree_map(
                lambda p: p * 0.3 + 0.1 * step, params)
            params_ref, state = ref.update(grads, state, params_ref
                                           if step > 1 else params)
            t = jnp.float32(step)
            scale = 0.02 * jnp.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
            f_params, f_mu, f_nu = fused_adam_apply(
                f_params, grads, f_mu, f_nu, scale=scale, interpret=True)
            for a, b in zip(jax.tree_util.tree_leaves(params_ref),
                            jax.tree_util.tree_leaves(f_params)):
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
            for a, b in zip(jax.tree_util.tree_leaves(state.mu),
                            jax.tree_util.tree_leaves(f_mu)):
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
            for a, b in zip(jax.tree_util.tree_leaves(state.nu),
                            jax.tree_util.tree_leaves(f_nu)):
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_fused_flag_off_tpu_matches_plain_path_under_jit(self):
        from tpu_dist.ops.optimizers import Adam

        params = self._params()
        grads = jax.tree_util.tree_map(lambda p: p * 0.3 + 0.1, params)
        fused = Adam(learning_rate=0.02, fused=True)
        plain = Adam(learning_rate=0.02)
        fp, fst = jax.jit(fused.update)(grads, fused.init(params), params)
        pp, pst = jax.jit(plain.update)(grads, plain.init(params), params)
        for a, b in zip(jax.tree_util.tree_leaves((fp, fst.mu, fst.nu)),
                        jax.tree_util.tree_leaves((pp, pst.mu, pst.nu))):
            np.testing.assert_allclose(a, b, rtol=0, atol=0)
        assert int(fst.step) == int(pst.step) == 1

    def test_scheduled_lr_fuses_and_matches_plain(self):
        # Unlike fused SGD, the Adam kernel takes its step size as a
        # scalar operand -- scheduled learning rates ride the fused path.
        from tpu_dist.ops import schedules
        from tpu_dist.ops.optimizers import Adam

        sched = schedules.ExponentialDecay(
            initial_learning_rate=0.1, decay_steps=10, decay_rate=0.9)
        fused = Adam(learning_rate=sched, fused=True)
        plain = Adam(learning_rate=sched)
        params = self._params()
        f_state, p_state = fused.init(params), plain.init(params)
        fp, pp = params, params
        for step in range(3):
            grads = jax.tree_util.tree_map(
                lambda p: p * 0.5 + 0.01 * step, params)
            fp, f_state = fused.update(grads, f_state, fp)
            pp, p_state = plain.update(grads, p_state, pp)
        for a, b in zip(jax.tree_util.tree_leaves(fp),
                        jax.tree_util.tree_leaves(pp)):
            np.testing.assert_allclose(a, b, rtol=0, atol=0)
        assert int(f_state.step) == int(p_state.step) == 3
