"""Data-pipeline tests: combinators, shard policies, distributed delivery.

Covers the reference's input-pipeline contract (SURVEY.md §3.4, D13/D14/D18):
map/cache/shuffle/batch composition (tf_dist_example.py:20-33), the
auto-shard Options plumbing (tf_dist_example.py:34-37), the OFF-policy
independent-shuffle semantics (README.md:113-120), and per-replica delivery.
"""

import numpy as np
import pytest

from tpu_dist.data import (
    AutoShardPolicy,
    Dataset,
    DistributedDataset,
    Options,
    load,
    load_arrays,
    shard_dataset,
)


def _range_ds(n):
    return Dataset.from_tensor_slices(np.arange(n))


class TestCombinators:
    def test_from_tensor_slices_tuple(self):
        x = np.arange(10).reshape(5, 2)
        y = np.arange(5)
        ds = Dataset.from_tensor_slices((x, y))
        els = list(ds)
        assert len(els) == 5
        np.testing.assert_array_equal(els[3][0], x[3])
        assert els[3][1] == 3

    def test_map_scale(self):
        # The reference's `scale` fn: uint8 -> float32 / 255
        # (tf_dist_example.py:22-25).
        x = np.full((4, 2, 2, 1), 255, np.uint8)
        y = np.zeros(4, np.int64)
        ds = Dataset.from_tensor_slices((x, y)).map(
            lambda img, lab: (img.astype(np.float32) / 255.0, lab))
        img, lab = next(iter(ds))
        assert img.dtype == np.float32 and img.max() == 1.0

    def test_batch_and_remainder(self):
        ds = _range_ds(10).batch(4)
        shapes = [b.shape[0] for b in ds]
        assert shapes == [4, 4, 2]
        ds = _range_ds(10).batch(4, drop_remainder=True)
        assert [b.shape[0] for b in ds] == [4, 4]
        assert ds.cardinality() == 2

    def test_cache_replays_and_counts_source_reads(self):
        reads = []
        src = Dataset.from_generator(lambda: (reads.append(i) or i for i in range(5)))
        ds = src.cache()
        assert list(ds) == list(range(5))
        assert list(ds) == list(range(5))
        assert len(reads) == 5  # second pass served from cache

    def test_shuffle_is_permutation(self):
        ds = _range_ds(100).shuffle(32, seed=0)
        out = list(ds)
        assert sorted(out) == list(range(100))
        assert out != list(range(100))

    def test_unseeded_shuffle_reshuffles_each_iteration(self):
        # Load-bearing for OFF-policy mode: each worker/epoch draws an
        # independent order (README.md:113-120).
        ds = _range_ds(64).shuffle(64)
        assert list(ds) != list(ds)

    def test_seeded_shuffle_deterministic_per_epoch(self):
        a = list(_range_ds(64).shuffle(64, seed=7))
        b = list(_range_ds(64).shuffle(64, seed=7))
        assert a == b

    def test_repeat_take_shard(self):
        assert list(_range_ds(3).repeat(2)) == [0, 1, 2, 0, 1, 2]
        assert list(_range_ds(10).take(4)) == [0, 1, 2, 3]
        assert list(_range_ds(10).shard(3, 1)) == [1, 4, 7]

    def test_prefetch_preserves_order_and_propagates_errors(self):
        assert list(_range_ds(20).prefetch(4)) == list(range(20))

        def bad():
            yield 1
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            list(Dataset.from_generator(bad).prefetch(2))

    def test_reference_pipeline_composition(self):
        # make_datasets_unbatched analog (tf_dist_example.py:20-33):
        # load -> map(scale) -> cache -> shuffle -> batch(GLOBAL_BATCH).
        ds = (load("mnist", "train", synthetic_size=512)
              .map(lambda x, y: (x.astype(np.float32) / 255.0, y))
              .cache()
              .shuffle(10000)
              .batch(128))
        xb, yb = next(iter(ds))
        assert xb.shape == (128, 28, 28, 1) and xb.dtype == np.float32
        assert yb.shape == (128,)
        assert 0.0 <= xb.min() and xb.max() <= 1.0


class TestMoreCombinators:
    def test_skip(self):
        ds = Dataset.range(10).skip(7)
        assert list(ds.as_numpy_iterator()) == [7, 8, 9]
        assert ds.cardinality() == 3
        assert Dataset.range(3).skip(5).cardinality() == 0

    def test_unbatch_roundtrips_batch(self):
        x = np.arange(12, dtype=np.float32).reshape(6, 2)
        y = np.arange(6, dtype=np.int64)
        ds = Dataset.from_tensor_slices((x, y)).batch(3).unbatch()
        got = list(ds.as_numpy_iterator())
        assert len(got) == 6
        np.testing.assert_array_equal(got[4][0], x[4])
        assert got[4][1] == y[4]

    def test_concatenate(self):
        ds = Dataset.range(3).concatenate(Dataset.range(2))
        assert list(ds.as_numpy_iterator()) == [0, 1, 2, 0, 1]
        assert ds.cardinality() == 5

    def test_zip_stops_at_shortest(self):
        a, b = Dataset.range(4), Dataset.range(2)
        z = Dataset.zip(a, b)
        assert list(z.as_numpy_iterator()) == [(0, 0), (1, 1)]
        assert z.cardinality() == 2
        # tuple-arg form, like tf.data.Dataset.zip((a, b))
        assert list(Dataset.zip((a, b)).as_numpy_iterator()) == \
            [(0, 0), (1, 1)]
        with pytest.raises(ValueError, match="at least one"):
            Dataset.zip()

    def test_unbatch_dict_elements(self):
        ds = Dataset.from_tensor_slices(
            {"a": np.arange(6).reshape(3, 2)}).batch(3).unbatch()
        got = list(ds.as_numpy_iterator())
        assert len(got) == 3
        np.testing.assert_array_equal(got[1]["a"], [2, 3])

    def test_concatenate_is_opaque_to_file_sharding(self):
        # Replaying concatenate through the FILE chain rewrite would append
        # the full extra stream to every worker's shard; it must force the
        # DATA fallback instead of crashing or duplicating.
        ds = Dataset.range(6).concatenate(Dataset.range(2))
        assert ds._transform is None

    def test_zip_preserves_options(self):
        a = Dataset.range(4)
        opts = Options()
        opts.experimental_distribute.auto_shard_policy = AutoShardPolicy.OFF
        a = a.with_options(opts)
        z = Dataset.zip(a, Dataset.range(4))
        assert z.auto_shard_policy == AutoShardPolicy.OFF

    def test_interleave_round_robin(self):
        # Each element maps to a 3-element stream; cycle 2 alternates them.
        ds = Dataset.range(2).interleave(
            lambda i: Dataset.range(3).map(lambda j: int(i) * 10 + j),
            cycle_length=2)
        assert list(ds.as_numpy_iterator()) == [0, 10, 1, 11, 2, 12]

    def test_interleave_uneven_streams_tf_ordering(self):
        # tf.data kernel semantics: when stream 0 ends, the cycle advances
        # to slot 1 (emitting 11) and only opens stream 2 in slot 0 when
        # the round-robin returns there — so 11 precedes 20.
        lengths = {0: 1, 1: 2, 2: 1}
        ds = Dataset.range(3).interleave(
            lambda i: Dataset.range(lengths[int(i)]).map(
                lambda j, i=i: int(i) * 10 + j),
            cycle_length=2)
        assert list(ds.as_numpy_iterator()) == [0, 10, 11, 20]

    def test_interleave_is_file_shard_replayable(self):
        ds = Dataset.range(4).interleave(lambda i: Dataset.range(2),
                                         cycle_length=2)
        assert ds._transform is not None and ds._transform[0] == "interleave"

    def test_interleave_block_length_and_refill(self):
        ds = Dataset.range(3).interleave(
            lambda i: Dataset.range(2).map(lambda j: int(i) * 10 + j),
            cycle_length=2, block_length=2)
        # Streams 0 and 1 drain fully (block 2 each), then stream 2 opens.
        assert list(ds.as_numpy_iterator()) == [0, 1, 10, 11, 20, 21]
        with pytest.raises(ValueError, match=">= 1"):
            Dataset.range(2).interleave(lambda i: Dataset.range(1),
                                        cycle_length=0)

    def test_zip_then_batch_feeds_pipeline(self):
        xs = Dataset.from_tensor_slices(np.arange(8, dtype=np.float32))
        ys = Dataset.from_tensor_slices((np.arange(8) % 2).astype(np.int64))
        batches = list(Dataset.zip(xs, ys).batch(4).as_numpy_iterator())
        assert len(batches) == 2
        np.testing.assert_array_equal(batches[0][0], [0, 1, 2, 3])


class TestOptions:
    def test_reference_options_plumbing(self):
        # tf_dist_example.py:34-37 verbatim shape.
        options = Options()
        options.experimental_distribute.auto_shard_policy = AutoShardPolicy.OFF
        ds = _range_ds(8).batch(4).with_options(options)
        assert ds.auto_shard_policy == AutoShardPolicy.OFF

    def test_default_policy_is_auto(self):
        assert _range_ds(4).auto_shard_policy == AutoShardPolicy.AUTO

    def test_enum_values_match_tf(self):
        # tf:python/data/ops/options.py:89-116.
        assert AutoShardPolicy.OFF == -1
        assert AutoShardPolicy.AUTO == 0
        assert AutoShardPolicy.FILE == 1
        assert AutoShardPolicy.DATA == 2
        assert AutoShardPolicy.HINT == 3


class TestShardPolicies:
    def test_off_keeps_full_stream(self):
        ds = shard_dataset(_range_ds(10), 2, 0, AutoShardPolicy.OFF)
        assert list(ds) == list(range(10))

    def test_data_strides_elements(self):
        got = [list(shard_dataset(_range_ds(10), 2, i, AutoShardPolicy.DATA))
               for i in range(2)]
        assert got[0] == [0, 2, 4, 6, 8]
        assert got[1] == [1, 3, 5, 7, 9]

    def test_data_prebatched_slices_batches(self):
        ds = _range_ds(8).batch(4)
        w0 = list(shard_dataset(ds, 2, 0, AutoShardPolicy.DATA, pre_batched=True))
        w1 = list(shard_dataset(ds, 2, 1, AutoShardPolicy.DATA, pre_batched=True))
        np.testing.assert_array_equal(w0[0], [0, 1])
        np.testing.assert_array_equal(w1[0], [2, 3])

    def test_file_policy_insufficient_files_raises(self):
        with pytest.raises(ValueError, match="source files"):
            shard_dataset(_range_ds(4), 2, 0, AutoShardPolicy.FILE)

    def test_auto_falls_back_to_data(self):
        ds = shard_dataset(_range_ds(10), 2, 0, AutoShardPolicy.AUTO)
        assert list(ds) == [0, 2, 4, 6, 8]

    def test_indivisible_prebatched_raises(self):
        ds = _range_ds(9).batch(3)
        with pytest.raises(ValueError, match="not divisible"):
            list(shard_dataset(ds, 2, 0, AutoShardPolicy.DATA, pre_batched=True))


class TestSources:
    def test_synthetic_shapes(self):
        for name, shape in (("mnist", (28, 28, 1)),
                            ("fashion_mnist", (28, 28, 1)),
                            ("cifar10", (32, 32, 3))):
            x, y = load_arrays(name, "test", synthetic_size=64)
            assert x.shape == (64, *shape) and x.dtype == np.uint8
            assert y.shape == (64,) and set(np.unique(y)) <= set(range(10))

    def test_synthetic_deterministic_across_calls(self):
        # Every process must see the same underlying dataset (OFF-policy
        # full-stream semantics).
        x1, y1 = load_arrays("mnist", "train", synthetic_size=32)
        x2, y2 = load_arrays("mnist", "train", synthetic_size=32)
        np.testing.assert_array_equal(x1, x2)
        np.testing.assert_array_equal(y1, y2)

    def test_unknown_dataset_raises(self):
        with pytest.raises(ValueError, match="unknown dataset"):
            load_arrays("imagenet")

    def test_as_supervised_false_yields_dicts(self):
        ds = load("mnist", "test", as_supervised=False, synthetic_size=8)
        el = next(iter(ds))
        assert set(el) == {"image", "label"}

    def test_load_reference_call_shape(self):
        # The reference's literal call (tf_dist_example.py:27-31):
        # tfds.load(with_info=True, name='mnist', as_supervised=True),
        # then datasets['train']. Must transliterate with no shape changes.
        datasets, info = load(with_info=True, name="mnist",
                              as_supervised=True, synthetic_size=16)
        assert set(datasets) == {"train", "test"}
        x, y = next(iter(datasets["train"]))
        assert x.shape == (28, 28, 1)
        assert info.splits["train"].num_examples == datasets[
            "train"].cardinality()
        assert info.splits["test"].num_examples == datasets[
            "test"].cardinality()
        assert info.num_classes == 10 and info.image_shape == (28, 28, 1)

    def test_load_no_split_returns_dict(self):
        datasets = load("cifar10", synthetic_size=8)
        assert set(datasets) == {"train", "test"}
        assert datasets["train"].cardinality() == 8

    def test_load_with_info_single_split(self):
        ds, info = load("mnist", split="test", with_info=True,
                        synthetic_size=8)
        el = next(iter(ds))
        assert len(el) == 2
        assert info.splits["test"].num_examples == 8
        assert info.synthetic  # no real MNIST in this environment
        # tfds lists every official split even when one was requested.
        assert set(info.splits) == {"train", "test"}
        assert info.splits["train"].num_examples == 8

    def test_load_info_reflects_real_files(self, tmp_path, monkeypatch):
        # With a real (written) sharded copy on disk, info must report the
        # served cardinality and synthetic=False for that split.
        from tpu_dist.data.sources import write_sharded
        rng = np.random.default_rng(0)
        x = rng.integers(0, 255, size=(24, 28, 28, 1)).astype(np.uint8)
        y = rng.integers(0, 10, size=(24,)).astype(np.int64)
        write_sharded(tmp_path, "mnist", "train", x, y, num_shards=3)
        monkeypatch.setenv("TPU_DIST_DATA_DIR", str(tmp_path))
        ds, info = load("mnist", split="train", with_info=True)
        assert info.splits["train"].num_examples == 24
        assert not info.synthetic
        assert ds.num_files == 3

    def test_disable_progress_bar_noop(self):
        from tpu_dist.data import disable_progress_bar
        disable_progress_bar()

    def test_load_rejects_unknown_split(self):
        with pytest.raises(ValueError, match="split must be"):
            load("mnist", split="validation", synthetic_size=8)

    def test_load_splits_are_lazy(self, monkeypatch):
        # The reference only consumes datasets['train']; the test split
        # must not be synthesized/read until touched.
        import tpu_dist.data.sources as sources
        calls = []
        real = sources._one_split

        def spy(name, split, *a, **kw):
            calls.append(split)
            return real(name, split, *a, **kw)

        monkeypatch.setattr(sources, "_one_split", spy)
        datasets, info = load(with_info=True, name="mnist",
                              synthetic_size=8)
        assert calls == []
        assert info.splits["train"].num_examples == 8
        assert calls == ["train"]
        # A pure info query is not "serving": synthetic stays False until
        # a Dataset is actually handed out.
        assert not info.synthetic
        next(iter(datasets["train"]))
        assert calls == ["train"]  # cached, not rebuilt
        assert info.synthetic
        datasets["test"]
        assert calls == ["train", "test"]
        with pytest.raises(KeyError):
            datasets["validation"]


class TestDistributedDelivery:
    def test_off_policy_batches_shard_across_local_devices(self, eight_devices):
        from tpu_dist.parallel import MirroredStrategy

        strategy = MirroredStrategy()
        options = Options()
        options.experimental_distribute.auto_shard_policy = AutoShardPolicy.OFF
        ds = (load("mnist", "train", synthetic_size=256)
              .map(lambda x, y: (x.astype(np.float32) / 255.0, y))
              .batch(128)
              .with_options(options))
        dist = DistributedDataset(ds, strategy)
        xb, yb = next(iter(dist))
        assert xb.shape == (128, 28, 28, 1)
        assert len(xb.addressable_shards) == 8
        assert xb.addressable_shards[0].data.shape == (16, 28, 28, 1)

    def test_experimental_distribute_dataset_single_process(self, eight_devices):
        from tpu_dist.parallel import MirroredStrategy

        strategy = MirroredStrategy()
        ds = _range_ds(32).map(lambda i: np.float32(i)).batch(16)
        dist = strategy.experimental_distribute_dataset(ds)
        batches = list(dist)
        # Single process: AUTO -> DATA over 1 shard = identity.
        assert len(batches) == 2
        assert batches[0].shape == (16,)

    def test_indivisible_local_batch_raises(self, eight_devices):
        from tpu_dist.parallel import MirroredStrategy

        strategy = MirroredStrategy()
        ds = _range_ds(12).batch(6)  # 6 % 8 != 0
        dist = DistributedDataset(ds, strategy,
                                  policy=AutoShardPolicy.OFF)
        with pytest.raises(ValueError, match="local device"):
            next(iter(dist))


class TestPipelineRobustness:
    """Regression tests for pipeline concurrency/lifecycle hazards."""

    def test_cache_interleaved_iterators_no_deadlock(self):
        import itertools

        ds = _range_ds(6).cache()
        pairs = list(itertools.islice(zip(iter(ds), iter(ds)), 6))
        assert [a for a, _ in pairs] == list(range(6))
        assert [b for _, b in pairs] == list(range(6))

    def test_cache_partial_pass_does_not_corrupt(self):
        import itertools

        ds = _range_ds(5).cache()
        assert list(itertools.islice(iter(ds), 2)) == [0, 1]  # abandoned pass
        assert list(ds) == [0, 1, 2, 3, 4]
        assert list(ds) == [0, 1, 2, 3, 4]  # served from a clean cache

    def test_unseeded_no_reshuffle_replays_same_order(self):
        ds = _range_ds(32).shuffle(32, reshuffle_each_iteration=False)
        first = list(ds)
        assert list(ds) == first
        assert sorted(first) == list(range(32))

    def test_prefetch_abandoned_consumer_releases_thread(self):
        import itertools
        import threading
        import time

        before = threading.active_count()
        for _ in range(5):
            it = iter(_range_ds(1000).prefetch(2))
            list(itertools.islice(it, 3))
            it.close()  # consumer walks away mid-stream
        time.sleep(0.3)  # producers notice stop and exit
        assert threading.active_count() <= before + 1


class TestAvgPoolSamePadding:
    def test_same_padding_counts_valid_elements_only(self):
        # Keras semantics: border windows average over real pixels, not
        # padded zeros.
        import jax.numpy as jnp

        from tpu_dist.models import AveragePooling2D

        layer = AveragePooling2D(pool_size=2, padding="same")
        x = jnp.ones((1, 3, 3, 1))
        params, state, out_shape = layer.init(None, (3, 3, 1))
        y, _ = layer.apply(params, state, x)
        assert out_shape == (2, 2, 1)
        np.testing.assert_allclose(np.asarray(y)[0, :, :, 0], np.ones((2, 2)))


class TestRecompile:
    def test_recompile_preserves_trained_weights(self, eight_devices):
        import tpu_dist as td
        from tpu_dist.models import Dense, Sequential
        from tpu_dist.ops import SGD, SparseCategoricalCrossentropy

        s = td.MirroredStrategy()
        with s.scope():
            model = Sequential([Dense(4)], input_shape=(4,))
            model.compile(loss=SparseCategoricalCrossentropy(from_logits=True),
                          optimizer=SGD(0.1))
        x = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
        y = (x.sum(-1) > 0).astype(np.int64)
        ds = Dataset.from_tensor_slices((x, y)).batch(32)
        model.fit(ds, epochs=2, verbose=0)
        before = model.predict(x[:8])
        with s.scope():
            model.compile(loss=SparseCategoricalCrossentropy(from_logits=True),
                          optimizer=SGD(0.001))  # fine-tune at lower lr
        after = model.predict(x[:8])
        np.testing.assert_allclose(before, after, rtol=1e-6)


class TestReplicatedDeterminismGuard:
    """ADVICE r4: when the data axis doesn't span all processes,
    same-data-coordinate processes must produce byte-identical streams on
    EVERY path (OFF, autoshard, ctx-function) — a detected unseeded shuffle
    is rejected, anything else warns."""

    def test_unseeded_shuffle_rejected(self):
        from tpu_dist.data.distribute import require_replicated_determinism

        ds = _range_ds(32).shuffle(8).batch(4)
        with pytest.raises(ValueError, match="unseeded shuffle"):
            require_replicated_determinism(ds, 1, 2, "AutoShardPolicy.DATA")

    def test_seeded_shuffle_warns_only(self, caplog):
        import logging

        from tpu_dist.data.distribute import require_replicated_determinism

        ds = _range_ds(32).shuffle(8, seed=5).batch(4)
        with caplog.at_level(logging.WARNING, logger="tpu_dist.data"):
            require_replicated_determinism(ds, 1, 2, "AutoShardPolicy.DATA")
        assert any("identical batches" in r.message for r in caplog.records)

    def test_spanning_data_axis_is_silent(self, caplog):
        import logging

        from tpu_dist.data.distribute import require_replicated_determinism

        ds = _range_ds(32).shuffle(8).batch(4)  # unseeded is FINE here
        with caplog.at_level(logging.WARNING, logger="tpu_dist.data"):
            require_replicated_determinism(ds, 2, 2, "AutoShardPolicy.OFF")
        assert not caplog.records

    def test_sharded_path_guarded(self, eight_devices, monkeypatch):
        # Simulate a pipe-spanning mesh: 2 processes, 1 data shard. The
        # AUTO/DATA branch must reject the unseeded shuffle, not just OFF.
        import jax

        from tpu_dist.parallel import MirroredStrategy

        strategy = MirroredStrategy()
        monkeypatch.setattr(type(strategy), "input_shard_info",
                            lambda self: (1, 0))
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        ds = _range_ds(32).shuffle(8).batch(4)
        with pytest.raises(ValueError, match="unseeded shuffle"):
            DistributedDataset(ds, strategy, policy=AutoShardPolicy.DATA)

    def test_ctx_function_path_guarded(self, eight_devices, monkeypatch):
        import jax

        from tpu_dist.parallel import MirroredStrategy

        strategy = MirroredStrategy()
        monkeypatch.setattr(type(strategy), "input_shard_info",
                            lambda self: (1, 0))
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        with pytest.raises(ValueError, match="unseeded shuffle"):
            strategy.distribute_datasets_from_function(
                lambda ctx: _range_ds(32).shuffle(8).batch(4))

    def test_auto_seeded_non_reshuffling_rejected(self):
        # code-review r5: shuffle(8, reshuffle_each_iteration=False) draws
        # its fixed seed independently PER PROCESS — just as divergent as
        # seed=None, and the spec records auto_seeded so the guard sees it.
        from tpu_dist.data.distribute import require_replicated_determinism

        ds = _range_ds(32).shuffle(
            8, reshuffle_each_iteration=False).batch(4)
        with pytest.raises(ValueError, match="unseeded shuffle"):
            require_replicated_determinism(ds, 1, 2, "AutoShardPolicy.OFF")

    def test_shuffle_replays_through_file_autoshard(self):
        # code-review r5 regression: the auto_seeded record-only marker
        # must not leak into _replay_transform's kwargs — FILE autoshard
        # replays every recorded transform over the sharded file set.
        ds = _range_ds(32).shuffle(8, seed=3)
        replayed = ds._replay_transform(ds._transform)
        assert sorted(int(v) for v in replayed) == list(range(32))
