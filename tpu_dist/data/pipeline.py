"""Host-side input pipeline: a tf.data-shaped Dataset for per-host delivery.

Re-provides the input-pipeline surface the reference exercises (SURVEY.md D13,
§3.4): ``map`` / ``cache`` / ``shuffle`` / ``batch`` combinators
(tf_dist_example.py:20-33), ``from_tensor_slices`` for numpy data
(README.md:121-129), and ``Options`` carrying
``experimental_distribute.auto_shard_policy`` (tf_dist_example.py:34-37) with
TF's enum values (tf:python/data/ops/options.py:89-116).

TPU-native stance: the input pipeline is *host-side numpy* — TPU sees only the
assembled global batch (``tpu_dist.data.distribute``). There is no graph of
dataset ops to rewrite; the autoshard policy that TF implements as a C++
Grappler pass over the dataset graph (auto_shard.cc) becomes a plain index
transformation in ``tpu_dist.data.sharding``. Shuffling is buffer-based with
the same semantics as tf.data's ``shuffle(buffer_size)``: an *unseeded* shuffle
draws a fresh order per iteration/worker — load-bearing for the reference's
OFF-policy mode where every worker iterates an independently-shuffled full
stream (README.md:113-120, SURVEY.md §3.4).
"""

from __future__ import annotations

import enum
import itertools
import queue as queue_lib
import threading
from typing import Any, Callable, Iterable, Iterator, Sequence

import numpy as np


class AutoShardPolicy(enum.IntEnum):
    """TF ``tf.data.experimental.AutoShardPolicy`` values
    (tf:python/data/ops/options.py:89-116). The reference sets OFF
    (tf_dist_example.py:35)."""

    OFF = -1
    AUTO = 0
    FILE = 1
    DATA = 2
    HINT = 3


class _DistributeOptions:
    """Mirror of ``options.experimental_distribute`` attribute shape."""

    def __init__(self) -> None:
        self.auto_shard_policy = AutoShardPolicy.AUTO

    def __repr__(self) -> str:
        return f"_DistributeOptions(auto_shard_policy={self.auto_shard_policy!r})"


class Options:
    """Dataset options — the subset the reference uses: the auto-shard policy
    (tf_dist_example.py:34-35: ``options.experimental_distribute
    .auto_shard_policy = AutoShardPolicy.OFF``)."""

    def __init__(self) -> None:
        self.experimental_distribute = _DistributeOptions()

    def __repr__(self) -> str:
        return f"Options({self.experimental_distribute!r})"


def _map_structure(fn, element):
    if isinstance(element, tuple):
        return tuple(_map_structure(fn, e) for e in element)
    if isinstance(element, dict):
        return {k: _map_structure(fn, v) for k, v in element.items()}
    return fn(element)


def _combine_structure(elements: Sequence, combine) -> Any:
    """Recurse a list of identically-structured elements down to leaves and
    merge each leaf list with ``combine`` (np.stack to batch, np.concatenate
    to rebatch)."""
    first = elements[0]
    if isinstance(first, tuple):
        return tuple(_combine_structure([e[i] for e in elements], combine)
                     for i in range(len(first)))
    if isinstance(first, dict):
        return {k: _combine_structure([e[k] for e in elements], combine)
                for k in first}
    return combine([np.asarray(e) for e in elements])


def _batch_structure(elements: Sequence) -> Any:
    """Stack a list of identically-structured elements into batched arrays."""
    return _combine_structure(elements, np.stack)


def _concat_structure(elements: Sequence) -> Any:
    """Concatenate already-batched elements along their leading dim."""
    return _combine_structure(elements, np.concatenate)


class Dataset:
    """A lazily-evaluated element pipeline (host-side, numpy).

    Built from a factory returning a fresh iterator per epoch — iterating a
    Dataset twice replays the source (and re-randomizes unseeded shuffles),
    matching tf.data re-iteration semantics the reference relies on for its
    per-worker independent shuffles (SURVEY.md §3.4).
    """

    def __init__(self, it_factory: Callable[[], Iterator], *,
                 options: Options | None = None,
                 cardinality: int | None = None,
                 num_files: int = 1):
        self._it_factory = it_factory
        self._options = options or Options()
        self._cardinality = cardinality
        self._prefetched = False  # set by prefetch(); read by DistributedDataset
        #: Source-file count, drives AutoShardPolicy.FILE/AUTO decisions
        #: (TF autoshards by file when the source has files, auto_shard.cc).
        self.num_files = num_files
        # Chain-rewrite metadata (the FILE-autoshard path, sharding.py): each
        # derived dataset records its parent and a (name, kwargs) transform
        # descriptor so the chain can be replayed onto a re-rooted source —
        # the element-stream analog of TF's Grappler auto_shard graph rewrite
        # pushing the shard op down to the file reader (auto_shard.cc).
        self._parent: "Dataset | None" = None
        self._transform: tuple[str, dict] | None = None
        #: Set on file-backed sources (from_files): (num_shards, index) -> a
        #: new source Dataset over the strided file subset.
        self._file_shard_fn: Callable[[int, int], "Dataset"] | None = None
        #: In-memory source arrays (from_tensor_slices) — lets the
        #: vectorized chain rewrite (data/vectorize.py) execute the whole
        #: combinator chain as index math + batched gathers.
        self._tensor_source = None
        #: Optional jittable fn applied to the PLACED x batch inside the
        #: compiled step (trainer plumbing): lets a pipeline ship compact
        #: wire dtypes (uint8) and run normalization on device, where it
        #: fuses into the step for free (SURVEY hard-part #5; the H2D link
        #: is the scarce resource).
        self._device_transform: Callable | None = None

    # -- constructors --------------------------------------------------------

    @staticmethod
    def from_tensor_slices(tensors) -> "Dataset":
        """Elements are slices along the leading axis — the README.md:121-129
        numpy-conversion path."""
        arrays = _map_structure(np.asarray, tensors)
        leaves = []
        _map_structure(leaves.append, arrays)
        if not leaves:
            raise ValueError("from_tensor_slices requires at least one array")
        n = len(leaves[0])
        for leaf in leaves:
            if len(leaf) != n:
                raise ValueError(
                    f"all arrays must share the leading dim, got {len(leaf)} != {n}")

        def factory():
            for i in range(n):
                yield _map_structure(lambda a: a[i], arrays)

        ds = Dataset(factory, cardinality=n)
        ds._tensor_source = arrays
        return ds

    @staticmethod
    def from_generator(gen_factory: Callable[[], Iterable]) -> "Dataset":
        return Dataset(lambda: iter(gen_factory()))

    @staticmethod
    def from_files(files: Sequence, reader: Callable[[Any], Iterable], *,
                   cardinality: int | None = None,
                   file_cardinalities: Sequence[int] | None = None) -> "Dataset":
        """A file-backed source: elements are ``reader(file)``'s, file by file,
        in the given order. This is the source shape AutoShardPolicy.FILE
        strides across workers (SURVEY.md D13; TF shards the file list in
        auto_shard.cc when the source is file-based).

        ``file_cardinalities`` (per-file element counts, when known) lets a
        FILE-sharded worker subset keep a known cardinality — without it the
        subset's cardinality is unknown and ``fit`` needs an explicit
        ``steps_per_epoch``."""
        files = list(files)
        if not files:
            raise ValueError("from_files requires at least one file")
        if file_cardinalities is not None:
            file_cardinalities = list(file_cardinalities)
            if len(file_cardinalities) != len(files):
                raise ValueError(
                    f"file_cardinalities has {len(file_cardinalities)} "
                    f"entries for {len(files)} files")
            total = sum(file_cardinalities)
            if cardinality is None:
                cardinality = total
            elif cardinality != total:
                raise ValueError(
                    f"cardinality {cardinality} != sum(file_cardinalities) "
                    f"{total}")

        def factory():
            for f in files:
                yield from reader(f)

        ds = Dataset(factory, cardinality=cardinality, num_files=len(files))
        #: Per-file counts (when known) let the FILE-shard guard verify each
        #: worker's strided subset carries the SAME total element count —
        #: equal file counts alone don't guarantee equal streams.
        ds._file_cardinalities = file_cardinalities
        # TF strides the file list across workers (worker i reads files
        # i, i+n, i+2n, ...); the subset source keeps its own file count and
        # (when per-file counts are known) its own cardinality.
        ds._file_shard_fn = lambda n, i: Dataset.from_files(
            files[i::n], reader,
            file_cardinalities=(None if file_cardinalities is None
                                else file_cardinalities[i::n]))
        return ds

    @staticmethod
    def range(n: int) -> "Dataset":
        return Dataset(lambda: iter(range(n)), cardinality=n)

    # -- combinators (each returns a new Dataset; reference set at
    #    tf_dist_example.py:20-37) -------------------------------------------

    def map(self, fn: Callable) -> "Dataset":
        def factory():
            for el in self._it_factory():
                yield fn(*el) if isinstance(el, tuple) else fn(el)

        return self._derive(factory, transform=("map", {"fn": fn}))

    def filter(self, predicate: Callable) -> "Dataset":
        def factory():
            for el in self._it_factory():
                keep = predicate(*el) if isinstance(el, tuple) else predicate(el)
                if keep:
                    yield el

        return self._derive(factory, cardinality=None,
                            transform=("filter", {"predicate": predicate}))

    def cache(self) -> "Dataset":
        """Materialize on first full pass; later passes replay the cache
        (tf_dist_example.py:30 uses this to avoid re-decoding MNIST).

        Only a COMPLETE pass publishes the cache: a partially-consumed or
        concurrent iterator never corrupts it (it just re-reads the source),
        and no lock is held across yields."""
        store: list = []
        complete = threading.Event()
        lock = threading.Lock()

        def factory():
            if complete.is_set():
                yield from store
                return
            local: list = []
            for el in self._it_factory():
                local.append(el)
                yield el
            with lock:
                if not complete.is_set():
                    store.extend(local)
                    complete.set()

        return self._derive(factory, transform=("cache", {}))

    def shuffle(self, buffer_size: int, seed: int | None = None,
                reshuffle_each_iteration: bool = True) -> "Dataset":
        """Buffer-based shuffle with tf.data semantics: fill a buffer of
        ``buffer_size``, emit a random occupant, refill. Unseeded => each
        iteration (and each worker process) draws an independent order — the
        property the reference's OFF-policy mode depends on (README.md:113-120).
        """
        if buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")
        auto_seeded = seed is None  # recorded: an auto-drawn seed is still
        # process-divergent (each process draws its own), which the
        # replicated-determinism guard must treat as unseeded.
        if seed is None and not reshuffle_each_iteration:
            # tf.data semantics: an unseeded non-reshuffling dataset picks one
            # random seed at construction and replays that order every pass.
            seed = int(np.random.default_rng().integers(2**31))
        epoch_counter = itertools.count()

        def factory():
            it = self._it_factory()
            epoch = next(epoch_counter)
            if seed is None:
                rng = np.random.default_rng()
            else:
                rng = np.random.default_rng(
                    seed + (epoch if reshuffle_each_iteration else 0))
            buf = list(itertools.islice(it, buffer_size))
            for el in it:
                idx = rng.integers(len(buf))
                out, buf[idx] = buf[idx], el
                yield out
            rng.shuffle(buf)
            yield from buf

        return self._derive(
            factory,
            transform=("shuffle",
                       {"buffer_size": buffer_size, "seed": seed,
                        "auto_seeded": auto_seeded,
                        "reshuffle_each_iteration": reshuffle_each_iteration}))

    def batch(self, batch_size: int, drop_remainder: bool = False) -> "Dataset":
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")

        def factory():
            acc = []
            for el in self._it_factory():
                acc.append(el)
                if len(acc) == batch_size:
                    yield _batch_structure(acc)
                    acc = []
            if acc and not drop_remainder:
                yield _batch_structure(acc)

        card = None
        if self._cardinality is not None:
            card = (self._cardinality // batch_size if drop_remainder
                    else -(-self._cardinality // batch_size))
        return self._derive(
            factory, cardinality=card,
            transform=("batch", {"batch_size": batch_size,
                                 "drop_remainder": drop_remainder}))

    def repeat(self, count: int | None = None) -> "Dataset":
        def factory():
            n = 0
            while count is None or n < count:
                it = self._it_factory()
                empty = True
                for el in it:
                    empty = False
                    yield el
                if empty:
                    return
                n += 1

        card = None
        if count is not None and self._cardinality is not None:
            card = count * self._cardinality
        return self._derive(factory, cardinality=card,
                            transform=("repeat", {"count": count}))

    def take(self, count: int) -> "Dataset":
        def factory():
            yield from itertools.islice(self._it_factory(), count)

        # Unknown source cardinality stays unknown: the source may yield fewer
        # than ``count`` elements (tf.data likewise keeps UNKNOWN_CARDINALITY).
        card = None if self._cardinality is None else min(count, self._cardinality)
        return self._derive(factory, cardinality=card,
                            transform=("take", {"count": count}))

    def interleave(self, map_func: Callable, cycle_length: int = 4,
                   block_length: int = 1) -> "Dataset":
        """tf.data's ``Dataset.interleave``: map each element to a Dataset
        and consume the resulting streams round-robin — ``block_length``
        elements at a time from ``cycle_length`` concurrently-open streams.
        The standard shape for mixing multiple file readers."""
        if cycle_length < 1 or block_length < 1:
            raise ValueError("cycle_length and block_length must be >= 1")

        def factory():
            source = self._it_factory()

            def new_stream():
                try:
                    el = next(source)
                except StopIteration:
                    return None
                return iter(map_func(*el) if isinstance(el, tuple)
                            else map_func(el))

            slots: list = []
            while len(slots) < cycle_length:
                s = new_stream()
                if s is None:
                    break
                slots.append(s)
            # tf.data ordering (InterleaveDataset kernel): when a stream
            # ends mid-block, advance to the NEXT cycle slot immediately;
            # the emptied slot opens its replacement stream only when the
            # round-robin cycle returns to it. (None marks an empty slot
            # awaiting lazy refill.)
            i = 0
            while slots:
                if i >= len(slots):
                    i = 0
                if slots[i] is None:
                    repl = new_stream()
                    if repl is None:
                        slots.pop(i)
                        continue
                    slots[i] = repl
                emitted = 0
                while emitted < block_length:
                    try:
                        yield next(slots[i])
                        emitted += 1
                    except StopIteration:
                        slots[i] = None
                        break
                i += 1

        return self._derive(
            factory, cardinality=None,
            transform=("interleave", {"map_func": map_func,
                                      "cycle_length": cycle_length,
                                      "block_length": block_length}))

    def skip(self, count: int) -> "Dataset":
        """Drop the first ``count`` elements — tf.data's ``Dataset.skip``."""
        def factory():
            yield from itertools.islice(self._it_factory(), count, None)

        card = (None if self._cardinality is None
                else max(0, self._cardinality - count))
        return self._derive(factory, cardinality=card,
                            transform=("skip", {"count": count}))

    def unbatch(self) -> "Dataset":
        """Split each batched element back into per-example elements —
        tf.data's ``Dataset.unbatch`` (leading dim must agree across the
        element's components)."""
        def first_leaf(el):
            if isinstance(el, tuple):
                return first_leaf(el[0])
            if isinstance(el, dict):
                return first_leaf(next(iter(el.values())))
            return el

        def factory():
            for el in self._it_factory():
                n = len(np.asarray(first_leaf(el)))
                for i in range(n):
                    yield _map_structure(lambda a: np.asarray(a)[i], el)

        return self._derive(factory, cardinality=None,
                            transform=("unbatch", {}))

    def concatenate(self, other: "Dataset") -> "Dataset":
        """This dataset's elements, then ``other``'s — tf.data's
        ``Dataset.concatenate``."""
        def factory():
            yield from self._it_factory()
            yield from iter(other)

        card = None
        other_card = other.cardinality()
        if (self._cardinality is not None and other_card is not None
                and other_card >= 0):
            card = self._cardinality + other_card
        # transform=None: replaying concatenate through the FILE-autoshard
        # chain rewrite would append the FULL `other` to every worker's file
        # shard (duplicated data); opaque forces the DATA fallback instead.
        return self._derive(factory, cardinality=card, transform=None)

    @staticmethod
    def zip(*datasets: "Dataset") -> "Dataset":
        """Element-wise tuples across datasets, stopping at the shortest —
        tf.data's ``Dataset.zip`` (accepts ``Dataset.zip((a, b))`` too)."""
        if len(datasets) == 1 and isinstance(datasets[0], (tuple, list)):
            datasets = tuple(datasets[0])
        if not datasets:
            raise ValueError("zip needs at least one dataset")

        def factory():
            its = [iter(d) for d in datasets]
            while True:
                row = []
                for it in its:
                    try:
                        row.append(next(it))
                    except StopIteration:
                        return
                yield tuple(row)

        cards = [d.cardinality() for d in datasets]
        card = (min(c for c in cards) if all(
            c is not None and c >= 0 for c in cards) else None)
        # Keep the first input's options (shard policy etc.) — a raw Dataset
        # would silently reset auto_shard_policy to AUTO.
        first_opts = getattr(datasets[0], "_options", None)
        return Dataset(factory, options=first_opts, cardinality=card)

    def shard(self, num_shards: int, index: int) -> "Dataset":
        """Every ``num_shards``-th element starting at ``index`` — tf.data's
        ``Dataset.shard``, the primitive DATA autosharding lowers to."""
        if not 0 <= index < num_shards:
            raise ValueError(f"index {index} not in [0, {num_shards})")

        def factory():
            yield from itertools.islice(self._it_factory(), index, None, num_shards)

        card = None
        if self._cardinality is not None:
            card = (self._cardinality - index + num_shards - 1) // num_shards
        return self._derive(factory, cardinality=card,
                            transform=("shard", {"num_shards": num_shards,
                                                 "index": index}))

    def prefetch(self, buffer_size: int = 2) -> "Dataset":
        """Background-thread prefetch, keeping host input off the step critical
        path (SURVEY.md §3.4 'cache+prefetch keep it off the critical path')."""
        if buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")

        def factory():
            q: queue_lib.Queue = queue_lib.Queue(maxsize=buffer_size)
            stop = threading.Event()
            _SENTINEL = object()

            def _put(item) -> bool:
                # Bounded put that gives up when the consumer abandoned the
                # iterator (e.g. evaluate(steps=N) breaking early) — otherwise
                # the producer thread would block forever and pin the upstream
                # pipeline.
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        return True
                    except queue_lib.Full:
                        continue
                return False

            def producer():
                try:
                    for el in self._it_factory():
                        if not _put(el):
                            return
                except BaseException as e:  # propagate into the consumer
                    _put((_SENTINEL, e))
                    return
                _put((_SENTINEL, None))

            t = threading.Thread(target=producer, daemon=True)
            t.start()
            try:
                # The producer's BaseException handler guarantees a sentinel
                # arrives even when it dies, so this get() always terminates.
                while True:  # shardcheck: disable=SC502 -- sentinel-bounded
                    item = q.get()
                    if (isinstance(item, tuple) and len(item) == 2
                            and item[0] is _SENTINEL):
                        if item[1] is not None:
                            raise item[1]
                        return
                    yield item
            finally:
                stop.set()

        ds = self._derive(factory,
                          transform=("prefetch", {"buffer_size": buffer_size}))
        ds._prefetched = True  # lets DistributedDataset skip double-wrapping
        return ds

    def with_options(self, options: Options) -> "Dataset":
        """Attach options — the reference's auto-shard-policy carrier
        (tf_dist_example.py:37)."""
        ds = self._derive(self._it_factory,
                          transform=("with_options", {"options": options}))
        ds._options = options
        return ds

    # -- introspection -------------------------------------------------------

    @property
    def options(self) -> Options:
        return self._options

    @property
    def auto_shard_policy(self) -> AutoShardPolicy:
        return self._options.experimental_distribute.auto_shard_policy

    def cardinality(self) -> int | None:
        """Element count if statically known, else None (unknown)."""
        return self._cardinality

    def __iter__(self) -> Iterator:
        return self._it_factory()

    def as_numpy_iterator(self) -> Iterator:
        return iter(self)

    def _derive(self, factory, cardinality: int | None = "inherit",
                transform: tuple[str, dict] | None = None) -> "Dataset":  # type: ignore[assignment]
        ds = Dataset(
            factory,
            options=self._options,
            cardinality=(self._cardinality if cardinality == "inherit"
                         else cardinality),
            num_files=self.num_files,
        )
        ds._parent = self
        ds._transform = transform
        # A prefetch anywhere upstream keeps the chain marked, so the
        # DistributedDataset default wrap never double-buffers.
        ds._prefetched = self._prefetched
        # The device transform composes AFTER placement, so it survives
        # only stream-shape ops; an element transform (map/filter/...)
        # would otherwise see the compact wire dtype AND still get the
        # deferred scale applied on top of its own output.
        if transform is not None and transform[0] in (
                "prefetch", "with_options", "repeat", "take", "skip",
                "shard", "batch"):
            ds._device_transform = self._device_transform
        return ds

    def _replay_transform(self, transform: tuple[str, dict]) -> "Dataset":
        """Apply a recorded (name, kwargs) transform descriptor to this
        dataset — used by the FILE-autoshard chain rewrite (sharding.py)."""
        name, kw = transform
        if name == "with_options":
            return self.with_options(kw["options"])
        # Drop record-only markers that are not combinator kwargs (the
        # auto_seeded flag the replicated-determinism guard reads).
        kw = {k: v for k, v in kw.items() if k != "auto_seeded"}
        return getattr(self, name)(**kw)


class DevicePrefetcher:
    """Double-buffered host→device input: a bounded background stage over an
    iterator of ALREADY device-placing batches (``iter(DistributedDataset)``
    runs ``strategy.distribute_batch`` — i.e. the ``device_put`` — inside
    ``next()``, so moving the iteration onto this producer thread moves the
    transfer off the training hot loop). While step k executes, up to
    ``depth`` later batches are fetched and placed; the trainer's measured
    ``data_wait_s`` collapses to a queue pop.

    Same bounded-queue discipline as :meth:`Dataset.prefetch`: the producer
    polls a stop event on every put so :meth:`close` (epoch-loop exit,
    ``StopTraining``, preemption drain) never leaves a thread blocked on a
    full queue. ``close()`` stops the producer, drains in-flight items, and
    joins the thread — the no-leaked-threads teardown contract
    (tests/test_step_perf.py).

    Observability (host-side only): ``data.prefetch.hits`` / ``.misses``
    counters (was the next batch already buffered when the trainer asked?)
    and a ``data.prefetch.depth`` gauge of the buffered count — all through
    :mod:`tpu_dist.observe.metrics`, so a disabled registry pays one flag
    check.
    """

    def __init__(self, it: Iterator, *, depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self.hits = 0
        self.misses = 0
        self._q: queue_lib.Queue = queue_lib.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._exhausted = False
        self._thread = threading.Thread(
            target=self._produce, args=(it,), daemon=True,
            name="tpu-dist-device-prefetch")
        self._thread.start()

    _SENTINEL = object()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue_lib.Full:
                continue
        return False

    def _produce(self, it: Iterator) -> None:
        try:
            for batch in it:
                if not self._put((batch, None)):
                    return
        except BaseException as e:  # propagate into the consumer
            self._put((self._SENTINEL, e))
            return
        self._put((self._SENTINEL, None))

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self):
        from tpu_dist.observe import metrics

        if self._exhausted:
            raise StopIteration
        buffered = self._q.qsize()
        metrics.set_gauge("data.prefetch.depth", buffered)
        item, err = self._q.get()
        if item is self._SENTINEL:
            self._exhausted = True
            if err is not None:
                raise err
            raise StopIteration
        # Count hit/miss only for real batches — the terminal sentinel
        # fetch is bookkeeping, so hits + misses == batches delivered.
        if buffered > 0:
            self.hits += 1
            metrics.inc("data.prefetch.hits")
        else:
            self.misses += 1
            metrics.inc("data.prefetch.misses")
        return item

    @property
    def closed(self) -> bool:
        """True once close() has fully torn down the producer thread."""
        return self._stop.is_set() and not self._thread.is_alive()

    def close(self, timeout: float = 5.0) -> None:
        """Stop the producer, drain in-flight batches, join the thread.
        Idempotent; safe mid-stream (the batches dropped here were
        speculative — exactly the teardown a preemption drain needs)."""
        self._stop.set()
        self._exhausted = True
        # Drain so a producer blocked in put() observes the stop event and
        # exits its poll loop promptly.
        while True:
            try:
                self._q.get_nowait()
            except queue_lib.Empty:
                break
        self._thread.join(timeout=timeout)
