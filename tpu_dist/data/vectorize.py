"""Vectorized execution of combinator chains over in-memory sources.

The reference's input pipeline is rewritten by TF's C++ Grappler passes
(map-and-batch fusion, map vectorization — auto_shard.cc's siblings in
tensorflow/core/grappler/optimizers/data/); tpu-dist's Datasets instead
record each combinator as chain metadata (pipeline.py ``_parent`` /
``_transform``), and this module is the rewrite pass over that chain.

For a chain of the shape the reference builds (tf_dist_example.py:20-33)

    from_tensor_slices -> map(fn)* -> cache? -> shuffle -> batch [-> repeat
        / take / skip / prefetch / with_options]

the per-element generator walk (one Python frame per example, one
``np.stack`` of B tiny arrays per batch) is replaced by *index math plus
batched gathers*:

* the shuffle runs over an ``int64`` index array with the SAME buffer
  algorithm and rng construction as ``Dataset.shuffle`` (seeded chains stay
  bit-identical; unseeded full-buffer shuffles collapse to one
  ``rng.shuffle``, which is also the element path's exact call sequence);
* each batch is one fancy-index gather (C memcpy) instead of B element
  yields + ``np.stack``;
* ``map`` functions are PROBED for safety — a function is only vectorized
  if applying it to a 2-element batch reproduces the stacked per-element
  results exactly, and applying it twice is deterministic; anything else
  (stateful augmentations, shape-bending fns) falls back to the untouched
  element path;
* a map that probes as pure uint8 normalization (``astype(float32) * k``)
  is FUSED into the gather via the native C++ loader
  (``native.gather_scale``) — and on non-CPU backends the normalization is
  deferred to the device entirely (``Dataset._device_transform``): the
  batch crosses the host->device link as uint8 (4x fewer bytes on the
  job's scarcest resource) and the scale fuses into the compiled step.

``try_rewrite`` returns None whenever ANY link of the chain is outside the
supported grammar — correctness never depends on the rewrite firing.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable

import numpy as np

from tpu_dist.data.pipeline import Dataset, _map_structure

logger = logging.getLogger("tpu_dist.data")

#: Pre-batch ops the index/value planner understands.
_PRE_BATCH = {"map", "cache", "shuffle", "skip", "take", "shard"}
#: Post-batch ops replayable on the batch stream.
_POST_BATCH = {"repeat", "take", "skip", "shard", "prefetch", "with_options"}


def enabled() -> bool:
    return os.environ.get("TPU_DIST_VECTORIZE", "").strip() != "0"


# -- chain parsing ------------------------------------------------------------


def _collect_chain(ds: Dataset):
    """(source Dataset, [transform (name, kwargs) source->sink]) or None."""
    steps: list[tuple[str, dict]] = []
    node = ds
    while node is not None:
        if getattr(node, "_tensor_source", None) is not None:
            return node, list(reversed(steps))
        t = node._transform
        if t is None:
            return None
        steps.append(t)
        node = node._parent
    return None


def _parse(ds: Dataset):
    """Split a supported chain into (pre-batch ops, batch kwargs,
    post-batch ops); None when outside the grammar."""
    got = _collect_chain(ds)
    if got is None:
        return None
    source, steps = got
    pre: list[tuple[str, dict]] = []
    post: list[tuple[str, dict]] = []
    batch_kw = None
    for name, kw in steps:
        if batch_kw is None:
            if name == "batch":
                batch_kw = kw
            elif name in _PRE_BATCH:
                pre.append((name, kw))
            else:
                return None
        else:
            if name in _POST_BATCH:
                post.append((name, kw))
            else:
                return None
    if batch_kw is None:
        return None
    # One shuffle, never behind a cache (cache-after-shuffle freezes the
    # first pass's order — semantics the index planner doesn't reproduce).
    shuffle_seen = False
    for name, _ in pre:
        if name == "shuffle":
            if shuffle_seen:
                return None
            shuffle_seen = True
        if name == "cache" and shuffle_seen:
            return None
    return source, pre, batch_kw, post


# -- map probing --------------------------------------------------------------


def _apply_fn(fn: Callable, el):
    return fn(*el) if isinstance(el, tuple) else fn(el)


def _leaves(el) -> list[np.ndarray]:
    out: list[np.ndarray] = []
    _map_structure(lambda a: out.append(np.asarray(a)), el)
    return out


def _same(a, b) -> bool:
    la, lb = _leaves(a), _leaves(b)
    if len(la) != len(lb):
        return False
    return all(x.dtype == y.dtype and x.shape == y.shape
               and np.array_equal(x, y) for x, y in zip(la, lb))


def _element(arrays, i: int):
    return _map_structure(lambda a: a[i], arrays)


def _probe_indices(arrays) -> np.ndarray:
    """Adversarial probe sample (ADVICE r4): a 2-element spot check lets a
    value-conditional batch-level fn (``np.where(x.max() > t, ...)`` where
    elements 0-1 stay under t) pass yet diverge once vectorized. Mirror
    ``_detect_scale``: an evenly-spaced sweep of the source plus the first
    occurrence of every distinct value of any small-integer leaf (a
    class/label-conditional fn must reveal itself on some class)."""
    leaves = _leaves(arrays)
    n = len(leaves[0])
    idx = np.linspace(0, n - 1, num=min(n, 32), dtype=np.int64)
    for leaf in leaves:
        if leaf.dtype.kind in "iu" and leaf.ndim <= 2:
            _, first = np.unique(
                leaf.reshape(n, -1)[:, 0], return_index=True)
            idx = np.concatenate([idx, first[:16].astype(np.int64)])
    return np.unique(idx)


def _probe_vectorizable(fn: Callable, arrays) -> bool:
    """fn(batched sample) must equal stack(fn(e_i) for each element) exactly,
    with fn(e_0) repeated for determinism. Exactness matters: elementwise
    math is bit-identical batched or not, while anything order-sensitive
    (reductions, reshapes) or value-conditional at batch level diverges and
    must keep the element path. The sample is adversarial (``_probe_indices``)
    — the rewrite's contract is that correctness never depends on it firing."""
    try:
        idx = _probe_indices(arrays)
        e0 = _element(arrays, int(idx[0]))
        f0a, f0b = _apply_fn(fn, e0), _apply_fn(fn, e0)
        if not _same(f0a, f0b):
            return False  # nondeterministic (random augmentation)
        per_el = [f0a] + [_apply_fn(fn, _element(arrays, int(i)))
                          for i in idx[1:]]
        batched_in = _map_structure(lambda a: np.asarray(a)[idx], arrays)
        got = _apply_fn(fn, batched_in)
        want_leaves = [np.stack(cols)
                       for cols in zip(*(_leaves(r) for r in per_el))]
        got_leaves = _leaves(got)
        return (len(got_leaves) == len(want_leaves)
                and all(g.dtype == w.dtype and g.shape == w.shape
                        and np.array_equal(g, w)
                        for g, w in zip(got_leaves, want_leaves)))
    except Exception:
        return False


def _detect_scale(fns: list[Callable], arrays
                  ) -> tuple[str, float] | None:
    """When the composed maps over a ``(uint8 image, label)`` source are
    exactly ``image.astype(float32) * k`` or ``image.astype(float32) / d``
    with the label untouched, return ``("mul", k)`` / ``("div", d)``.

    The distinction is bit-level: ``x / 255.0`` (the reference's scale fn)
    and ``x * (1/255)`` differ in the last ulp for many inputs, and the
    rewrite's contract is an IDENTICAL stream — so the exact formula is
    detected and replayed, on host or device. None otherwise."""
    if not (isinstance(arrays, tuple) and len(arrays) == 2):
        return None
    images, labels = np.asarray(arrays[0]), np.asarray(arrays[1])
    if images.dtype != np.uint8 or len(images) < 2:
        return None
    try:
        # The scale path DROPS fn for the whole dataset, so the probe must
        # be adversarial, not a 2-element spot check: an evenly-spaced
        # sample, one representative of every distinct label value (a
        # label-conditional fn must reveal itself on some class), and a
        # crafted image cycling all 256 uint8 values (a value-conditional
        # fn — clipping, thresholding — must reveal itself on some pixel).
        n = len(images)
        idx = list(np.linspace(0, n - 1, num=min(n, 64), dtype=np.int64))
        _, first_of_label = np.unique(
            labels.reshape(len(labels), -1)[:, 0], return_index=True)
        idx = np.unique(np.concatenate(
            [idx, first_of_label[:32]]).astype(np.int64))
        probe_x = images[idx]
        probe_y = labels[idx]
        ramp = (np.arange(int(np.prod(images.shape[1:])) or 1,
                          dtype=np.int64) % 256).astype(np.uint8)
        probe_x = np.concatenate(
            [probe_x, ramp.reshape(1, *images.shape[1:])])
        probe_y = np.concatenate([probe_y, labels[idx[:1]]])
        el = (probe_x, probe_y)
        out = el
        for fn in fns:
            out = _apply_fn(fn, out)
        if not (isinstance(out, tuple) and len(out) == 2):
            return None
        oimg, olab = np.asarray(out[0]), np.asarray(out[1])
        if oimg.dtype != np.float32 or oimg.shape != el[0].shape:
            return None
        if not np.array_equal(olab, el[1]):
            return None
        src = el[0].astype(np.float32)
        nz = src > 0
        if not nz.any():
            return None
        s = float(src[nz].flat[0])
        o = float(oimg[nz].flat[0])
        if o == 0.0:
            return None
        k = np.float32(o / s)
        if np.array_equal(oimg, src * k):
            detected = ("mul", float(k))
        else:
            d = np.float32(s / o)
            if not np.array_equal(oimg, src / d):
                return None
            detected = ("div", float(d))
        # The pipeline applies fn per ELEMENT; the formula above was
        # validated against a batched application. Cross-check EVERY probe
        # element singly (ADVICE r4): a label/value-conditional fn that
        # fires per-element but not batched (scalar-label branch) would
        # otherwise validate the wrong reference — and the whole point of
        # the label/ramp representatives is to be run where the branch can
        # trigger.
        for i in range(len(probe_x)):
            single = (probe_x[i], probe_y[i])
            for fn in fns:
                single = _apply_fn(fn, single)
            if not np.array_equal(np.asarray(single[0]), oimg[i]):
                return None
            if not np.array_equal(np.asarray(single[1]), olab[i]):
                return None
        return detected
    except Exception:
        return None


# -- index pipeline -----------------------------------------------------------


def _buffer_shuffle_indices(idx: np.ndarray, buffer_size: int, rng) -> np.ndarray:
    """``Dataset.shuffle``'s buffer algorithm over an index array — same rng
    call sequence, so a seeded chain is bit-identical to the element path."""
    n = len(idx)
    if buffer_size >= n:
        out = list(idx)
        rng.shuffle(out)  # element path: buf = all, one rng.shuffle(buf)
        return np.asarray(out, dtype=idx.dtype)
    out = np.empty(n, dtype=idx.dtype)
    buf = list(idx[:buffer_size])
    k = 0
    for el in idx[buffer_size:]:
        j = int(rng.integers(len(buf)))
        out[k] = buf[j]
        buf[j] = el
        k += 1
    rng.shuffle(buf)
    out[k:] = buf
    return out


class _IndexPlan:
    """Per-epoch index stream for the pre-batch ops."""

    def __init__(self, n: int, pre: list[tuple[str, dict]]):
        self.n = n
        self.ops = [(name, kw) for name, kw in pre if name != "map"
                    and name != "cache"]

    def epoch(self, epoch_no: int) -> np.ndarray:
        idx = np.arange(self.n, dtype=np.int64)
        for name, kw in self.ops:
            if name == "shuffle":
                seed = kw["seed"]
                if seed is None:
                    rng = np.random.default_rng()
                else:
                    rng = np.random.default_rng(
                        seed + (epoch_no if kw["reshuffle_each_iteration"]
                                else 0))
                idx = _buffer_shuffle_indices(idx, kw["buffer_size"], rng)
            elif name == "skip":
                idx = idx[kw["count"]:]
            elif name == "take":
                idx = idx[:kw["count"]]
            elif name == "shard":
                idx = idx[kw["index"]::kw["num_shards"]]
        return idx


# -- the rewrite --------------------------------------------------------------


def _device_scale_fn(k: float, op: str = "mul"):
    """Replays the host normalization ON DEVICE with the same formula (mul
    vs div is a bit-level distinction; XLA's f32 ops are IEEE like numpy's,
    so device results match the host path exactly)."""
    def transform(x):
        import jax.numpy as jnp

        xf = x.astype(jnp.float32)
        return xf * jnp.float32(k) if op == "mul" else xf / jnp.float32(k)

    transform._scale = k  # introspectable for tests/logging
    transform._op = op
    return transform


def try_promote_to_device(ds: Dataset):
    """Promote a reference-shaped chain over an HBM-sized in-memory source
    to a :class:`DeviceDataset` — upload the raw bytes ONCE, then assemble
    every batch on device from a host-sent index vector (kilobytes/step).

    This is the idiomatic endpoint of the rewrite on TPU: where
    ``try_rewrite`` shrinks per-step wire traffic 4x (uint8), promotion
    removes it altogether — the streaming bandwidth floor stops applying
    because pixels cross the link once per job.

    Deliberately conservative; returns None unless ALL hold:

    * single process (multi-worker OFF semantics — independent per-worker
      shuffles — are not DeviceDataset's one-global-permutation semantic);
    * non-CPU backend (on CPU, device memory IS host memory);
    * the chain is source -> map* -> cache? -> shuffle? -> batch with the
      maps detected as pure normalization (``_detect_scale``) or absent;
    * any shuffle is UNSEEDED with per-iteration reshuffle (no
      reproducibility contract — a seeded order is honored by declining);
    * the batch divides the dataset or drops the remainder (device shapes
      are static);
    * no repeat/skip/take/shard anywhere (cardinality and stream-shape
      contracts stay exact on the unpromoted path).
    """
    if not enabled():
        return None
    cached = getattr(ds, "_device_promoted", None)
    if cached is not None:
        return cached  # one upload per chain, however many fit() calls
    import jax

    if jax.default_backend() == "cpu" or jax.process_count() > 1:
        return None
    parsed = _parse(ds)
    if parsed is None:
        return None
    source, pre, batch_kw, post = parsed
    arrays = source._tensor_source
    if not (isinstance(arrays, tuple) and len(arrays) == 2):
        return None
    images, labels = np.asarray(arrays[0]), np.asarray(arrays[1])
    if images.nbytes > 512 * 1024 * 1024:  # keep HBM headroom
        return None
    if not np.issubdtype(labels.dtype, np.integer):
        return None
    n = len(images)
    batch = batch_kw["batch_size"]
    if n % batch and not batch_kw["drop_remainder"]:
        return None
    if any(name in ("skip", "take", "shard") for name, _ in pre):
        return None
    if any(name not in ("prefetch", "with_options") for name, _ in post):
        return None
    shuffle = False
    for name, kw in pre:
        if name == "shuffle":
            if kw["seed"] is not None or not kw["reshuffle_each_iteration"]:
                return None
            shuffle = True
    fns = [kw["fn"] for name, kw in pre if name == "map"]
    scale, scale_op = None, "mul"
    if fns:
        detected = _detect_scale(fns, arrays)
        if detected is None:
            return None
        scale_op, scale = detected
    from tpu_dist.data.device import DeviceDataset

    out = DeviceDataset(  # shardcheck: disable=SC601 -- chain declared an UNSEEDED shuffle (seed-None guard above); a random seed IS that contract
        images, labels, global_batch_size=batch,
        seed=int(np.random.default_rng().integers(2**31)),
        shuffle=shuffle, scale=scale, scale_op=scale_op)
    logger.info("vectorize: promoted %d-element chain to device residency "
                "(%.1f MB uploaded once, index-only steps)", n,
                images.nbytes / 1e6)
    ds._device_promoted = out  # shardcheck: disable=SC900 -- promotion cache attribute, never persisted; taint ends here
    return out


def try_rewrite(ds: Dataset, *, defer_scale_to_device: bool | None = None
                ) -> Dataset | None:
    """A Dataset yielding the same batch stream as ``ds`` via index math +
    batched gathers, or None when ``ds``'s chain is outside the grammar.

    ``defer_scale_to_device`` (default: on for non-CPU jax backends) ships
    uint8 across the wire with the normalization as a device transform;
    the CPU backend keeps the native fused gather+scale instead (device ==
    host there, and the TF baseline's tf.data also scales in host C++)."""
    if not enabled():
        return None
    parsed = _parse(ds)
    if parsed is None:
        return None
    source, pre, batch_kw, post = parsed
    arrays = source._tensor_source
    n = source.cardinality()
    if n is None or n < 2:
        return None

    fns = [kw["fn"] for name, kw in pre if name == "map"]
    cache_present = any(name == "cache" for name, _ in pre)
    scale = _detect_scale(fns, arrays) if fns else None

    if defer_scale_to_device is None:
        import jax

        defer_scale_to_device = jax.default_backend() != "cpu"
    if scale is not None and scale[0] != "mul" and not defer_scale_to_device:
        # The native fused gather multiplies; a division map replayed on
        # host stays bit-exact only through the generic batched-apply path.
        scale = None
    if scale is None:
        for fn in fns:
            if not _probe_vectorizable(fn, arrays):
                logger.debug("vectorize: map fn %r not batch-safe; keeping "
                             "element path", fn)
                return None

    plan = _IndexPlan(n, pre)
    batch_size = batch_kw["batch_size"]
    drop_remainder = batch_kw["drop_remainder"]

    device_transform = None
    if scale is not None:
        from tpu_dist.data import native

        scale_op, scale_k = scale
        images, labels = (np.ascontiguousarray(np.asarray(arrays[0])),
                          np.asarray(arrays[1]))
        if defer_scale_to_device:
            device_transform = _device_scale_fn(scale_k, scale_op)

            def make_batch(idx):
                return images[idx], native.gather_labels(labels, idx)
        else:
            def make_batch(idx):
                return (native.gather_scale(images, idx, scale_k),
                        native.gather_labels(labels, idx))
    else:
        # Generic: gather (materialized-once when cached), then batch-apply
        # the probed maps. Without a cache the maps re-run per batch —
        # preserving per-pass re-execution, just vectorized.
        state: dict[str, Any] = {}

        def _materialized():
            if "arrays" not in state:
                out = arrays
                for fn in fns:
                    out = _apply_fn(fn, _map_structure(np.asarray, out))
                state["arrays"] = _map_structure(np.asarray, out)
            return state["arrays"]

        if cache_present:
            def make_batch(idx):
                return _map_structure(lambda a: a[idx], _materialized())
        else:
            def make_batch(idx):
                el = _map_structure(lambda a: np.asarray(a)[idx], arrays)
                for fn in fns:
                    el = _apply_fn(fn, el)
                return _map_structure(np.asarray, el)

    epoch_counter = [0]

    def one_pass():
        idx = plan.epoch(epoch_counter[0])
        epoch_counter[0] += 1
        m = len(idx)
        stop = m - (m % batch_size) if drop_remainder else m
        for s in range(0, stop, batch_size):
            yield make_batch(idx[s:s + batch_size])

    # Post-batch replay: fold repeat/take/skip/shard over the batch stream
    # in their RECORDED order (take-then-repeat loops the taken prefix;
    # repeat-then-take bounds the looped stream — combinator nesting).
    import itertools

    def _repeated(inner: Callable, count):
        def gen():
            done = 0
            while count is None or done < count:
                it = inner()
                empty = True
                for el in it:
                    empty = False
                    yield el
                if empty:
                    return
                done += 1
        return gen

    stream_factory: Callable = one_pass
    for name, kw in post:
        if name == "repeat":
            stream_factory = _repeated(stream_factory, kw["count"])
        elif name == "take":
            stream_factory = (lambda f=stream_factory, c=kw["count"]:
                              itertools.islice(f(), c))
        elif name == "skip":
            stream_factory = (lambda f=stream_factory, c=kw["count"]:
                              itertools.islice(f(), c, None))
        elif name == "shard":
            stream_factory = (lambda f=stream_factory, k=dict(kw):
                              itertools.islice(f(), k["index"], None,
                                               k["num_shards"]))

    def factory():
        yield from stream_factory()

    out = Dataset(factory, options=ds._options,
                  cardinality=ds.cardinality(), num_files=ds.num_files)
    out._device_transform = device_transform
    out._vectorized = True
    mode = ("fused-scale->device-u8" if device_transform is not None else
            "fused-scale-native" if scale is not None else "batched-maps")
    logger.info("vectorize: rewrote %d-op chain over %d elements (%s)",
                len(pre) + 1 + len(post), n, mode)
    # Replay any prefetch from the original chain's tail on the rewritten
    # stream (keeps background production off the consumer's critical path).
    for name, kw in post:
        if name == "prefetch":
            out = out.prefetch(kw["buffer_size"])
            break
    return out
