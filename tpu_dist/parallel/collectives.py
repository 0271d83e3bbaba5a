"""Thin collectives layer: communication modes, reduce wrappers, shape logging.

The reference's entire collective stack (SURVEY.md §5.8) — RING-over-gRPC and
NCCL transports, group/instance keys, tensor packing, launcher threads,
MEAN = SUM / group_size (tf:...cross_device_ops.py:1045-1234,
cross_device_utils.py:347-420) — collapses on TPU into XLA-compiled
``psum/pmean`` over mesh axes: the compiler emits CrossReplicaSum over ICI
(intra-slice) / DCN (inter-slice) and does its own bucketing and
compute/communication overlap. What legitimately survives as framework code:

* the communication-mode enum, accepted for reference compatibility
  (``CollectiveCommunication.{AUTO,RING,NCCL}``, tf_dist_example.py:12,
  README.md:23) plus the TPU-native modes it maps onto;
* reduce wrappers with *collective-shape debug logging*, mirroring the
  reference's per-step "Collective all_reduce tensors: N all_reduces,
  group_size = G" INFO lines (tf:...cross_device_ops.py:1153-1158) that the
  survey used to verify sync behavior (SURVEY.md §3.5, §5.5);
* host-side scalar reductions over the coordination service for out-of-step
  values (metric summaries, early-stop votes).
"""

from __future__ import annotations

import enum
import logging
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
# The public jax.core alias was removed; mesh.manual_axes_state reads the
# same private module.
from jax._src.core import trace_state_clean

logger = logging.getLogger("tpu_dist.collectives")

#: Flip with `set_collective_logging` — mirrors TF's INFO logging of every
#: batched all-reduce shape.
_LOG_COLLECTIVES = False


def set_collective_logging(enabled: bool) -> None:
    global _LOG_COLLECTIVES
    _LOG_COLLECTIVES = bool(enabled)


#: Fault-injection seam (tpu_dist.resilience): when installed, every wrapper
#: in this module (and bootstrap.barrier) reports its op name here BEFORE
#: doing the real work, so a chaos harness can delay or wedge host-level
#: collectives without code edits. None in production — one pointer check.
_FAULT_HOOK = None


def install_fault_hook(hook):
    """Install (or, with None, remove) the collective fault hook.

    ``hook(op_name)`` is called eagerly before each host-level collective;
    it may sleep (delay/hang injection) or raise (failure injection).
    Returns the previously installed hook so callers can restore it.
    """
    global _FAULT_HOOK
    prev = _FAULT_HOOK
    _FAULT_HOOK = hook
    return prev


def fire_fault_hook(op: str) -> None:
    """Invoke the installed fault hook, but only from eager (host) context:
    collectives traced into a jitted program call these wrappers once at
    trace time, where a sleep would stall compilation, not the step."""
    hook = _FAULT_HOOK
    if hook is None:
        return
    if not trace_state_clean():
        return
    hook(op)


#: Telemetry seam (tpu_dist.observe), sibling of the fault hook above: when
#: installed, every wrapper reports (op, phase, payload size, host wall time)
#: AFTER doing the real work. Unlike the fault hook it also fires at trace
#: time — tagged phase="trace" — so compile-time wrapper activity is
#: countable without being mistaken for steady-state traffic. None in
#: production — one pointer check per call.
_OBSERVE_HOOK = None


def install_observe_hook(hook):
    """Install (or, with None, remove) the collective observe hook.

    ``hook(op, *, phase, leaves, nbytes, seconds)`` is called after each
    wrapper in this module (and bootstrap.barrier): ``phase`` is "eager" or
    "trace", ``leaves``/``nbytes`` describe the payload pytree (0 when not
    applicable), ``seconds`` is host wall time for host-level collectives
    (None for in-program ones). Returns the previously installed hook so
    callers can restore it.
    """
    global _OBSERVE_HOOK
    prev = _OBSERVE_HOOK
    _OBSERVE_HOOK = hook
    return prev


def _tree_payload(tree: Any) -> tuple[int, int]:
    """(leaf count, total payload bytes) of a pytree — works on tracers,
    whose aval still carries size/dtype. Opaque leaves count as 0 bytes."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            try:
                total += int(size) * np.dtype(dtype).itemsize
            except TypeError:
                pass
    return len(leaves), total


def fire_observe_hook(op: str, tree: Any = None, *,
                      seconds: "float | None" = None) -> None:
    """Report one collective call to the installed observe hook. A hook
    failure is logged and swallowed — telemetry must never take down the
    collective it is watching."""
    hook = _OBSERVE_HOOK
    if hook is None:
        return
    phase = "eager" if trace_state_clean() else "trace"
    leaves, nbytes = (0, 0) if tree is None else _tree_payload(tree)
    try:
        hook(op, phase=phase, leaves=leaves, nbytes=nbytes, seconds=seconds)
    except Exception:  # noqa: BLE001 - observability is best-effort
        logger.debug("observe hook failed for %s", op, exc_info=True)


class CollectiveCommunication(enum.Enum):
    """Communication-implementation hint.

    ``AUTO``/``RING``/``NCCL`` are the reference's enum values
    (tf:python/distribute/collective_util.py:28-47; README.md:23: AUTO picks by
    hardware/topology/tensor size). On TPU there is no user-selectable
    transport — XLA emits ICI collectives intra-slice and DCN collectives
    across slices — so RING and NCCL are accepted and mapped to AUTO with a
    log note, and ICI/DCN exist to make the TPU fabric choice explicit in
    diagnostics.
    """

    AUTO = "AUTO"
    RING = "RING"
    NCCL = "NCCL"
    ICI = "ICI"
    DCN = "DCN"

    @classmethod
    def resolve(cls, value: "CollectiveCommunication | str | None"):
        if value is None:
            return cls.AUTO
        if isinstance(value, str):
            try:
                value = cls[value.upper()]
            except KeyError:
                raise ValueError(
                    f"unknown CollectiveCommunication {value!r}; valid: "
                    f"{[m.name for m in cls]}") from None
        if value in (cls.RING, cls.NCCL):
            logger.info(
                "CollectiveCommunication.%s has no effect on TPU; XLA emits "
                "ICI/DCN collectives (treating as AUTO)", value.name)
        return value


class ReduceOp(enum.Enum):
    """Cross-replica reduction op (TF ``tf.distribute.ReduceOp`` analog).

    MEAN is implemented as SUM / group_size exactly as the reference does
    (tf:...cross_device_ops.py:1170-1180)."""

    SUM = "sum"
    MEAN = "mean"
    MAX = "max"
    MIN = "min"


def _log_tree(op: str, tree: Any, axis: str) -> None:
    if not _LOG_COLLECTIVES:
        return
    leaves = jax.tree_util.tree_leaves(tree)
    # Group size is the mesh-axis extent; available inside tracing via
    # axis size.
    try:
        group = jax.lax.axis_size(axis)
    except Exception:
        group = "?"
    logger.info(
        "Collective %s tensors: %d all_reduces, group_size = %s, shapes = %s",
        op, len(leaves), group, [tuple(l.shape) for l in leaves])


def all_reduce(tree: Any, axis: str, op: ReduceOp | str = ReduceOp.MEAN) -> Any:
    """Reduce a pytree across a mesh axis, inside a jitted/shard_map context.

    The one-call replacement for the reference's gradient all-reduce pipeline
    (grad packing + CollectiveReduceV2 launch, SURVEY.md D5-D7). XLA fuses and
    schedules the emitted CrossReplicaSum ops; no manual packing needed.
    """
    op = ReduceOp(op) if not isinstance(op, ReduceOp) else op
    fire_fault_hook("all_reduce")
    fire_observe_hook("all_reduce", tree)
    _log_tree(f"all_reduce[{op.value}]", tree, axis)
    if op is ReduceOp.SUM:
        return jax.lax.psum(tree, axis)
    if op is ReduceOp.MEAN:
        return jax.lax.pmean(tree, axis)
    if op is ReduceOp.MAX:
        return jax.tree_util.tree_map(lambda x: jax.lax.pmax(x, axis), tree)
    if op is ReduceOp.MIN:
        return jax.tree_util.tree_map(lambda x: jax.lax.pmin(x, axis), tree)
    raise ValueError(f"unsupported reduce op {op}")


def _leaf_nbytes(leaf: Any) -> int:
    """Payload bytes of one leaf — works on tracers (aval carries
    size/dtype); opaque leaves count as 0."""
    size = getattr(leaf, "size", None)
    dtype = getattr(leaf, "dtype", None)
    if size is None or dtype is None:
        return 0
    try:
        return int(size) * np.dtype(dtype).itemsize
    except TypeError:
        return 0


def partition_buckets(tree: Any, bucket_bytes: int) -> list[list[int]]:
    """Partition a pytree's leaves into size-bucketed groups for reduction.

    Returns a list of buckets, each a list of indices into
    ``jax.tree_util.tree_leaves(tree)``. Leaves are walked in REVERSE
    flatten order — the backward pass produces the last layer's gradients
    first, so reverse-topological buckets fill (and can be reduced) while
    earlier layers' gradients are still being computed. A bucket flushes
    once its accumulated payload reaches ``bucket_bytes``; a single leaf
    larger than the budget therefore gets a bucket of its own.
    ``bucket_bytes <= 0`` collapses to ONE bucket holding every leaf
    (still reverse order) — the fully-packed degenerate schedule.

    The partition depends only on the tree structure and leaf shapes, so
    every rank computes the identical bucket sequence — the property SC201
    checks in the traced program (a rank-divergent order deadlocks real
    collectives).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return []
    indices = list(range(len(leaves)))[::-1]
    if bucket_bytes <= 0:
        return [indices]
    buckets: list[list[int]] = []
    current: list[int] = []
    current_bytes = 0
    for i in indices:
        current.append(i)
        current_bytes += _leaf_nbytes(leaves[i])
        if current_bytes >= bucket_bytes:
            buckets.append(current)
            current, current_bytes = [], 0
    if current:
        buckets.append(current)
    return buckets


def bucketed_all_reduce(tree: Any, axis: str,
                        op: ReduceOp | str = ReduceOp.MEAN, *,
                        bucket_bytes: int = 0) -> Any:
    """Reduce a pytree across a mesh axis in size-bucketed launches.

    The explicit-scheduling alternative to :func:`all_reduce`'s single
    fused tree reduction: leaves are packed (same-dtype concat of raveled
    leaves) into :func:`partition_buckets` groups and each bucket is ONE
    ``psum``/``pmean`` launch, issued in reverse-topological order as the
    backward pass makes gradients available — XLA's latency-hiding
    scheduler can then overlap early-bucket reduction with the remaining
    backward compute instead of waiting for the full tree. Packing is a
    concat/split round-trip, so the result is ELEMENTWISE IDENTICAL to
    per-leaf ``psum``/``pmean`` of the same inputs (the reduction itself
    is never reassociated). MAX/MIN don't benefit from packing and
    delegate to :func:`all_reduce`.

    Launch count equals the bucket count (times the number of distinct
    leaf dtypes sharing a bucket) — more launches buy overlap at the
    price of per-launch latency, which ``analysis cost`` prices via the
    latency model.
    """
    op = ReduceOp(op) if not isinstance(op, ReduceOp) else op
    if op in (ReduceOp.MAX, ReduceOp.MIN):
        return all_reduce(tree, axis, op)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    fire_fault_hook("bucketed_all_reduce")
    reduce_fn = jax.lax.psum if op is ReduceOp.SUM else jax.lax.pmean
    reduced: list[Any] = [None] * len(leaves)
    for bucket in partition_buckets(tree, bucket_bytes):
        # Group the bucket's leaves by dtype (first-occurrence order, so
        # every rank builds the same launch sequence); one packed launch
        # per (bucket, dtype) group.
        by_dtype: dict[Any, list[int]] = {}
        for i in bucket:
            by_dtype.setdefault(jnp.asarray(leaves[i]).dtype, []).append(i)
        for idxs in by_dtype.values():
            fire_observe_hook("bucketed_all_reduce",
                              [leaves[i] for i in idxs])
            _log_tree(f"bucketed_all_reduce[{op.value}]",
                      [leaves[i] for i in idxs], axis)
            if len(idxs) == 1:
                i = idxs[0]
                reduced[i] = reduce_fn(leaves[i], axis)
                continue
            flat = jnp.concatenate(
                [jnp.ravel(leaves[i]) for i in idxs])
            packed = reduce_fn(flat, axis)
            offset = 0
            for i in idxs:
                n = int(np.prod(leaves[i].shape)) if leaves[i].shape else 1
                reduced[i] = packed[offset:offset + n].reshape(
                    leaves[i].shape)
                offset += n
    return jax.tree_util.tree_unflatten(treedef, reduced)


def all_gather(x: Any, axis: str, *, tiled: bool = False) -> Any:
    """Gather values across a mesh axis (per-replica -> global view)."""
    fire_fault_hook("all_gather")
    fire_observe_hook("all_gather", x)
    _log_tree("all_gather", x, axis)
    return jax.lax.all_gather(x, axis, tiled=tiled)


def host_all_reduce_sum(x) -> Any:
    """Host-level scalar/array SUM across processes, outside any jitted step.

    Uses a tiny compiled psum over the global device set (rides the same ICI/
    DCN fabric); the analog of the reference's host-side PerReplica metric
    reduction (keras trainer reduce_per_replica, SURVEY.md D15).
    """
    fire_fault_hook("host_all_reduce_sum")
    t0 = time.perf_counter()
    if jax.process_count() == 1:
        out = x
    else:
        from jax.experimental import multihost_utils

        out = multihost_utils.process_allgather(jnp.asarray(x)).sum(axis=0)
    fire_observe_hook("host_all_reduce_sum", out,
                      seconds=time.perf_counter() - t0)
    return out


def host_all_gather(x) -> Any:
    """Host-level gather across processes: every process's value stacked on
    a new leading axis, ``[process_count, ...]``, identical everywhere.

    The telemetry exchange primitive: each rank contributes its local
    measurement (e.g. this epoch's mean step time) and the chief — like
    every other rank — sees the full per-rank vector
    (observe/telemetry.py straggler detection). Single-process runs return
    ``np.asarray(x)[None]`` so callers never branch on process count.
    """
    fire_fault_hook("host_all_gather")
    t0 = time.perf_counter()
    if jax.process_count() == 1:
        out = np.asarray(x)[None]
    else:
        from jax.experimental import multihost_utils

        out = np.asarray(
            multihost_utils.process_allgather(jnp.asarray(x)))
    fire_observe_hook("host_all_gather", out,
                      seconds=time.perf_counter() - t0)
    return out


def broadcast_from_chief(tree: Any) -> Any:
    """Broadcast process 0's pytree to all processes (host-level, D4 init
    broadcast / checkpoint-restore fan-out)."""
    fire_fault_hook("broadcast_from_chief")
    t0 = time.perf_counter()
    if jax.process_count() == 1:
        out = tree
    else:
        from jax.experimental import multihost_utils

        out = multihost_utils.broadcast_one_to_all(tree)
    fire_observe_hook("broadcast_from_chief", out,
                      seconds=time.perf_counter() - t0)
    return out
