"""Device-mesh construction and sharding helpers.

This module is the TPU-native replacement for the reference's device-placement
machinery (SURVEY.md D2/D3): where ``MultiWorkerMirroredStrategy`` enumerated
per-worker devices and built cross-device ops over them
(tf:...collective_all_reduce_strategy.py:613-634), we build a named
``jax.sharding.Mesh`` and express "mirrored variables" (D4) and "per-replica
batches" as ``NamedSharding``s over it:

* params: ``PartitionSpec()`` — fully replicated, one copy per device, the
  analog of TF's MirroredVariable (README.md:15).
* batch:  ``PartitionSpec('data', ...)`` — leading axis split across the data
  axis, the analog of per-replica input.

The default mesh is 1-D over every global device with axis name ``'data'``
(pure data parallelism — the only strategy the reference exercises, SURVEY.md
§2.3); extra axes (``'model'``, ``'seq'``, ...) can be requested so the design
doesn't preclude TP/SP later.
"""

from __future__ import annotations

import collections
import math
from typing import Mapping, Sequence

import numpy as np

from tpu_dist.parallel.axes import DATA_AXIS, MODEL_AXIS  # noqa: F401 - canonical home


def manual_axes_state(mesh) -> bool | None:
    """Whether any of ``mesh``'s axis names is already bound in the current
    trace (inside a shard_map over it, e.g. a model applied within
    ``strategy.run``) — or ``None`` when the axis environment can't be read
    (jax internals moved). Callers pick their own conservative direction
    for ``None``: decliners of nested mappings treat it as "inside", while
    safety gates for raw kernels must treat it as "can't confirm"."""
    try:
        from jax._src.core import get_axis_env

        bound = set(get_axis_env().axis_sizes)
    except Exception:  # pragma: no cover - jax internals moved
        return None
    return bool(bound & set(mesh.axis_names))


def inside_manual_axes(mesh) -> bool:
    """True when a mesh axis is already bound (binding it twice raises, so
    callers decline nested mappings). Conservative: unreadable → True."""
    state = manual_axes_state(mesh)
    return True if state is None else state


def make_mesh(axis_shapes: Mapping[str, int] | None = None,
              *, devices: Sequence | None = None,
              local: bool = False):
    """Build a named device mesh.

    Args:
      axis_shapes: ordered ``{axis_name: size}``; at most one size may be ``-1``
        (inferred, like numpy reshape). Default: ``{'data': -1}`` — every device
        on one data axis.
      devices: explicit device list; defaults to all global devices (or local
        devices when ``local=True`` — the MirroredStrategy case, README.md:15-19).
      local: restrict to this process's devices.

    Returns:
      ``jax.sharding.Mesh``.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.local_devices() if local else jax.devices()
        # Process-contiguous ordering: jax.devices()'s global order is not
        # guaranteed process-contiguous on every multi-host topology, but
        # a trailing mesh axis only stays intra-host (ICI-speed
        # collectives) if each outer-axis row is one process's block.
        # Sorting by (process_index, id) makes the row-major reshape below
        # put inner axes within a process whenever the sizes align (e.g.
        # {'data': n_processes, 'model': n_local}).
        devices = sorted(devices,
                         key=lambda d: (d.process_index, d.id))
    else:
        devices = list(devices)
    if not devices:
        raise ValueError("no devices available for mesh construction")

    if axis_shapes is None:
        axis_shapes = {DATA_AXIS: -1}
    axis_shapes = collections.OrderedDict(axis_shapes)

    for name, size in axis_shapes.items():
        if size != -1 and size < 1:
            raise ValueError(f"axis {name!r} must have size >= 1 or -1, got {size}")
    n = len(devices)
    known = [s for s in axis_shapes.values() if s != -1]
    n_inferred = sum(1 for s in axis_shapes.values() if s == -1)
    if n_inferred > 1:
        raise ValueError(f"at most one axis may be -1, got {dict(axis_shapes)}")
    known_prod = math.prod(known) if known else 1
    if n_inferred:
        if n % known_prod:
            raise ValueError(
                f"cannot infer axis size: {n} devices not divisible by "
                f"{known_prod} ({dict(axis_shapes)})")
        inferred = n // known_prod
        axis_shapes = collections.OrderedDict(
            (k, inferred if s == -1 else s) for k, s in axis_shapes.items())
    elif known_prod != n:
        raise ValueError(
            f"mesh shape {dict(axis_shapes)} needs {known_prod} devices, "
            f"have {n}")

    shape = tuple(axis_shapes.values())
    mesh_devices = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(mesh_devices, tuple(axis_shapes.keys()))


def replicated(mesh):
    """NamedSharding for fully-replicated state — MirroredVariable semantics
    (SURVEY.md D4): one identical copy on every mesh device."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def shard_groups(sharding, shape):
    """Device positions (rows of the owning mesh's flat device order)
    grouped by IDENTICAL shard of a ``shape``-d array.

    Devices in one group hold the same bytes under ``sharding`` — they are
    replicas of that shard and must agree bit-for-bit in healthy training;
    devices in different groups legitimately hold different data. A
    replicated sharding yields one global group; a tensor-parallel kernel
    yields one group per distinct shard (e.g. per column block). Groups are
    ordered by their shard's index ranges, so a group id is stable for a
    given (sharding, shape). This is the comparison structure the
    shard-aware SDC audit (training/integrity.py) runs on host.
    """
    devices = list(sharding.mesh.devices.flat)
    row_of = {d: i for i, d in enumerate(devices)}
    by_shard: dict = {}
    for d, idx in sharding.devices_indices_map(tuple(shape)).items():
        if d not in row_of:  # pragma: no cover - defensive
            continue
        key = tuple(s.indices(dim) for s, dim in zip(idx, shape))
        by_shard.setdefault(key, []).append(row_of[d])
    return [sorted(rows) for _, rows in sorted(by_shard.items())]


def batch_sharded(mesh, axis: str = DATA_AXIS):
    """NamedSharding splitting the leading (batch) dim across ``axis`` —
    per-replica input semantics (SURVEY.md D14)."""
    from jax.sharding import NamedSharding, PartitionSpec

    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has axes {mesh.axis_names}, no {axis!r}")
    return NamedSharding(mesh, PartitionSpec(axis))


def _shard_with_spec(batch, mesh, spec):
    """Place a pytree of host arrays with the given PartitionSpec: one
    ``device_put`` single-process, ``make_array_from_process_local_data``
    assembly multi-process (SURVEY.md D14's TPU-native equivalent)."""
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)

    def _place(x):
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, np.asarray(x))
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(_place, batch)


def shard_batch(batch, mesh, axis: str = DATA_AXIS):
    """Place a pytree of host arrays onto the mesh, batch-dim sharded."""
    from jax.sharding import PartitionSpec

    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has axes {mesh.axis_names}, no {axis!r}")
    return _shard_with_spec(batch, mesh, PartitionSpec(axis))


def shard_batch_stack(batch, mesh, axis: str = DATA_AXIS):
    """Place a pytree of K-stacked host batches onto the mesh: leading axis is
    the execution/step axis (replicated), the SECOND axis is the batch dim,
    split across ``axis`` — the layout consumed by the multi-step
    (steps_per_execution) train function."""
    from jax.sharding import PartitionSpec

    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has axes {mesh.axis_names}, no {axis!r}")
    return _shard_with_spec(batch, mesh, PartitionSpec(None, axis))


def replicate(tree, mesh, *, broadcast: bool = False):
    """Place a pytree replicated on every mesh device.

    MirroredVariable semantics (SURVEY.md D4): one identical copy per device.
    With ``broadcast=True`` in a multi-process job, process 0's values are
    broadcast so every process starts from identical state — the reference's
    "initial value produced on first replica and broadcast"
    (tf:...collective_all_reduce_strategy.py:686-689).
    """
    import jax

    sharding = replicated(mesh)
    return place_with_shardings(
        tree, jax.tree_util.tree_map(lambda _: sharding, tree),
        broadcast=broadcast)


def place_with_shardings(tree, shardings, *, broadcast: bool = False):
    """Place a pytree with a PER-LEAF NamedSharding tree (replicated
    mirrors, tensor-parallel shards, or a mix). With ``broadcast=True`` in
    a multi-process job, process 0's values are broadcast first so every
    process starts identical (SURVEY.md D4)."""
    import jax

    if broadcast and jax.process_count() > 1:
        from jax.experimental import multihost_utils

        tree = multihost_utils.broadcast_one_to_all(tree)

    def _place(x, sharding):
        x = np.asarray(x)
        # make_array_from_callback only asks each process for its addressable
        # shards, so this single code path is multi-process safe (device_put to
        # non-addressable devices is not).
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    return jax.tree_util.tree_map(_place, tree, shardings)
