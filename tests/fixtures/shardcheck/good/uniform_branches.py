"""shardcheck good fixture: branches issue identical collective sequences
(SC201 clean). The psum is hoisted out of the cond; both branches are
collective-free, so every device runs the same launch sequence regardless
of the predicate."""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _uniform(x):
    total = jax.lax.psum(x, AXIS)
    on_first = jax.lax.axis_index(AXIS) == 0
    return jax.lax.cond(
        on_first,
        lambda v: v * 0.5,
        lambda v: v * 2.0,
        total)


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
    mapped = jax.shard_map(_uniform, check_vma=False, **kw)
    return mapped, (jnp.zeros((4,)),)
