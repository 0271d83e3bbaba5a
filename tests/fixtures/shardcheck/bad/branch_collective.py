"""shardcheck bad fixture: collective inside one cond branch (SC201).

Traced via ``shardcheck_entry``: the true branch psums, the false branch
does not. With a device-varying predicate half the mesh launches a psum
the other half never joins — deadlock.
"""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _lopsided(x):
    on_first = jax.lax.axis_index(AXIS) == 0
    return jax.lax.cond(
        on_first,
        lambda v: jax.lax.psum(v, AXIS),
        lambda v: v * 2.0,
        x)


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
    mapped = jax.shard_map(_lopsided, check_vma=False, **kw)
    return mapped, (jnp.zeros((4,)),)
