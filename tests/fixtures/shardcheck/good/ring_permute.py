"""shardcheck good fixture: a well-formed ring ppermute (SC203 clean).
Indices in range, every source and destination unique — the neighbor
exchange both pipeline schedules are built on."""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _rotate(x):
    return jax.lax.ppermute(x, AXIS, [(0, 1), (1, 0)])


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
    mapped = jax.shard_map(_rotate, check_vma=False, **kw)
    return mapped, (jnp.ones((4,)),)
