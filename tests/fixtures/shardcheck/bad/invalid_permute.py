"""shardcheck bad fixture: ppermute with a duplicate destination (SC203).

``perm=[(0, 1), (1, 1)]`` sends both devices' payloads to device 1 — two
sends racing one receive. jax traces it without complaint; shardcheck
validates the permutation against the mesh axis size statically.
"""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _clash(x):
    return jax.lax.ppermute(x, AXIS, [(0, 1), (1, 1)])


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
    mapped = jax.shard_map(_clash, check_vma=False, **kw)
    return mapped, (jnp.ones((4,)),)
