"""shardcheck bad fixture: cond branches psum DIFFERENT payloads (SC203).

Both branches issue the same collective sequence — one psum over the same
axis — so SC201's order check passes; but the true branch reduces a
float32[2] half-slice while the false branch reduces the full float32[4].
Ranks taking different branches rendezvous with mismatched shapes: a hang
or silent corruption on real hardware.
"""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _mismatched(x):
    on_first = jax.lax.axis_index(AXIS) == 0

    def half(v):
        s = jax.lax.psum(v[:2], AXIS)
        return jnp.concatenate([s, s])

    def full(v):
        return jax.lax.psum(v, AXIS)

    return jax.lax.cond(on_first, half, full, x)


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
    mapped = jax.shard_map(_mismatched, check_vma=False, **kw)
    return mapped, (jnp.ones((4,)),)
