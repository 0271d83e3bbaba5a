"""shardcheck good fixture: collective inside a STATIC-length scan (SC202
clean). Every rank runs exactly ``length`` iterations, so the ppermute
launch counts line up by construction — the safe spelling of the
iterated-collective pattern the while-loop fixture gets wrong."""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _ring(x):
    def step(carry, _):
        return jax.lax.ppermute(carry, AXIS, [(0, 1), (1, 0)]), None

    y, _ = jax.lax.scan(step, x, None, length=2)
    return y


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
    mapped = jax.shard_map(_ring, check_vma=False, **kw)
    return mapped, (jnp.ones((4,)),)
