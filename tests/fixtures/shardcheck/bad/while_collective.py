"""shardcheck bad fixture: collective inside a while-loop body (SC202).

The loop drains until the local values decay below a threshold — a
data-dependent trip count. Each iteration psums, so two ranks whose
predicates diverge launch different psum counts and the rendezvous
deadlocks. A static-length scan (see good/scan_collective.py) is the
safe spelling.
"""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _drain(x):
    def cond(carry):
        v, _ = carry
        return jnp.max(v) > 1e-3

    def body(carry):
        v, i = carry
        return jax.lax.psum(v, AXIS) * 0.25, i + 1

    v, _ = jax.lax.while_loop(cond, body, (x, 0))
    return v


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P())
    mapped = jax.shard_map(_drain, check_vma=False, **kw)
    return mapped, (jnp.ones((4,)),)
