"""shardcheck cost fixture: a hand-computable entry for the baseline gate
(SC301/SC302 tests).

Mesh ``data=2``; the f32[4, 4] input is sharded over data, so the traced
per-shard payload is f32[2, 4] = 32 bytes. One psum at ring cost
``2*(P-1)/P`` gives ``total_comm_bytes = 32`` at P=2 — the number the
committed fixture baselines under ../baselines/ encode (and the regressed
one undercuts).
"""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _reduce(x):
    return jax.lax.psum(x, AXIS)


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=(P(AXIS),), out_specs=P(AXIS))
    mapped = jax.shard_map(_reduce, check_vma=False, **kw)
    return mapped, (jnp.ones((4, 4)),)
