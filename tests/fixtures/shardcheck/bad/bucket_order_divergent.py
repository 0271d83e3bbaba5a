"""shardcheck bad fixture: rank-divergent gradient-bucket order (SC201).

Traced via ``shardcheck_entry``: a cond on ``axis_index`` reduces the
same gradient tree with DIFFERENT bucket packings per branch — rank 0
flushes one psum per leaf while the other ranks flush a single fused
psum. Bucketed all-reduce is only safe because every rank derives the
identical bucket schedule from the identical tree; the moment the
schedule becomes rank-dependent, launch counts differ and the mismatched
psums rendezvous with each other — deadlock. SC201 must catch it.
"""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def _rank_divergent_buckets(grads):
    on_first = jax.lax.axis_index(AXIS) == 0

    def per_leaf_buckets(g):
        from tpu_dist.parallel import collectives

        # bucket_bytes=1: every leaf flushes as its own bucket (2 psums).
        return collectives.bucketed_all_reduce(
            g, AXIS, collectives.ReduceOp.SUM, bucket_bytes=1)

    def fused_bucket(g):
        from tpu_dist.parallel import collectives

        # bucket_bytes=0: the whole tree packs into ONE psum.
        return collectives.bucketed_all_reduce(
            g, AXIS, collectives.ReduceOp.SUM, bucket_bytes=0)

    return jax.lax.cond(on_first, per_leaf_buckets, fused_bucket, grads)


def shardcheck_entry():
    devices = jax.devices()[:2]
    mesh = Mesh(devices, (AXIS,))
    kw = dict(mesh=mesh, in_specs=({"w": P(), "b": P()},),
              out_specs={"w": P(), "b": P()})
    mapped = jax.shard_map(_rank_divergent_buckets, check_vma=False, **kw)
    grads = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    return mapped, (grads,)
