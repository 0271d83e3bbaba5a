"""Expert-parallel Mixture-of-Experts LM on a data x expert mesh.

The fourth parallelism family (after DP, the seq ring, Megatron TP and
the pipe schedules): a Switch-transformer LM whose FFN experts shard
one-bundle-per-device over the ``expert`` mesh axis, with tokens
travelling to their experts and back through two ``all_to_all``
collectives inside the compiled step (parallel/expert.py). The router's
load-balance auxiliary loss joins the training objective automatically
(the trainer's add_loss analog).

Run on the 8-device virtual CPU mesh:

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/moe_expert_parallel_lm.py
"""

import numpy as np

import tpu_dist as td
from tpu_dist.models.transformer import build_transformer_lm

VOCAB, SEQ = 512, 64
EXPERTS = 8

strategy = td.MirroredStrategy(axis_shapes={"data": 2, "expert": 4})
print(f"mesh: {dict(strategy.mesh.shape)} "
      f"({EXPERTS} experts, {EXPERTS // 4} per expert-axis device)")

stream = (np.arange(20_000) * 2654435761) % VOCAB
xs = np.stack([stream[i:i + SEQ] for i in range(0, 16_000, 40)])
ys = np.stack([stream[i + 1:i + SEQ + 1] for i in range(0, 16_000, 40)])
ds = (td.data.Dataset.from_tensor_slices(
    (xs.astype(np.int64), ys.astype(np.int64))).batch(32).repeat())

with strategy.scope():
    model = build_transformer_lm(
        VOCAB, SEQ, d_model=128, depth=4, num_heads=8, ff_dim=256,
        moe_experts=EXPERTS, moe_top_k=2, moe_groups=8)
    model.compile(
        loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
        optimizer=td.ops.Adam(1e-3), metrics=["accuracy"])
    model.fit(ds, epochs=3, steps_per_epoch=20)

import jax  # noqa: E402

flat = jax.tree_util.tree_flatten_with_path(model.variables["params"])[0]
w1 = [leaf for path, leaf in flat
      if getattr(path[-1], "key", None) == "w1"][0]
print(f"expert stack w1 {w1.shape}: spec={w1.sharding.spec}, "
      f"local bundle={w1.addressable_shards[0].data.shape}")
sflat = jax.tree_util.tree_flatten_with_path(model.variables["state"])[0]
aux = [float(leaf) for path, leaf in sflat
       if getattr(path[-1], "key", None) == "aux_loss"]
print(f"load-balance aux losses (in the objective): "
      f"{[round(a, 5) for a in aux]}")
