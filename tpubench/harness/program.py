"""The seam between the benchmark and the system under test.

From ``tpu_dist`` the benchmark takes the entry points (``Model.fit``,
``ServeEngine.submit``/``step``), the counters of ``observe.metrics`` and
nothing else. This file builds the repo's LM at a configuration's widths
and lays the benchmark's own weights (``reference/gpt2.py``, from the
seed) into the tree the program names its parameters by. It is the only
file that knows those names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpubench.reference import gpt2


def block_name(i: int) -> str:
    return "block" if i == 0 else f"block_{i}"


def to_program_tree(p: dict, n_layer: int) -> dict:
    """Reference weights (stacked layers) in ``build_transformer_lm``'s
    parameter tree."""
    tree = {"embedding": {"table": p["wte"]},
            "positionalembedding": {"table": p["wpe"]},
            "layernormalization": {"gamma": p["lnf_g"], "beta": p["lnf_b"]},
            "dense": {"kernel": p["head_w"], "bias": p["head_b"]}}
    for i in range(n_layer):
        h = {n: p["h." + n][i] for n in gpt2.BLOCK_LEAF_NAMES}
        tree[block_name(i)] = {
            "residual": {"main": {
                "layernormalization": {"gamma": h["ln1_g"],
                                       "beta": h["ln1_b"]},
                "multiheadattention": {k: h[k] for k in (
                    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo")}}},
            "residual_1": {"main": {
                "layernormalization": {"gamma": h["ln2_g"],
                                       "beta": h["ln2_b"]},
                "dense": {"kernel": h["w1"], "bias": h["b1"]},
                "dense_1": {"kernel": h["w2"], "bias": h["b2"]}}}}
    return tree


def canonical_leaves(tree: dict, n_layer: int) -> dict:
    """The program's tree flattened to the reference's leaf names
    (``wte``, ``h3.wq``, ...)."""
    out = {"wte": tree["embedding"]["table"],
           "wpe": tree["positionalembedding"]["table"],
           "lnf_g": tree["layernormalization"]["gamma"],
           "lnf_b": tree["layernormalization"]["beta"],
           "head_w": tree["dense"]["kernel"],
           "head_b": tree["dense"]["bias"]}
    for i in range(n_layer):
        b = tree[block_name(i)]
        attn, mlp = b["residual"]["main"], b["residual_1"]["main"]
        leaves = {"ln1_g": attn["layernormalization"]["gamma"],
                  "ln1_b": attn["layernormalization"]["beta"],
                  "ln2_g": mlp["layernormalization"]["gamma"],
                  "ln2_b": mlp["layernormalization"]["beta"],
                  "w1": mlp["dense"]["kernel"], "b1": mlp["dense"]["bias"],
                  "w2": mlp["dense_1"]["kernel"],
                  "b2": mlp["dense_1"]["bias"]}
        leaves.update({k: attn["multiheadattention"][k] for k in (
            "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo")})
        for k, v in leaves.items():
            out[f"h{i}.{k}"] = v
    return out


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def build_lm(cfg: dict, seed: int):
    """The repo's LM at ``cfg``'s widths whose ``init`` hands out the
    benchmark's weights: made on the device in one jitted call from the
    seed, float32 as the program holds them."""
    from tpu_dist.models.transformer import build_transformer_lm

    model = build_transformer_lm(
        cfg["n_vocab"], cfg["n_ctx"], d_model=cfg["n_embd"],
        depth=cfg["n_layer"], num_heads=cfg["n_head"], ff_dim=cfg["n_inner"])
    theirs = jax.eval_shape(lambda: model.init(0))["params"]
    make = jax.jit(lambda key: to_program_tree(
        gpt2.make_params(key, cfg), cfg["n_layer"]))
    ours = jax.eval_shape(make, gpt2.seed_key(seed))
    a = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), theirs)
    b = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), ours)
    if a != b:
        raise RuntimeError(
            "the program's parameter tree is not the one "
            "tpubench/harness/program.py lays weights into")

    def init(_seed=0, input_shape=None):
        return {"params": make(gpt2.seed_key(seed)), "state": {}}

    model.init = init
    return model


def program_grad_norms(cfg: dict, beta_1: float = 0.9):
    """jit: Adam's first moment after ONE step -> leaf norms of the
    gradient as the optimizer got it (mu = (1 - beta_1) * g)."""
    n_layer = cfg["n_layer"]

    def fn(mu):
        return {k: _norm(v) / (1.0 - beta_1)
                for k, v in canonical_leaves(mu, n_layer).items()}

    return jax.jit(fn)


def program_delta_norms(cfg: dict):
    """jit: (params now, seed key) -> leaf norms of the change since the
    weights the seed gives."""
    n_layer = cfg["n_layer"]

    def fn(params, key):
        start = to_program_tree(gpt2.make_params(key, cfg), n_layer)
        now = canonical_leaves(params, n_layer)
        then = canonical_leaves(start, n_layer)
        return {k: _norm(now[k] - then[k]) for k in now}

    return jax.jit(fn)
