"""The ``exaone_moe`` family (K-EXAONE-236B-A23B): everything the benchmark
knows of it, in one module that a configuration names under ``reference``
(``harness/cells.py`` states the interface).

Three parts, as ``ling_hybrid.py`` has them: **the plain reference**
(``make_params``, ``layer_weights``, ``forward``: straightforward
``jax.numpy``, float32, the caller sets ``highest``; attention as one
masked softmax over the whole sequence a block of queries at a time, the
routed rows sorted by expert and multiplied by their own expert; no cache,
no kernel, nothing of ``tpu_dist``), **the program at these sizes**
(``build_program``, the only importer of ``tpu_dist``) and **sizes and work
from shapes**.

Equations (``d`` = ``hidden_size``, ``H`` query heads, ``G`` K/V heads of
``head_dim``, eps = ``rms_norm_eps``, no biases; ``cfg`` keeps the published
key names of ``config.json``):

* Block ``i``: ``h = x + RMSNorm(Attn_i(x))``, ``y = h + RMSNorm(FFN_i(h))``:
  no norm on a sublayer's input, one on its OUTPUT before the residual add.
* ``Attn_i``: ``q = x W_q`` (``H`` heads), ``k = x W_k``, ``v = x W_v``
  (``G`` heads); per head ``q_h <- RMSNorm(q_h)``, ``k_g <- RMSNorm(k_g)``
  (one learned scale each a layer). Where ``layer_types[i]`` is
  ``sliding_attention`` both are turned by RoPE over the whole head
  (``rope_parameters.rope_theta``, half-split pairs ``(x[j], x[j + n/2])``
  as the Hugging Face ``rotate_half`` pairs them) and a query at ``t`` sees
  keys ``t - sliding_window < j <= t``; a ``full_attention`` layer carries
  no rotary positions and sees ``j <= t``. Query head ``h`` reads K/V head
  ``h // (H / G)``; ``s = q . k / sqrt(head_dim)``, softmax in float32,
  ``o = [p v]_h W_o``.
* ``FFN_i``: ``mlp_layer_types[i]`` ``dense`` is SwiGLU at
  ``intermediate_size``; ``sparse`` is the expert layer: ``s = sigmoid(W_r
  x)`` in float32, the ``num_experts_per_tok`` best of ``s + b`` chosen
  (``n_group`` 1, ``topk_group`` 1: no group limit), ``w_e =
  routed_scaling_factor * s_e / sum_chosen s`` (``norm_topk_prob``), ``y =
  sum_e w_e E_e(x) + E_shared(x)``, every expert SwiGLU at
  ``moe_intermediate_size``. Only the experts ``experts_held = [first, first
  + count)`` are here: the router keeps its published width, and what the
  absent experts would add is left out (here and in the program alike).
* Head: final RMSNorm, untied ``W_out`` over the vocabulary slice. The
  multi-token-prediction module takes no part in next-token logits and is
  not made.

**Assumed** (the config does not say; ``configs/*.json`` lists them): the
norms' place and the q/k norms (the family's own, EXAONE 4.0,
arXiv:2507.11407); full layers carry no rotary positions (the same
report's hybrid attention); the router has a selection bias ``b``; every
weight random from the seed, matrices bfloat16 VALUES (as a checkpoint
stores them: the reference computes in float32 on the numbers the program
holds); the scale of the draw, fixed below as constants with their reasons.

**Computed in blocks.** The cut's weights are 24 GB in float32, more than
the chip: ``make_params`` returns the embedding, the head, the last norm
and ONE KEY A LAYER; ``forward`` makes layer ``i``'s weights from its key
inside the loop (3.0 GB at a time, behind an optimisation barrier), attends
``QUERY_BLOCK`` queries at a time (a ``[64, 512, 8192]`` score is 1.07 GB)
and routes ``TOKEN_BLOCK`` tokens at a time. ``build_program`` makes the
same weights the same way, a layer at a time, matrices rounded once to the
policy's compute dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tpubench.harness.reference import seed_key

#: Queries the reference attends at a time, tokens it routes at a time.
QUERY_BLOCK = 512
TOKEN_BLOCK = 2048

# The draw of the random weights (``ling_hybrid.py`` has the history: a
# comparison of SERVED TOKENS against this module's logits needs a random
# network that stands where a trained one does, each block moving the
# residual stream by a fraction).

#: Embedding rows of unit scale: the stream starts at the size the blocks'
#: normed updates are measured against.
EMBEDDING_STD = 1.0

#: The learned scale of every OUTPUT norm (a sublayer's update is
#: ``gamma * unit vector``, whatever its matrices' scale): sixteen updates
#: of 0.15 explain about a quarter of the last stream's variance, as
#: ``ling_hybrid``'s blocks do with ``W_o`` and ``W_d`` at a quarter of
#: Glorot. At 1.0, a checkpoint's initial value, each block would REPLACE
#: the stream and bf16 could not be told from fp8.
OUTPUT_NORM_GAMMA = 0.15

#: The routed experts' ``W_d`` as a share of Glorot (the shared expert's is
#: Glorot, and the norm behind the sum keeps only their ratio). The
#: router's choice is discrete: under bf16 a few tokens in a thousand put
#: another expert eighth than float32 does, and such a token's update moves
#: by that expert's share of it. ``served_logit_gap`` is a maximum over
#: thousands of tokens, so it reads the WORST flip; a flip and a planted
#: fault (every held expert dropped) scale alike with this number, the
#: fault about three times a flip (it sums over layers and held experts).
#: It is set where the worst flip sinks to the level of the other bf16
#: rounding, as ``ling_hybrid``'s 0.03 against 0.25 is: the same ratio.
ROUTED_DOWN_SCALE = 0.12


# -- sizes read from the configuration ------------------------------------


def dims(cfg: dict) -> dict:
    """The handful of derived sizes every part below uses."""
    first, count = cfg["experts_held"]
    assert count == cfg["num_experts"], "num_experts is the number held"
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "dk": cfg["head_dim"],
        "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        # One shared expert a layer, at the routed experts' width.
        "fs": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        "routed": cfg["num_experts_published"], "first": first,
        "held": count, "window": cfg["sliding_window"],
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
    }


def layer_kind(cfg: dict, i: int) -> tuple:
    """("window" | "full", "dense" | "moe") of layer ``i``, from the
    published lists (of which the cut builds the first
    ``num_hidden_layers``)."""
    attn = {"sliding_attention": "window", "full_attention": "full"}
    ffn = {"dense": "dense", "sparse": "moe"}
    return attn[cfg["layer_types"][i]], ffn[cfg["mlp_layer_types"][i]]


# -- weights ----------------------------------------------------------------


#: The leaves a checkpoint stores in bfloat16 (the rest are float32: norms,
#: the router and its bias).
_MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ewg", "ewu", "ewd",
             "swg", "swu", "swd")


def _glorot(key, shape):
    limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def layer_weights(key, kind: tuple, cfg: dict) -> dict:
    """One layer's float32 weights from its key; ``kind`` is
    :func:`layer_kind`'s pair (static: three kinds in the cut). Window and
    full layers hold the same leaves."""
    m = dims(cfg)
    d, dk = m["d"], m["dk"]
    ks = iter(jax.random.split(key, 16))
    gamma = jnp.full((d,), OUTPUT_NORM_GAMMA, jnp.float32)
    w = {"wq": _glorot(next(ks), (d, m["heads"] * dk)),
         "wk": _glorot(next(ks), (d, m["kv"] * dk)),
         "wv": _glorot(next(ks), (d, m["kv"] * dk)),
         "q_norm": jnp.ones((dk,), jnp.float32),
         "k_norm": jnp.ones((dk,), jnp.float32),
         "wo": _glorot(next(ks), (m["heads"] * dk, d)),
         "norm1": gamma, "norm2": gamma}
    if kind[1] == "dense":
        w.update(wg=_glorot(next(ks), (d, m["f"])),
                 wu=_glorot(next(ks), (d, m["f"])),
                 wd=_glorot(next(ks), (m["f"], d)))
    else:
        e, fe, fs = m["held"], m["fe"], m["fs"]
        w.update(
            router=_glorot(next(ks), (d, m["routed"])),
            # Assumed: a selection bias, drawn small so that it moves some
            # choices (the config names the gate's keys, not its bias).
            bias=0.03 * jax.random.normal(next(ks), (m["routed"],),
                                          jnp.float32),
            ewg=_glorot(next(ks), (e, d, fe)),
            ewu=_glorot(next(ks), (e, d, fe)),
            ewd=ROUTED_DOWN_SCALE * _glorot(next(ks), (e, fe, d)),
            swg=_glorot(next(ks), (d, fs)), swu=_glorot(next(ks), (d, fs)),
            swd=_glorot(next(ks), (fs, d)))
    # The checkpoint's dtype: matrices are bfloat16 VALUES (kept float32).
    return {k: (_as_published(v) if k in _MATRICES else v)
            for k, v in w.items()}


def _as_published(w):
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def make_params(key, cfg: dict) -> dict:
    """The small leaves and one key a layer (jit this whole): a layer's
    weights are made where they are used, by :func:`layer_weights`."""
    m = dims(cfg)
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    return {
        "wte": _as_published(EMBEDDING_STD * jax.random.normal(
            k_emb, (m["vocab"], m["d"]), jnp.float32)),
        "lnf": jnp.ones((m["d"],), jnp.float32),
        "head_w": _as_published(_glorot(k_head, (m["d"], m["vocab"]))),
        "layer_keys": jax.random.split(k_layers, m["layers"]),
    }


# -- forward ------------------------------------------------------------------


def _round_operand(x, quant):
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown quant {quant!r}")


def _mm(a, b, quant):
    return jnp.matmul(_round_operand(a, quant), _round_operand(b, quant))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x, pos, theta):
    """Half-split pairs ``(x[j], x[j + n/2])`` of ``x`` [..., L, n] turned
    by ``pos * theta ** (-2j / n)``."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos[:, None].astype(jnp.float32) * inv          # [L, n/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :n // 2], x[..., n // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(x, w, kind: str, cfg: dict, quant):
    m = dims(cfg)
    b, ln, _ = x.shape
    h, g, dk, eps = m["heads"], m["kv"], m["dk"], cfg["rms_norm_eps"]
    pos = jnp.arange(ln)
    heads = lambda name, n: _mm(x, w[name], quant).reshape(
        b, ln, n, dk).transpose(0, 2, 1, 3)                 # [B, n, L, dk]
    q = _rms(heads("wq", h), w["q_norm"], eps)
    k = _rms(heads("wk", g), w["k_norm"], eps)
    v = heads("wv", g)
    if kind == "window":
        # Assumed: rotary positions on the window layers only.
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    # Query head j of K/V head i is head i * (H / G) + j.
    q = q.reshape(b, g, h // g, ln, dk)
    block = min(QUERY_BLOCK, ln)
    assert ln % block == 0, "pad the sequence to whole query blocks"

    def one(start):
        at = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=3)
        s = _mm(qb, k[:, :, None].transpose(0, 1, 2, 4, 3), quant)
        s = s / math.sqrt(dk)                               # [B,G,r,blk,L]
        sees = pos[None, :] <= at[:, None]
        if kind == "window":
            # A query sees itself and the ``sliding_window - 1`` before it.
            sees &= at[:, None] - pos[None, :] < m["window"]
        s = jnp.where(sees, s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), v[:, :, None], quant)

    o = jax.lax.map(one, jnp.arange(0, ln, block))   # [n, B, G, r, blk, dk]
    o = jnp.moveaxis(o, 0, 3).reshape(b, h, ln, dk)
    return _mm(o.transpose(0, 2, 1, 3).reshape(b, ln, h * dk), w["wo"],
               quant)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(_silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def route(x, router, bias, cfg: dict, quant=None):
    """(chosen expert ids [T, k], weights [T, k]) of tokens ``x`` [T, d]:
    sigmoid scores, the best ``num_experts_per_tok`` of ``s + b`` (one
    group, which stays: no group limit), weights from ``s`` alone,
    normalised and scaled."""
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    s = jax.nn.sigmoid(_mm(x, router, quant))               # [T, E]
    chosen = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])[1]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weights = picked / jnp.sum(picked, axis=1, keepdims=True)
    return chosen, cfg["routed_scaling_factor"] * weights


def _grouped(rows, w, sizes, quant):
    """Rows sorted by expert times their own expert's matrix."""
    return jax.lax.ragged_dot(_round_operand(rows, quant),
                              _round_operand(w, quant), sizes)


def _routed(flat, w, cfg, quant):
    """The held experts' part for tokens ``flat`` [T, d]: each token's
    chosen experts that are held here, weighted; the rows are sorted by
    expert and each multiplied by its own expert's matrices (not every row
    by every expert). What the absent experts would add is left out."""
    m = dims(cfg)
    t, k = flat.shape[0], cfg["num_experts_per_tok"]
    chosen, weights = route(flat, w["router"], w["bias"], cfg, quant)
    local = (chosen - m["first"]).reshape(-1)               # [T * k]
    held = (local >= 0) & (local < m["held"])
    group = jnp.where(held, local, m["held"])
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((m["held"] + 1,), jnp.int32).at[group].add(
        1)[:m["held"]]
    rows = flat[order // k]
    hid = (_silu(_grouped(rows, w["ewg"], sizes, quant))
           * _grouped(rows, w["ewu"], sizes, quant))
    out = _grouped(hid, w["ewd"], sizes, quant)
    # Rows behind the held groups belong to no expert here.
    scale = jnp.where(held, weights.reshape(-1), 0.0)[order]
    out = jnp.where(scale[:, None] != 0.0, out * scale[:, None], 0.0)
    return jnp.zeros_like(flat).at[order // k].add(out)


def _experts(x, w, cfg, quant):
    b, ln, d = x.shape
    flat = x.reshape(b * ln, d)
    block = min(TOKEN_BLOCK, flat.shape[0])
    assert flat.shape[0] % block == 0, "pad to whole token blocks"
    routed = jax.lax.map(lambda part: _routed(part, w, cfg, quant),
                         flat.reshape(-1, block, d)).reshape(flat.shape)
    shared = _swiglu(flat, w["swg"], w["swu"], w["swd"], quant)
    return (routed + shared).reshape(b, ln, d)


def layer_forward(x, w, kind: tuple, cfg: dict, quant=None):
    """One block on ``x`` [B, L, d] with its weights ``w``."""
    eps = cfg["rms_norm_eps"]
    # Assumed: the norm sits on the sublayer's OUTPUT, none on its input.
    x = x + _rms(_attention(x, w, kind[0], cfg, quant), w["norm1"], eps)
    if kind[1] == "dense":
        y = _swiglu(x, w["wg"], w["wu"], w["wd"], quant)
    else:
        y = _experts(x, w, cfg, quant)
    return x + _rms(y, w["norm2"], eps)


def forward(params: dict, tokens, cfg: dict, *, quant=None):
    """Logits [B, L, vocabulary slice] of int tokens [B, L]: the
    full-sequence causal forward, a layer's weights made as it is
    reached."""
    x = params["wte"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        kind = layer_kind(cfg, i)
        # Layer i's weights wait for layer i - 1's output: never two
        # layers' float32 weights alive at once.
        key, x = jax.lax.optimization_barrier((params["layer_keys"][i], x))
        x = layer_forward(x, layer_weights(key, kind, cfg), kind, cfg, quant)
    x = _rms(x, params["lnf"], cfg["rms_norm_eps"])
    return _mm(x, params["head_w"], quant)


# -- the program at these sizes -------------------------------------------
# The seam between the benchmark and the system under test: the repo's
# model through its normal constructor, its ``init`` handing out the
# reference's weights, laid into the tree the program names its parameters
# by. The only place that knows those names.

def block_name(i: int) -> str:
    return "block" if i == 0 else f"block_{i}"


def to_program_layer(w: dict, kind: tuple, dtype) -> dict:
    """One layer's reference weights in ``build_exaone_moe_lm``'s tree;
    matrices rounded once to ``dtype``, everything else float32 (norms,
    the router and its bias)."""
    w = {k: (v.astype(dtype) if k in _MATRICES else v) for k, v in w.items()}
    attn = {k: w[k] for k in ("wq", "wk", "wv", "q_norm", "k_norm", "wo")}
    if kind[1] == "dense":
        ffn = ("gatedmlp", {k: w[k] for k in ("wg", "wu", "wd")})
    else:
        ffn = ("routedexperts", {
            "router": w["router"], "bias": w["bias"], "wg": w["ewg"],
            "wu": w["ewu"], "wd": w["ewd"], "shared_wg": w["swg"],
            "shared_wu": w["swu"], "shared_wd": w["swd"]})
    return {
        "residual": {"main": {"groupedqueryattention": attn,
                              "rmsnorm": {"gamma": w["norm1"]}}},
        "residual_1": {"main": {ffn[0]: ffn[1],
                                "rmsnorm": {"gamma": w["norm2"]}}}}


def build_program(cfg: dict, seed: int):
    """The repo's ``exaone_moe`` LM at ``cfg``'s widths whose ``init``
    hands out the benchmark's weights: made on the device a layer at a
    time from the seed, matrices in the policy's compute dtype (one copy,
    no float32 twin: the engine serves what it is handed)."""
    from tpu_dist.models.hybrid import build_exaone_moe_lm
    from tpu_dist.models.policy import compute_dtype

    model = build_exaone_moe_lm(cfg)
    dtype = compute_dtype()
    make_layer = jax.jit(
        lambda key, kind: to_program_layer(layer_weights(key, kind, cfg),
                                           kind, dtype),
        static_argnums=1)
    small = jax.jit(lambda key: make_params(key, cfg))

    def init(_seed=0, input_shape=None):
        p = small(seed_key(seed))
        tree = {"streamembedding": {"table": p["wte"].astype(dtype)},
                "rmsnorm": {"gamma": p["lnf"]},
                "dense": {"kernel": p["head_w"].astype(dtype)}}
        for i in range(cfg["num_hidden_layers"]):
            tree[block_name(i)] = make_layer(p["layer_keys"][i],
                                             layer_kind(cfg, i))
        return {"params": tree, "state": {}}

    theirs = jax.eval_shape(lambda: model.init(0))["params"]
    ours = jax.eval_shape(init)["params"]
    shape = lambda t: jax.tree_util.tree_map(lambda s: s.shape, t)
    if shape(theirs) != shape(ours):
        raise RuntimeError("the program's parameter tree is not the one "
                           "this family lays its weights into")
    model.init = init
    return model


# -- sizes and work from shapes -------------------------------------------


def sizes(cfg: dict) -> dict:
    """``n_vocab``: the vocabulary slice the traffic draws from and the
    decode program's logits are told by; ``n_ctx``: the longest sequence
    the cell serves and the reference's pad (the model's own limit is
    ``max_position_embeddings``)."""
    return {"n_vocab": cfg["vocab_size"], "n_ctx": cfg["served_positions"]}


def layer_counts(cfg: dict) -> dict:
    kinds = [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    return {"window": sum(k[0] == "window" for k in kinds),
            "full": sum(k[0] == "full" for k in kinds),
            "dense": sum(k[1] == "dense" for k in kinds),
            "moe": sum(k[1] == "moe" for k in kinds)}


def part_params(cfg: dict) -> dict:
    """Matrix parameters of each part (what a token multiplies)."""
    m = dims(cfg)
    d = m["d"]
    return {
        "attention": 2 * d * m["heads"] * m["dk"] + 2 * d * m["kv"] * m["dk"],
        "dense": 3 * d * m["f"], "expert": 3 * d * m["fe"],
        "shared": 3 * d * m["fs"], "router": d * m["routed"],
        "head": d * m["vocab"], "embedding": m["vocab"] * d,
    }


def non_expert_matmul_params(cfg: dict) -> int:
    """What every token multiplies whatever the router says."""
    p, n = part_params(cfg), layer_counts(cfg)
    return ((n["window"] + n["full"]) * p["attention"]
            + n["dense"] * p["dense"]
            + n["moe"] * (p["shared"] + p["router"]) + p["head"])


def param_count(cfg: dict) -> int:
    p, n = part_params(cfg), layer_counts(cfg)
    return (non_expert_matmul_params(cfg) + p["embedding"]
            + n["moe"] * cfg["num_experts"] * p["expert"])


def decode_step_flops(cfg: dict, contexts) -> float:
    """FLOPs one decode step needs for its active slots (``contexts``:
    each slot's context length): 2 x the matrix parameters a token touches
    (the experts in expectation: ``num_experts_per_tok`` x held / routed),
    and scores and values over each context: the whole of it in a full
    layer, ``sliding_window`` keys at most in a window layer."""
    m, n, p = dims(cfg), layer_counts(cfg), part_params(cfg)
    tokens = len(contexts)
    expected = cfg["num_experts_per_tok"] * m["held"] / m["routed"]
    weights = non_expert_matmul_params(cfg) + n["moe"] * expected * p["expert"]
    keys = (n["full"] * float(sum(contexts))
            + n["window"] * float(sum(min(c, m["window"]) for c in contexts)))
    return tokens * 2 * weights + 2 * 2 * m["heads"] * m["dk"] * keys


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """Pool bytes one cached position pins: a K and a V row a FULL layer
    (a window layer's ring does not grow with the sequence)."""
    m = dims(cfg)
    item = {"bf16": 2, "fp32": 4}[kv_dtype]
    return layer_counts(cfg)["full"] * 2 * m["kv"] * m["dk"] * item


def window_bytes_per_slot(cfg: dict, kv_dtype: str) -> int:
    """What a slot's window layers hold beside its pages: a ring of
    ``sliding_window`` K and V rows a layer, whatever the length."""
    m = dims(cfg)
    item = {"bf16": 2, "fp32": 4}[kv_dtype]
    return (layer_counts(cfg)["window"] * 2 * m["window"] * m["kv"] * m["dk"]
            * item)


def decode_step_bytes(cfg: dict, live_tokens: int, kv_dtype: str,
                      weight_itemsize: int = 2) -> float:
    """A LOWER bound on what one decode step reads: the non-expert
    matrices once and the full layers' live K/V positions. **Left out**,
    because the harness hands this function the sum of the contexts and
    not how many slots were active: the experts touched (8.45 GB when all
    sixteen of every layer are) and the window layers' rings (at most
    ``sliding_window`` keys a slot and a layer, 0.2 GB at 64 slots). A
    share built on this reads low, never high: never over 100 %."""
    return (non_expert_matmul_params(cfg) * weight_itemsize
            + live_tokens * kv_bytes_per_token(cfg, kv_dtype))
