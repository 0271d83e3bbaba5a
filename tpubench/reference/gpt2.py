"""The GPT-2 family: everything the benchmark knows of it, in one module
that a configuration names under ``reference`` (``harness/cells.py`` states
the interface; no other file of the benchmark names this family or reads
its keys).

Three parts:

* **The plain reference** (``make_params``, ``forward``, ``loss_sum``):
  weights from a key, forward and loss in straightforward ``jax.numpy``
  and float32. It imports nothing of ``tpu_dist`` and takes nothing that
  the program has made. It follows OpenAI's GPT-2 (pre-LN blocks, learned
  positions, tanh-GELU, LayerNorm eps 1e-5) with the departures that
  ``PERF.md`` section 4 states for the repo's block: separate biased
  ``wq/wk/wv`` projections instead of one ``c_attn``, and an untied,
  biased vocabulary head.
* **The program at these sizes** (``build_program`` and the leaf names):
  the repo's LM through its normal constructor, whose ``init`` hands out
  the reference's weights. Only ``build_program`` imports ``tpu_dist``.
* **Sizes and work from shapes** (``sizes``, ``train_flops_per_token``,
  ``train_kernels``, ``decode_step_flops``, ``decode_step_bytes``,
  ``kv_bytes_per_token``): what the algorithm needs, whatever implements
  it: causal attention is counted over the lower triangle, recomputation
  is never counted, and a weight is read once. A share of a peak built on
  them cannot pass 100 % unless the time leaves out part of the work.

Sizes (``cfg``) use the published key names: ``n_vocab, n_ctx, n_embd,
n_head, n_layer`` and ``n_inner`` (4 * n_embd).

Layer parameters are stacked on a leading ``n_layer`` axis, so a leaf is
named ``wte`` or ``h.wq`` and ``h.*`` leaves hold every layer. ``quant``
selects the precision of every matrix multiplication's operands:

* ``None``   — float32, and the caller sets ``highest`` matmul precision;
* ``"fp8"``  — operands rounded to float8 e4m3 with one scale per tensor
  (the control: the nearest precision below the bf16 the cells state);
* ``"bf16"`` — operands rounded to bfloat16 (a witness, never a limit).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from tpubench.harness.reference import norm, seed_key

LN_EPS = 1e-5
#: Stacked (per-layer) leaves: name -> (shape builder, kind).
_BLOCK_LEAVES = (
    ("ln1_g", lambda d, f: (d,), "ones"), ("ln1_b", lambda d, f: (d,), "bias"),
    ("wq", lambda d, f: (d, d), "matrix"), ("bq", lambda d, f: (d,), "bias"),
    ("wk", lambda d, f: (d, d), "matrix"), ("bk", lambda d, f: (d,), "bias"),
    ("wv", lambda d, f: (d, d), "matrix"), ("bv", lambda d, f: (d,), "bias"),
    ("wo", lambda d, f: (d, d), "matrix"), ("bo", lambda d, f: (d,), "bias"),
    ("ln2_g", lambda d, f: (d,), "ones"), ("ln2_b", lambda d, f: (d,), "bias"),
    ("w1", lambda d, f: (d, f), "matrix"), ("b1", lambda d, f: (f,), "bias"),
    ("w2", lambda d, f: (f, d), "matrix"), ("b2", lambda d, f: (d,), "bias"),
)
BLOCK_LEAF_NAMES = tuple(n for n, _, _ in _BLOCK_LEAVES)


def _leaf(key, shape, kind):
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind in ("bias", "embed"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    # Glorot-uniform matrices, the family the repo's layers draw from.
    limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def make_params(key, cfg: dict) -> dict:
    """Every weight of the model from one key; jit this whole."""
    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["n_vocab"]
    n_layer = cfg["n_layer"]
    names = ["wte", "wpe", "lnf_b", "head_w", "head_b"] + [
        "h." + n for n in BLOCK_LEAF_NAMES]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    params = {
        "wte": _leaf(keys["wte"], (v, d), "embed"),
        "wpe": _leaf(keys["wpe"], (cfg["n_ctx"], d), "embed"),
        "lnf_g": jnp.ones((d,), jnp.float32),
        "lnf_b": _leaf(keys["lnf_b"], (d,), "bias"),
        "head_w": _leaf(keys["head_w"], (d, v), "matrix"),
        "head_b": _leaf(keys["head_b"], (v,), "bias"),
    }
    for name, shape_of, kind in _BLOCK_LEAVES:
        params["h." + name] = _leaf(keys["h." + name],
                                    (n_layer, *shape_of(d, f)), kind)
    return params


# -- forward --------------------------------------------------------------


def _round_operand(x, quant):
    """``x`` rounded to the lower precision, straight through: the
    backward pass sees the rounded operands but is itself not rounded (an
    unscaled float8 cotangent would flush to zero, which is a crash and
    not a lower precision)."""
    if quant is None:
        return x
    if quant == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    return jnp.matmul(_round_operand(a, quant), _round_operand(b, quant))


def _layer_norm(x, g, b):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, quant):
    """One pre-LN block on ``x`` [B, L, D]; ``p`` holds one layer."""
    b, ln, d = x.shape
    dk = d // n_head
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"])

    def heads(w, bias):
        y = _mm(h, w, quant) + bias
        return y.reshape(b, ln, n_head, dk).transpose(0, 2, 1, 3)

    q, k, v = (heads(p["wq"], p["bq"]), heads(p["wk"], p["bk"]),
               heads(p["wv"], p["bv"]))
    s = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(dk)
    mask = jnp.tril(jnp.ones((ln, ln), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = _mm(jax.nn.softmax(s, axis=-1), v, quant)
    a = a.transpose(0, 2, 1, 3).reshape(b, ln, d)
    x = x + _mm(a, p["wo"], quant) + p["bo"]
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    h = _gelu(_mm(h, p["w1"], quant) + p["b1"])
    return x + _mm(h, p["w2"], quant) + p["b2"]


def forward(params: dict, tokens, cfg: dict, *, quant=None, remat=False):
    """Logits [B, L, n_vocab] of int tokens [B, L]: the full-sequence
    causal forward, no cache, no kernel."""
    ln = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:ln]
    stacked = {n: params["h." + n] for n in BLOCK_LEAF_NAMES}
    block = functools.partial(_block, n_head=cfg["n_head"], quant=quant)
    if remat:
        block = jax.checkpoint(block)

    def body(x, p):
        return block(x, p), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    return _mm(x, params["head_w"], quant) + params["head_b"]


# -- training -------------------------------------------------------------


def loss_sum(params, x, y, cfg, quant=None):
    """Summed next-token cross-entropy of rows ``x`` against ``y``."""
    logits = forward(params, x, cfg, quant=quant, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).sum()


# -- the program at these sizes -------------------------------------------
# The seam between the benchmark and the system under test. From
# ``tpu_dist`` the benchmark takes the entry points (``Model.fit``,
# ``ServeEngine.submit``/``step``), the counters of ``observe.metrics`` and
# nothing else. This part builds the repo's LM at a configuration's widths
# and lays the reference's weights into the tree the program names its
# parameters by. It is the only place that knows those names.


def block_name(i: int) -> str:
    return "block" if i == 0 else f"block_{i}"


def to_program_tree(p: dict, cfg: dict) -> dict:
    """Reference weights (stacked layers) in ``build_transformer_lm``'s
    parameter tree."""
    tree = {"embedding": {"table": p["wte"]},
            "positionalembedding": {"table": p["wpe"]},
            "layernormalization": {"gamma": p["lnf_g"], "beta": p["lnf_b"]},
            "dense": {"kernel": p["head_w"], "bias": p["head_b"]}}
    for i in range(cfg["n_layer"]):
        h = {n: p["h." + n][i] for n in BLOCK_LEAF_NAMES}
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


def canonical_leaves(tree: dict, cfg: dict) -> dict:
    """The program's tree flattened to the reference's leaf names
    (``wte``, ``h3.wq``, ...)."""
    out = {"wte": tree["embedding"]["table"],
           "wpe": tree["positionalembedding"]["table"],
           "lnf_g": tree["layernormalization"]["gamma"],
           "lnf_b": tree["layernormalization"]["beta"],
           "head_w": tree["dense"]["kernel"],
           "head_b": tree["dense"]["bias"]}
    for i in range(cfg["n_layer"]):
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


def build_program(cfg: dict, seed: int):
    """The repo's LM at ``cfg``'s widths whose ``init`` hands out the
    benchmark's weights: made on the device in one jitted call from the
    seed, float32 as the program holds them."""
    from tpu_dist.models.transformer import build_transformer_lm

    model = build_transformer_lm(
        cfg["n_vocab"], cfg["n_ctx"], d_model=cfg["n_embd"],
        depth=cfg["n_layer"], num_heads=cfg["n_head"], ff_dim=cfg["n_inner"])
    theirs = jax.eval_shape(lambda: model.init(0))["params"]
    make = jax.jit(lambda key: to_program_tree(make_params(key, cfg), cfg))
    ours = jax.eval_shape(make, seed_key(seed))
    a = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), theirs)
    b = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), ours)
    if a != b:
        raise RuntimeError(
            "the program's parameter tree is not the one "
            "this family lays its weights into")

    def init(_seed=0, input_shape=None):
        return {"params": make(seed_key(seed)), "state": {}}

    model.init = init
    return model


def program_grad_norms(cfg: dict, beta_1: float = 0.9):
    """jit: Adam's first moment after ONE step -> leaf norms of the
    gradient as the optimizer got it (mu = (1 - beta_1) * g)."""
    def fn(mu):
        return {k: norm(v) / (1.0 - beta_1)
                for k, v in canonical_leaves(mu, cfg).items()}

    return jax.jit(fn)


def program_delta_norms(cfg: dict):
    """jit: (params now, seed key) -> leaf norms of the change since the
    weights the seed gives."""
    def fn(params, key):
        start = to_program_tree(make_params(key, cfg), cfg)
        now = canonical_leaves(params, cfg)
        then = canonical_leaves(start, cfg)
        return {k: norm(now[k] - then[k]) for k in now}

    return jax.jit(fn)


# -- sizes and work from shapes -------------------------------------------


def sizes(cfg: dict) -> dict:
    """The sizes the harness and the metric patterns ask for by name:
    ``n_vocab`` the traffic draws its ids from and the decode program's
    logits are told by, ``n_ctx`` the longest sequence."""
    return {"n_vocab": cfg["n_vocab"], "n_ctx": cfg["n_ctx"]}


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: the blocks' projections and MLP, and the vocabulary head."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + d * cfg["n_vocab"]


def param_count(cfg: dict) -> int:
    """Every parameter of the repo's GPT-2 block (untied, biased head)."""
    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["n_vocab"]
    per_layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    return (v * d + cfg["n_ctx"] * d + cfg["n_layer"] * per_layer
            + 2 * d + d * v + v)


def attention_flops(cfg: dict, seq: int, *, causal: bool = True) -> float:
    """Forward FLOPs of one layer's QK^T and PV for ONE sequence."""
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    return 2 * 2 * pairs * cfg["n_embd"]


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs per token of a full causal sequence of ``seq``."""
    attn = cfg["n_layer"] * attention_flops(cfg, seq) / seq
    return 2 * matmul_params(cfg) + attn


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); nothing recomputed."""
    return 3 * forward_flops_per_token(cfg, seq)


def flash_fwd_work(cfg: dict, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) of one layer's causal attention forward over
    ``batch`` sequences: reads q, k, v, writes o."""
    flops = batch * attention_flops(cfg, seq)
    bytes_ = 4 * batch * seq * cfg["n_embd"] * itemsize
    return flops, bytes_


def flash_bwd_work(cfg: dict, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) of one layer's attention backward. It needs four
    products (dV, dP, dQ, dK), twice the forward's two; forming QK^T
    again is recomputation and is not counted. Reads q, k, v, o, do;
    writes dq, dk, dv."""
    flops = 2 * batch * attention_flops(cfg, seq)
    bytes_ = 8 * batch * seq * cfg["n_embd"] * itemsize
    return flops, bytes_


def train_kernels(cfg: dict, rows: int, seq: int) -> dict:
    """Kernel name -> (FLOPs, bytes) that ONE training step of ``rows``
    sequences asks of it on a device, summed over the layers that call
    it: what ``kernel_roofline`` divides by the kernel's device time."""
    calls = cfg["n_layer"]
    fwd, bwd = flash_fwd_work(cfg, rows, seq), flash_bwd_work(cfg, rows, seq)
    return {"flash_fwd": (fwd[0] * calls, fwd[1] * calls),
            "flash_bwd": (bwd[0] * calls, bwd[1] * calls)}


def decode_step_flops(cfg: dict, contexts) -> float:
    """FLOPs one decode step needs for the slots active in it;
    ``contexts`` holds each active slot's context length (tokens its new
    query attends over)."""
    n = len(contexts)
    attn = 2 * 2 * cfg["n_embd"] * cfg["n_layer"] * float(sum(contexts))
    return n * 2 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """Pool bytes one cached position pins over all layers, K and V; an
    int8 position also carries one float32 scale per head, K and V."""
    per = 2 * cfg["n_layer"] * cfg["n_embd"]
    if kv_dtype == "int8":
        return per + 2 * cfg["n_layer"] * cfg["n_head"] * 4
    return per * {"bf16": 2, "fp32": 4}[kv_dtype]


def decode_step_bytes(cfg: dict, live_tokens: int, kv_dtype: str,
                      weight_itemsize: int = 2) -> float:
    """Bytes one decode step has to read: the multiplied weights once in
    the compute dtype, and the live K/V positions at the pool's dtype."""
    return (matmul_params(cfg) * weight_itemsize
            + live_tokens * kv_bytes_per_token(cfg, kv_dtype))
