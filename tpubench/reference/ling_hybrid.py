"""The ``bailing_hybrid`` family (Ling-3.0-flash): everything the benchmark
knows of it, in one module that a configuration names under ``reference``
(``harness/cells.py`` states the interface).

Three parts, as ``gpt2.py`` has them: **the plain reference**
(``make_params``, ``layer_weights``, ``forward``: straightforward
``jax.numpy``, float32, the caller sets ``highest``; sequential scan for
the delta rule, expanded latent attention, every held expert computed
densely and masked; no cache, no kernel, nothing of ``tpu_dist``), **the
program at these sizes** (``build_program``, the only importer of
``tpu_dist``) and **sizes and work from shapes**.

Equations (``d`` = ``hidden_size``, eps = ``rms_norm_eps``, no biases;
``cfg`` keeps the published key names of ``config.json``):

* Block ``i``, pre-norm: ``h = x + Attn_i(RMSNorm(x))``,
  ``y = h + FFN_i(RMSNorm(h))``. ``Attn_i`` is MLA where
  ``(i + 1) % layer_group_size == 0``, else KDA (the config's
  ``layer_group_size`` 6 gives 35 KDA to 7 MLA in the 42 published layers;
  the catalog's prose says 3 : 1 and the config is trusted). ``FFN_i`` is
  a dense SwiGLU of width ``intermediate_size`` for
  ``i < first_k_dense_replace``, else the expert layer. The swiglu clamps
  (``expert_swiglu_limit_list``, ``share_expert_swiglu_limit_list``) are 0
  for every layer below 34 and so for every layer of the cut.
* MLA (``q_lora_rank`` null): ``q = W_q x`` -> per head
  ``[q_nope(128); q_rope(64)]``; ``[c; k_r] = W_kva x``, ``c`` in
  R^``kv_lora_rank``, ``k_r`` in R^64; ``c <- RMSNorm(c)``;
  ``[k_nope_h; v_h] = W_kvb,h c``; ``k_r``, ``q_rope`` <- RoPE (interleaved
  pairs, ``rope_theta``); ``s = (q_nope . k_nope + q_rope . k_r) /
  sqrt(192)``, causal softmax, ``o_h = sum p v_h``; head-wise output gate
  ``o_h <- sigmoid(w_g,h . x) o_h``; ``out = W_o [o_h]``. A cache would
  hold ``[c; k_r]`` a token (``latent_width``: 576 values).
* KDA (Kimi Delta Attention, arXiv:2510.26692): ``q^, k^, v =
  SiLU(conv4(W_{q,k,v} x))`` (causal, depthwise);
  ``q = l2norm(q^_h) / sqrt(d_k)``, ``k = l2norm(k^_h)``;
  ``beta = sigmoid(W_beta x)`` a head; per-channel log-decay
  ``g = lb * sigmoid(exp(A_h) * (W_a x + b))``, ``lb`` =
  ``kda_lower_bound``, ``alpha = exp(g)``; per head
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; ``o <- RMSNorm_head(o)`` (``group_norm_size`` 1)
  times the head-wise gate ``sigmoid(w_g,h . x)``; ``out = W_o [o_h]``.
* Expert layer: ``s = sigmoid(W_r x)`` in float32; choice by ``s + b``
  (``moe_router_enable_expert_bias``): a group's score is the sum of its
  two best ``s + b``, the ``topk_group`` best groups stay, the
  ``num_experts_per_tok`` best experts in them are chosen (``noaux_tc``);
  ``w_e = routed_scaling_factor * s_e / sum_chosen s`` (``norm_topk_prob``);
  ``y = sum_e w_e E_e(x) + E_shared(x)``, ``E(x) = W_d (SiLU(W_g x) * W_u
  x)``. Only the experts ``experts_held = [first, first + count)`` are
  here: the router keeps its published width, and what the absent experts
  would add is left out (here and in the program alike).
* Head: final RMSNorm, untied ``W_out`` over the vocabulary slice. The
  multi-token-prediction module takes no part in next-token logits and is
  not made.

**Assumed** (the config does not say; ``configs/*.json`` lists them):

* ``use_qk_norm`` in MLA: RMSNorm (learned scale) over the 192 of each
  ``q_h`` and over the 64 of the shared ``k_r``, both before RoPE; the
  latent ``c`` has its own RMSNorm above. **Departure from the issue's
  guess** (a norm over each head's ``[k_nope_h; k_r]``): that scale
  differs by head and by position, so it cannot be folded into an absorbed
  decode over a 576-value latent cache; a norm that a latent cache cannot
  carry is not what a model built on one would use.
* The KDA gate's form above (``kda_safe_gate``, after
  flash-linear-attention's gate with a lower bound); the paper's own is
  ``g = -exp(A) softplus(.)``.
* ``A``, ``b``, the expert bias and every weight are random from the seed;
  ``b`` is drawn around -3 so that a channel forgets over some tokens and
  not at once, the expert bias small so that it moves some choices.
  Matrices are bfloat16 VALUES, as a published checkpoint stores them:
  the reference computes in float32 on the same numbers the program
  holds, so the comparison reads the precision of the arithmetic and not
  the rounding of the checkpoint.
* The scale of the draw: ONE draw, fixed below as constants with their
  reasons (``EMBEDDING_STD``, ``RESIDUAL_SCALE``, ``ROUTED_DOWN_SCALE``);
  no configuration carries a knob for it.

**Computed in blocks.** The cut's weights are 21 GB in float32, more than
the chip: ``make_params`` returns the embedding, the head, the last norm
and ONE KEY A LAYER; ``forward`` makes layer ``i``'s weights from its key
inside the loop (3.3 GB at a time, behind an optimisation barrier so that
no two layers' weights are alive together), and the held experts are
computed eight at a time. ``build_program`` makes the same weights the
same way, a layer at a time, matrices rounded once to the policy's
compute dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tpubench.harness.reference import seed_key

EXPERT_BLOCK = 8

# The draw of the random weights. A checkpoint is trained; these are not,
# and the comparison that decides ``correct`` reads SERVED TOKENS against
# this module's logits, so the draw has to put a random network where a
# trained one is: each block moves the residual stream by a fraction. With
# every matrix Glorot and a 0.02 embedding each block REPLACED the stream,
# and bf16 could not be told from fp8 (``served_logit_gap`` 2.26 on the
# first chip run of PR 28).

#: Embedding rows of unit scale: the stream starts at the size RMSNorm
#: would give it, so the first blocks add to it instead of drowning it.
EMBEDDING_STD = 1.0

#: The matrices that write into the stream (``wo``, dense and shared
#: ``wd``) are a quarter of Glorot: eight blocks then explain about a fifth
#: of the logits' variance and move more than half of the argmaxes.
RESIDUAL_SCALE = 0.25

#: The routed experts' ``W_d``, again as a share of Glorot. The router's
#: choice is discrete: under bf16 a few tokens in a hundred put another
#: expert (sometimes another group of them) eighth than float32 does, and
#: such a token's logits move by that expert's whole output, whatever the
#: precision of the rest. ``served_logit_gap`` is a maximum over thousands
#: of tokens, so it reads the WORST flip: at the shared expert's scale
#: (0.25) a sound bf16 pass reads 0.2-0.3 against 0.4-0.45 for the fp8
#: control (the reference alone, operands rounded, hidden 512 on the CPU),
#: and no limit stands between them. A flip and a fault scale alike with
#: this number (all held experts dropped reads 2.5-3 x the worst flip, one
#: expert's ``W_d`` in another's place 4 x), so it is set where the worst
#: flip sinks to the level of the other bf16 rounding: there the planted
#: faults still read over the cell's limit of 0.04 (on the chip: experts
#: dropped 0.087-0.094, a neighbour's ``W_d`` 0.110-0.121, sound at most
#: 0.024 in 34 runs; ``PERF.md`` section 6) and fp8 five times over it.
ROUTED_DOWN_SCALE = 0.03


# -- sizes read from the configuration ------------------------------------


def dims(cfg: dict) -> dict:
    """The handful of derived sizes every part below uses."""
    first, count = cfg["experts_held"]
    assert count == cfg["num_experts"], "num_experts is the number held"
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "dk": cfg["head_dim"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "rank": cfg["kv_lora_rank"], "f": cfg["intermediate_size"],
        "fe": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "routed": cfg["num_experts_published"], "first": first,
        "held": count, "taps": cfg["short_conv_kernel_size"],
        "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
    }


def layer_kind(cfg: dict, i: int) -> tuple:
    """("mla" | "kda", "dense" | "moe") of layer ``i``."""
    attn = "mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
    ffn = "dense" if i < cfg["first_k_dense_replace"] else "moe"
    return attn, ffn


# -- weights ----------------------------------------------------------------


#: The leaves a checkpoint stores in bfloat16 (the rest are float32: norms,
#: the router and its bias, the convolution taps, ``A`` and ``b``).
_MATRICES = ("wq", "wk", "wv", "wa", "wkva", "wkvb", "wo", "wg", "wu", "wd",
             "ewg", "ewu", "ewd", "swg", "swu", "swd", "wbeta", "wgate")


def _glorot(key, shape):
    limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def layer_weights(key, kind: tuple, cfg: dict) -> dict:
    """One layer's float32 weights from its key; ``kind`` is
    :func:`layer_kind`'s pair (static: three kinds in all). The matrices
    that write into the residual stream are drawn smaller than Glorot:
    ``wo``, ``wd``, ``swd`` by ``RESIDUAL_SCALE``, the routed ``ewd`` by
    ``ROUTED_DOWN_SCALE``."""
    m = dims(cfg)
    d, h = m["d"], m["heads"]
    attn, ffn = kind
    ks = iter(jax.random.split(key, 32))
    out = lambda k, shape: RESIDUAL_SCALE * _glorot(k, shape)
    w = {"norm1": jnp.ones((d,), jnp.float32),
         "norm2": jnp.ones((d,), jnp.float32)}
    if attn == "mla":
        qk = m["nope"] + m["rope"]
        w.update(
            wq=_glorot(next(ks), (d, h * qk)),
            wkva=_glorot(next(ks), (d, m["rank"] + m["rope"])),
            c_norm=jnp.ones((m["rank"],), jnp.float32),
            wkvb=_glorot(next(ks), (m["rank"], h * (m["nope"] + m["dv"]))),
            q_norm=jnp.ones((qk,), jnp.float32),
            kr_norm=jnp.ones((m["rope"],), jnp.float32),
            wgate=_glorot(next(ks), (d, h)),
            wo=out(next(ks), (h * m["dv"], d)))
    else:
        hd = h * m["dk"]
        w.update(
            wq=_glorot(next(ks), (d, hd)), wk=_glorot(next(ks), (d, hd)),
            wv=_glorot(next(ks), (d, hd)),
            conv=jax.random.uniform(next(ks), (m["taps"], 3 * hd),
                                    jnp.float32, -0.5, 0.5),
            wbeta=_glorot(next(ks), (d, h)), wa=_glorot(next(ks), (d, hd)),
            a_log=jax.random.uniform(next(ks), (h,), jnp.float32, -0.5, 0.5),
            dt_bias=-3.0 + 0.5 * jax.random.normal(next(ks), (hd,),
                                                   jnp.float32),
            o_norm=jnp.ones((m["dk"],), jnp.float32),
            wgate=_glorot(next(ks), (d, h)), wo=out(next(ks), (hd, d)))
    if ffn == "dense":
        w.update(wg=_glorot(next(ks), (d, m["f"])),
                 wu=_glorot(next(ks), (d, m["f"])),
                 wd=out(next(ks), (m["f"], d)))
    else:
        e, fe, fs = m["held"], m["fe"], m["fs"]
        w.update(
            router=_glorot(next(ks), (d, m["routed"])),
            bias=0.03 * jax.random.normal(next(ks), (m["routed"],),
                                          jnp.float32),
            ewg=_glorot(next(ks), (e, d, fe)),
            ewu=_glorot(next(ks), (e, d, fe)),
            ewd=ROUTED_DOWN_SCALE * _glorot(next(ks), (e, fe, d)),
            swg=_glorot(next(ks), (d, fs)), swu=_glorot(next(ks), (d, fs)),
            swd=out(next(ks), (fs, d)))
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
    """Interleaved pairs ``(x[2i], x[2i+1])`` of ``x`` [..., L, n] turned
    by ``pos * theta ** (-2i / n)``."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos[:, None].astype(jnp.float32) * inv          # [L, n/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _mla(x, w, cfg, quant):
    m = dims(cfg)
    b, ln, _ = x.shape
    h, nope, rope, dv = m["heads"], m["nope"], m["rope"], m["dv"]
    eps, pos = cfg["rms_norm_eps"], jnp.arange(ln)
    q = _mm(x, w["wq"], quant).reshape(b, ln, h, nope + rope)
    q = _rms(q, w["q_norm"], eps).transpose(0, 2, 1, 3)     # [B, H, L, 192]
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos,
                                          cfg["rope_theta"])
    ckr = _mm(x, w["wkva"], quant)
    c = _rms(ckr[..., :m["rank"]], w["c_norm"], eps)        # [B, L, r]
    k_r = _rope(_rms(ckr[..., m["rank"]:], w["kr_norm"], eps), pos,
                cfg["rope_theta"])                          # [B, L, 64]
    kv = _mm(c, w["wkvb"], quant).reshape(b, ln, h, nope + dv)
    kv = kv.transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (_mm(q_nope, k_nope.transpose(0, 1, 3, 2), quant)
         + _mm(q_rope, k_r[:, None].transpose(0, 1, 3, 2), quant))
    s = s / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((ln, ln), bool)), s, -jnp.inf)
    o = _mm(jax.nn.softmax(s, axis=-1), v, quant)           # [B, H, L, dv]
    gate = jax.nn.sigmoid(_mm(x, w["wgate"], quant))        # [B, L, H]
    o = o.transpose(0, 2, 1, 3) * gate[..., None]
    return _mm(o.reshape(b, ln, h * dv), w["wo"], quant)


def _causal_conv(x, taps):
    """Depthwise causal convolution of ``x`` [B, L, C] with ``taps``
    [K, C]: ``y_t = sum_j taps[j] x_{t - K + 1 + j}``, zeros before 0."""
    k, ln = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + ln] for j in range(k))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(x, w, cfg, quant):
    m = dims(cfg)
    b, ln, _ = x.shape
    h, dk = m["heads"], m["dk"]
    qkv = jnp.concatenate([_mm(x, w[n], quant) for n in ("wq", "wk", "wv")],
                          axis=-1)
    qkv = _silu(_causal_conv(qkv, w["conv"]))
    heads = lambda y: y.reshape(b, ln, h, dk)
    q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
    q, k = _l2norm(q) / math.sqrt(dk), _l2norm(k)
    beta = jax.nn.sigmoid(_mm(x, w["wbeta"], quant))        # [B, L, H]
    a = heads(_mm(x, w["wa"], quant) + w["dt_bias"])
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[:, None] * a)                   # [B, L, H, dk]
    alpha = jnp.exp(g)

    def read(s, x_t):                                       # S^T x, [B, H, dv]
        return _mm(x_t[..., None, :], s, quant)[..., 0, :]

    def step(s, t):
        q_t, k_t, v_t, a_t, b_t = t                         # [B, H, ...]
        s = a_t[..., None] * s
        u = b_t[..., None] * (v_t - read(s, k_t))
        s = s + k_t[..., None] * u[..., None, :]
        return s, read(s, q_t)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dk), jnp.float32), seq)
    o = jnp.moveaxis(o, 0, 1)                               # [B, L, H, dv]
    gate = jax.nn.sigmoid(_mm(x, w["wgate"], quant))
    o = _rms(o, w["o_norm"], cfg["rms_norm_eps"]) * gate[..., None]
    return _mm(o.reshape(b, ln, h * dk), w["wo"], quant)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(_silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def route(x, router, bias, cfg: dict, quant=None):
    """(chosen expert ids [T, k], weights [T, k]) of tokens ``x`` [T, d]:
    sigmoid scores, group-limited choice by ``s + b``, weights from ``s``
    alone, normalised and scaled."""
    e, k = cfg["num_experts_published"], cfg["num_experts_per_tok"]
    groups, keep = cfg["n_group"], cfg["topk_group"]
    s = jax.nn.sigmoid(_mm(x, router, quant))               # [T, E]
    biased = s + bias
    per = biased.reshape(-1, groups, e // groups)
    group_score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)  # [T, G]
    kept = jax.lax.top_k(group_score, keep)[1]              # [T, keep]
    in_kept = jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)
    masked = jnp.where(jnp.repeat(in_kept, e // groups, axis=1), biased,
                       -jnp.inf)
    chosen = jax.lax.top_k(masked, k)[1]                    # [T, k]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weights = picked / jnp.sum(picked, axis=1, keepdims=True)
    return chosen, cfg["routed_scaling_factor"] * weights


def _experts(x, w, cfg, quant):
    m = dims(cfg)
    b, ln, d = x.shape
    flat = x.reshape(b * ln, d)
    chosen, weights = route(flat, w["router"], w["bias"], cfg, quant)
    # Token t's weight for held expert e: 0 unless e was chosen.
    local = chosen - m["first"]                             # [T, k]
    dense = jnp.sum(
        jnp.where(local[:, :, None] == jnp.arange(m["held"]),
                  weights[:, :, None], 0.0), axis=1)        # [T, held]

    def block(acc, part):
        wg, wu, wd, wt = part                               # 8 experts
        y = jax.vmap(lambda g, u, dn: _swiglu(flat, g, u, dn, quant))(
            wg, wu, wd)                                     # [8, T, d]
        return acc + jnp.einsum("etd,te->td", y, wt), None

    blocks = lambda a: a.reshape(m["held"] // EXPERT_BLOCK, EXPERT_BLOCK,
                                 *a.shape[1:])
    parts = (blocks(w["ewg"]), blocks(w["ewu"]), blocks(w["ewd"]),
             blocks(dense.T).transpose(0, 2, 1))
    routed, _ = jax.lax.scan(block, jnp.zeros_like(flat), parts)
    shared = _swiglu(flat, w["swg"], w["swu"], w["swd"], quant)
    return (routed + shared).reshape(b, ln, d)


def layer_forward(x, w, kind: tuple, cfg: dict, quant=None):
    """One block on ``x`` [B, L, d] with its weights ``w``."""
    eps = cfg["rms_norm_eps"]
    attn = _mla if kind[0] == "mla" else _kda
    x = x + attn(_rms(x, w["norm1"], eps), w, cfg, quant)
    h = _rms(x, w["norm2"], eps)
    if kind[1] == "dense":
        return x + _swiglu(h, w["wg"], w["wu"], w["wd"], quant)
    return x + _experts(h, w, cfg, quant)


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
    """One layer's reference weights in ``build_hybrid_lm``'s tree;
    matrices rounded once to ``dtype``, everything else float32 (norms,
    the router and its bias, the convolution taps, ``A`` and ``b``)."""
    w = {k: (v.astype(dtype) if k in _MATRICES else v) for k, v in w.items()}
    if kind[0] == "mla":
        attn = ("latentattention", {k: w[k] for k in (
            "wq", "wkva", "c_norm", "wkvb", "q_norm", "kr_norm", "wgate",
            "wo")})
    else:
        attn = ("deltaattention", {k: w[k] for k in (
            "wq", "wk", "wv", "conv", "wbeta", "wa", "a_log", "dt_bias",
            "o_norm", "wgate", "wo")})
    if kind[1] == "dense":
        ffn = ("gatedmlp", {k: w[k] for k in ("wg", "wu", "wd")})
    else:
        ffn = ("routedexperts", {
            "router": w["router"], "bias": w["bias"], "wg": w["ewg"],
            "wu": w["ewu"], "wd": w["ewd"], "shared_wg": w["swg"],
            "shared_wu": w["swu"], "shared_wd": w["swd"]})
    return {
        "residual": {"main": {"rmsnorm": {"gamma": w["norm1"]},
                              attn[0]: attn[1]}},
        "residual_1": {"main": {"rmsnorm": {"gamma": w["norm2"]},
                                ffn[0]: ffn[1]}}}


def build_program(cfg: dict, seed: int):
    """The repo's hybrid LM at ``cfg``'s widths whose ``init`` hands out
    the benchmark's weights: made on the device a layer at a time from the
    seed, matrices in the policy's compute dtype (one copy, no float32
    twin: the engine serves what it is handed)."""
    from tpu_dist.models.hybrid import build_hybrid_lm
    from tpu_dist.models.policy import compute_dtype

    model = build_hybrid_lm(cfg)
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
    return {"mla": sum(k[0] == "mla" for k in kinds),
            "kda": sum(k[0] == "kda" for k in kinds),
            "dense": sum(k[1] == "dense" for k in kinds),
            "moe": sum(k[1] == "moe" for k in kinds)}


def part_params(cfg: dict) -> dict:
    """Matrix parameters of each part (what a token multiplies)."""
    m = dims(cfg)
    d, h = m["d"], m["heads"]
    hd = h * m["dk"]
    return {
        "kda": 5 * d * hd + 2 * d * h,
        "mla": (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
                + m["rank"] * h * (m["nope"] + m["dv"]) + d * h
                + h * m["dv"] * d),
        "dense": 3 * d * m["f"], "expert": 3 * d * m["fe"],
        "shared": 3 * d * m["fs"], "router": d * m["routed"],
        "head": d * m["vocab"], "embedding": m["vocab"] * d,
    }


def non_expert_matmul_params(cfg: dict) -> int:
    """What every token multiplies whatever the router says."""
    p, n = part_params(cfg), layer_counts(cfg)
    return (n["kda"] * p["kda"] + n["mla"] * p["mla"] + n["dense"] * p["dense"]
            + n["moe"] * (p["shared"] + p["router"]) + p["head"])


def param_count(cfg: dict) -> int:
    p, n = part_params(cfg), layer_counts(cfg)
    return (non_expert_matmul_params(cfg) + p["embedding"]
            + n["moe"] * cfg["num_experts"] * p["expert"])


def decode_step_flops(cfg: dict, contexts) -> float:
    """FLOPs one decode step needs for its active slots (``contexts``:
    each slot's context length): 2 x the matrix parameters a token
    touches (the experts in expectation: ``num_experts_per_tok`` x held /
    routed), the absorbed MLA scores and values over each context, and the
    KDA state update and read."""
    m, n, p = dims(cfg), layer_counts(cfg), part_params(cfg)
    tokens = len(contexts)
    expected = cfg["num_experts_per_tok"] * m["held"] / m["routed"]
    weights = non_expert_matmul_params(cfg) + n["moe"] * expected * p["expert"]
    mla = 2 * 2 * m["heads"] * (m["rank"] + m["rope"]) * float(sum(contexts))
    kda = tokens * 3 * 2 * m["heads"] * m["dk"] * m["dk"]
    return tokens * 2 * weights + n["mla"] * mla + n["kda"] * kda


def kv_bytes_per_token(cfg: dict, kv_dtype: str) -> int:
    """Pool bytes one cached position pins: a latent row an MLA layer."""
    m = dims(cfg)
    item = {"bf16": 2, "fp32": 4}[kv_dtype]
    return layer_counts(cfg)["mla"] * (m["rank"] + m["rope"]) * item


def state_bytes_per_slot(cfg: dict) -> int:
    """What a slot's recurrent layers hold beside its pages: ``S``
    (float32) and the convolution's tail, a KDA layer."""
    m = dims(cfg)
    s = m["heads"] * m["dk"] * m["dk"] * 4
    tail = (m["taps"] - 1) * 3 * m["heads"] * m["dk"] * 4
    return layer_counts(cfg)["kda"] * (s + tail)


def decode_step_bytes(cfg: dict, live_tokens: int, kv_dtype: str,
                      weight_itemsize: int = 2) -> float:
    """A LOWER bound on what one decode step reads: the non-expert
    matrices once and the live latent positions. **Left out**, because
    the harness hands this function the sum of the contexts and not how
    many slots were active: the experts touched (5.8 GB at 64 slots) and
    the recurrent state read and written (1.9 GB). A share built on this
    reads low, never high."""
    return (non_expert_matmul_params(cfg) * weight_itemsize
            + live_tokens * kv_bytes_per_token(cfg, kv_dtype))
