"""Hybrid-attention LM layers: RMSNorm, RoPE (interleaved or half-split
pairs), a gated (SwiGLU) MLP, latent attention (MLA), delta-rule linear
attention (KDA) and grouped-query attention with an optional window, and
the constructors that build a causal LM from published ``config.json``
keys (:func:`build_hybrid_lm`, :func:`build_exaone_moe_lm`).

The layers follow the protocol of ``layers.py`` (immutable descriptions,
parameters owned by the caller), so :func:`build_hybrid_lm` returns the
same ``Sequential`` that ``build_transformer_lm`` does, and ``ServeEngine``
serves it through the same ``submit()``/``step()``. Each attention layer
keeps its mathematics in methods that BOTH its full-sequence ``apply`` and
the serving path (``serve/kv_cache.py``) call, so the cached path shares
weights and code with the plain forward:

* :class:`LatentAttention` — one latent row ``[c; k_r]`` a token, shared by
  all heads (``latent_width`` values). ``attend_expanded`` rebuilds
  ``k_nope`` and ``v`` from the latent (full forward, prefill);
  ``attend_absorbed`` folds ``W_kvb`` into the query and the output and
  attends over the latent rows themselves (decode).
* :class:`DeltaAttention` — per head a state ``S`` in R^{dk x dv}, float32:
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``. :func:`delta_rule_chunked` is the WY/UT form over
  blocks of 64 (full forward, prefill; state carried in and out),
  :func:`delta_rule_step` one token (decode).
* :class:`GroupedQueryAttention` — ``num_heads`` query heads over
  ``num_kv_heads`` K/V heads, a per-head RMSNorm on ``q`` and ``k``,
  optional RoPE, optional window. ``project`` gives the normed, rotated
  ``q`` and the ``k``/``v`` rows a cache holds; ``attend`` is the softmax
  over whatever keys a caller hands it (the whole sequence, a ring of the
  last ``window`` keys plus a chunk's own, a slot's gathered pages) and
  ``attend_blocks`` the same softmax a block of keys at a time.

Precision: matrices multiply in the policy's compute dtype; norms,
softmax, gates, the recurrent state and everything that touches it are
float32, the state's products at ``highest`` (on a TPU a default float32
product rounds its operands to bfloat16). **The residual stream is
float32** (:class:`StreamEmbedding` starts it so, :class:`RMSNorm` hands
each sublayer the compute dtype, and a float32 stream plus a bfloat16
update is float32): sixteen roundings of the whole stream to 8 bits were
most of the distance to the float32 reference, and every one of them
could flip a discrete expert choice; the stream is ``[tokens, hidden]``
and costs nothing to keep whole.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from tpu_dist.models.layers import Block, Dense, Layer, Residual
from tpu_dist.models.transformer import Embedding
from tpu_dist.ops import initializers

_HIGHEST = jax.lax.Precision.HIGHEST

#: Tokens a block of the chunked delta rule covers.
SCAN_BLOCK = 64


def _glorot(key, shape):
    return initializers.get("glorot_uniform")(key, shape)


def rms_norm(x, gamma, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` over the last axis, float32
    statistics, the input's dtype out."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma).astype(x.dtype)


def rope(x, pos, theta: float, *, interleaved: bool = True):
    """Rotary positions on ``x`` [..., L, n]: pair ``i`` is turned by
    ``pos * theta ** (-2i / n)``; ``pos`` is ``[L]`` or broadcastable
    ``[..., L]`` absolute positions. float32. The pairs are
    ``(x[2i], x[2i+1])`` when ``interleaved``, else the half-split
    ``(x[i], x[i + n/2])`` of the Hugging Face ``rotate_half``."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if not interleaved:
        a, b = xf[..., :n // 2], xf[..., n // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                               axis=-1)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


@dataclasses.dataclass(frozen=True, repr=False)
class RMSNorm(Layer):
    """RMSNorm over the last axis; statistics in float32 always, the
    policy's compute dtype out (what the next sublayer multiplies in)."""

    epsilon: float = 1e-6

    def init(self, key, in_shape):
        return {"gamma": jnp.ones((in_shape[-1],), jnp.float32)}, {}, in_shape

    def apply(self, params, state, x, *, training=False, rng=None):
        from tpu_dist.models.policy import compute_dtype

        y = rms_norm(x.astype(jnp.float32), params["gamma"], self.epsilon)
        return y.astype(compute_dtype()), state


@dataclasses.dataclass(frozen=True, repr=False)
class StreamEmbedding(Embedding):
    """Token embedding that starts a FLOAT32 residual stream whatever the
    policy (the table keeps the dtype it was handed)."""

    def apply(self, params, state, x, *, training=False, rng=None):
        return params["table"][x].astype(jnp.float32), state


@dataclasses.dataclass(frozen=True, repr=False)
class ComputeCast(Layer):
    """Hands a sublayer the float32 residual stream in the policy's
    compute dtype: what a pre-norm block's :class:`RMSNorm` does on the
    way, for a block whose norm sits on the sublayer's OUTPUT."""

    def init(self, key, in_shape):
        return {}, {}, in_shape

    def apply(self, params, state, x, *, training=False, rng=None):
        from tpu_dist.models.policy import compute_dtype

        return x.astype(compute_dtype()), state


@dataclasses.dataclass(frozen=True, repr=False)
class GatedMLP(Layer):
    """SwiGLU: ``W_d (SiLU(W_g x) * W_u x)``, no biases."""

    units: int

    def init(self, key, in_shape):
        d = in_shape[-1]
        kg, ku, kd = jax.random.split(key, 3)
        return ({"wg": _glorot(kg, (d, self.units)),
                 "wu": _glorot(ku, (d, self.units)),
                 "wd": _glorot(kd, (self.units, d))}, {}, in_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        return swiglu(x, params["wg"], params["wu"], params["wd"]), state


def swiglu(x, wg, wu, wd):
    h = jax.nn.silu(x @ wg.astype(x.dtype)) * (x @ wu.astype(x.dtype))
    return h @ wd.astype(x.dtype)


# -- latent attention -------------------------------------------------------


@dataclasses.dataclass(frozen=True, repr=False)
class LatentAttention(Layer):
    """Multi-head latent attention (no query compression): keys and
    values are rebuilt from one cached latent row a token."""

    num_heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    epsilon: float = 1e-6

    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.nope_dim + self.rope_dim)

    def init(self, key, in_shape):
        d, h = in_shape[-1], self.num_heads
        qk = self.nope_dim + self.rope_dim
        ks = jax.random.split(key, 5)
        return ({
            "wq": _glorot(ks[0], (d, h * qk)),
            "wkva": _glorot(ks[1], (d, self.latent_width)),
            "c_norm": jnp.ones((self.kv_rank,), jnp.float32),
            "wkvb": _glorot(ks[2], (self.kv_rank,
                                    h * (self.nope_dim + self.v_dim))),
            "q_norm": jnp.ones((qk,), jnp.float32),
            "kr_norm": jnp.ones((self.rope_dim,), jnp.float32),
            "wgate": _glorot(ks[3], (d, h)),
            "wo": _glorot(ks[4], (h * self.v_dim, d)),
        }, {}, in_shape)

    def project(self, p, x, pos):
        """``x`` [.., L, d] at absolute positions ``pos`` ([L], or
        [.., L] where rows differ: one token a row in decode) ->
        ``(q_nope [.., H, L, nope], q_rope [.., H, L, rope], latent
        [.., L, rank + rope])``, float32; the latent row is what a cache
        holds: the normed ``c`` and the normed, rotated ``k_r``."""
        *lead, ln, _ = x.shape
        h, nope = self.num_heads, self.nope_dim
        q = (x @ p["wq"].astype(x.dtype)).reshape(*lead, ln, h, -1)
        q = rms_norm(q.astype(jnp.float32), p["q_norm"], self.epsilon)
        q = jnp.moveaxis(q, -2, -3)                      # [.., H, L, qk]
        q_nope = q[..., :nope]
        q_pos = pos if pos.ndim == 1 else pos[..., None, :]  # over heads
        q_rope = rope(q[..., nope:], q_pos, self.rope_theta)
        ckr = (x @ p["wkva"].astype(x.dtype)).astype(jnp.float32)
        c = rms_norm(ckr[..., :self.kv_rank], p["c_norm"], self.epsilon)
        k_r = rope(rms_norm(ckr[..., self.kv_rank:], p["kr_norm"],
                            self.epsilon), pos, self.rope_theta)
        return q_nope, q_rope, jnp.concatenate([c, k_r], axis=-1)

    def _wkvb(self, p, dtype):
        """``W_kvb`` as ``(W^K [r, H, nope], W^V [r, H, dv])``."""
        w = p["wkvb"].astype(dtype).reshape(
            self.kv_rank, self.num_heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def attend_expanded(self, p, q_nope, q_rope, latent, mask):
        """Queries ``[.., H, L, *]`` over latent rows ``[.., S, width]``
        under ``mask`` [L, S] (True = attend): ``k_nope`` and ``v`` rebuilt
        from ``c`` for every row. Returns ``[.., H, L, dv]`` float32."""
        c = latent[..., :self.kv_rank]
        k_r = latent[..., self.kv_rank:].astype(jnp.float32)
        wk, wv = self._wkvb(p, c.dtype)
        k_nope = jnp.einsum("...sr,rhn->...hsn", c, wk,
                            preferred_element_type=jnp.float32)
        v = jnp.einsum("...sr,rhv->...hsv", c, wv,
                       preferred_element_type=jnp.float32)
        s = (jnp.einsum("...hqn,...hsn->...hqs", q_nope, k_nope)
             + jnp.einsum("...hqn,...sn->...hqs", q_rope, k_r)) * self.scale
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.einsum("...hqs,...hsv->...hqv",
                          jax.nn.softmax(s, axis=-1), v)

    def attend_absorbed(self, p, q_nope, q_rope, latent, valid):
        """One query a row: ``q_*`` [b, H, *] over ``latent`` [b, S,
        width] under ``valid`` [b, S]. ``W^K`` is folded into the query and
        ``W^V`` applied after the sum: ``s = (W^K^T q_nope) . c + q_rope .
        k_r``, ``o = W^V sum p c``. Returns ``[b, H, dv]`` float32."""
        c = latent[..., :self.kv_rank].astype(jnp.float32)
        k_r = latent[..., self.kv_rank:].astype(jnp.float32)
        wk, wv = self._wkvb(p, jnp.float32)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, wk)
        s = (jnp.einsum("bhr,bsr->bhs", q_lat, c)
             + jnp.einsum("bhn,bsn->bhs", q_rope, k_r)) * self.scale
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        o_lat = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1), c)
        return jnp.einsum("bhr,rhv->bhv", o_lat, wv)

    def output(self, p, x, o):
        """Head-wise sigmoid gate from ``x`` [.., L, d] on ``o``
        [.., L, H, dv], then ``W_o``."""
        gate = jax.nn.sigmoid(
            (x @ p["wgate"].astype(x.dtype)).astype(jnp.float32))
        o = (o * gate[..., None]).astype(x.dtype)
        return o.reshape(*o.shape[:-2], -1) @ p["wo"].astype(x.dtype)

    def apply(self, params, state, x, *, training=False, rng=None):
        ln = x.shape[-2]
        q_nope, q_rope, latent = self.project(params, x, jnp.arange(ln))
        mask = jnp.tril(jnp.ones((ln, ln), bool))
        o = self.attend_expanded(params, q_nope, q_rope,
                                 latent.astype(x.dtype), mask)
        return self.output(params, x, jnp.moveaxis(o, -3, -2)), state


# -- grouped-query attention ---------------------------------------------------


@dataclasses.dataclass(frozen=True, repr=False)
class GroupedQueryAttention(Layer):
    """Causal attention of ``num_heads`` query heads over ``num_kv_heads``
    K/V heads (query head ``h`` reads K/V head ``h // (num_heads //
    num_kv_heads)``), no biases: per-head RMSNorm on ``q`` and ``k`` (one
    ``gamma`` each a layer), then RoPE over the whole head in half-split
    pairs where ``rope_theta`` is given, a softmax in float32 over the keys
    ``j <= t`` and, with a ``window``, ``t - j < window`` (a query sees
    itself and the ``window - 1`` before it)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None = None
    rope_theta: float | None = None
    epsilon: float = 1e-6

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads do not share "
                f"{self.num_kv_heads} K/V heads evenly")

    @property
    def kv_width(self) -> int:
        """Values of one cached ``k`` (or ``v``) row: every K/V head."""
        return self.num_kv_heads * self.head_dim

    def init(self, key, in_shape):
        d, dk = in_shape[-1], self.head_dim
        ks = jax.random.split(key, 4)
        return ({
            "wq": _glorot(ks[0], (d, self.num_heads * dk)),
            "wk": _glorot(ks[1], (d, self.kv_width)),
            "wv": _glorot(ks[2], (d, self.kv_width)),
            "q_norm": jnp.ones((dk,), jnp.float32),
            "k_norm": jnp.ones((dk,), jnp.float32),
            "wo": _glorot(ks[3], (self.num_heads * dk, d)),
        }, {}, in_shape)

    def project(self, p, x, pos):
        """``x`` [.., L, d] at absolute positions ``pos`` ([L], or [.., L]
        where rows differ: one token a row in decode) -> ``(q [.., H, L,
        dk]`` float32, ``k``, ``v`` [.., L, G * dk])``: the rows a cache
        holds, ``k`` normed and rotated, in ``x``'s dtype."""
        *lead, ln, _ = x.shape
        dk = self.head_dim
        heads = lambda w, n: (x @ p[w].astype(x.dtype)).reshape(
            *lead, ln, n, dk)
        q = rms_norm(heads("wq", self.num_heads).astype(jnp.float32),
                     p["q_norm"], self.epsilon)
        k = rms_norm(heads("wk", self.num_kv_heads).astype(jnp.float32),
                     p["k_norm"], self.epsilon)
        q, k = jnp.moveaxis(q, -2, -3), jnp.moveaxis(k, -2, -3)  # [.,n,L,dk]
        if self.rope_theta is not None:
            at = pos if pos.ndim == 1 else pos[..., None, :]     # over heads
            q = rope(q, at, self.rope_theta, interleaved=False)
            k = rope(k, at, self.rope_theta, interleaved=False)
        k = jnp.moveaxis(k, -3, -2).reshape(*lead, ln, self.kv_width)
        return q, k.astype(x.dtype), x @ p["wv"].astype(x.dtype)

    def sees(self, q_pos, k_pos):
        """Whether a query at ``q_pos`` [.., L] attends a key at ``k_pos``
        [.., S]: -> bool [.., L, S]. A negative ``k_pos`` is no key."""
        q_pos, k_pos = q_pos[..., :, None], k_pos[..., None, :]
        ok = (k_pos <= q_pos) & (k_pos >= 0)
        if self.window is not None:
            ok &= q_pos - k_pos < self.window
        return ok

    def scores(self, q, k):
        """``q`` [.., H, L, dk] against cached rows ``k`` [.., S, G * dk]
        -> scaled scores [.., G, H / G, L, S] float32; the operands enter
        the product in the rows' dtype."""
        *lead, h, ln, dk = q.shape
        g = self.num_kv_heads
        q = q.reshape(*lead, g, h // g, ln, dk).astype(k.dtype)
        k = k.reshape(*k.shape[:-1], g, dk)
        s = jnp.einsum("...grqd,...sgd->...grqs", q, k,
                       preferred_element_type=jnp.float32)
        return s / math.sqrt(dk)

    def weigh(self, prob, v):
        """Probabilities [.., G, H / G, L, S] over cached rows ``v``
        [.., S, G * dk] -> [.., H, L, dk] float32."""
        g, dk = self.num_kv_heads, self.head_dim
        v = v.reshape(*v.shape[:-1], g, dk)
        o = jnp.einsum("...grqs,...sgd->...grqd", prob.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(*o.shape[:-4], self.num_heads, *o.shape[-2:])

    def attend(self, q, k, v, mask):
        """Softmax attention of ``q`` [.., H, L, dk] over the rows ``k``,
        ``v`` [.., S, G * dk] under ``mask`` [.., L, S] (True = attend;
        every query sees a key). Returns ``[.., H, L, dk]`` float32."""
        s = jnp.where(mask[..., None, None, :, :], self.scores(q, k),
                      -jnp.inf)
        return self.weigh(jax.nn.softmax(s, axis=-1), v)

    def attend_blocks(self, q, q_pos, fetch, n_blocks, block: int):
        """The same softmax over keys ``0 .. n_blocks * block - 1``, a
        block at a time: ``fetch(i)`` hands over block ``i``'s rows ``(k,
        v)`` [block, G * dk], whose key ``j`` is position ``i * block +
        j``. Running maximum, sum and accumulator in float32; no score
        wider than a block is ever built. ``q`` [H, L, dk] at ``q_pos``
        [L]; ``n_blocks`` may be traced; block 0 holds a key every query
        sees. Returns ``[H, L, dk]`` float32."""
        h, ln, dk = q.shape
        g = self.num_kv_heads
        shape = (g, h // g, ln)

        def one(i, carry):
            m, l, acc = carry
            k, v = fetch(i)
            k_pos = i * block + jnp.arange(block)
            s = jnp.where(self.sees(q_pos, k_pos), self.scores(q, k),
                          -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = alpha.reshape(h, ln, 1) * acc + self.weigh(p, v)
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, n_blocks, one,
            (jnp.full(shape, -jnp.inf, jnp.float32),
             jnp.zeros(shape, jnp.float32),
             jnp.zeros((h, ln, dk), jnp.float32)))
        return acc / l.reshape(h, ln, 1)

    def output(self, p, x, o):
        """``o`` [.., H, L, dk] -> ``W_o`` of the heads side by side."""
        o = jnp.moveaxis(o, -3, -2).astype(x.dtype)
        return o.reshape(*o.shape[:-2], -1) @ p["wo"].astype(x.dtype)

    def apply(self, params, state, x, *, training=False, rng=None):
        pos = jnp.arange(x.shape[-2])
        q, k, v = self.project(params, x, pos)
        o = self.attend(q, k, v, self.sees(pos, pos))
        return self.output(params, x, o), state


# -- delta-rule linear attention ---------------------------------------------


def delta_rule_step(q, k, v, g, beta, s):
    """One token of the gated delta rule for every row: ``q, k`` [.., dk],
    ``v`` [.., dv], ``g`` [.., dk] (log decay <= 0), ``beta`` [..],
    ``s`` [.., dk, dv]. Returns ``(o [.., dv], s)``, float32."""
    s = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.einsum("...kv,...k->...v", s, k,
                                          precision=_HIGHEST))
    s = s + k[..., None] * u[..., None, :]
    return jnp.einsum("...kv,...k->...v", s, q, precision=_HIGHEST), s


def _unit_lower_inverse(x):
    """``(I - x)^-1`` for strictly lower-triangular ``x`` [.., C, C]
    (nilpotent): ``(I + x)(I + x^2)(I + x^4)...``, log2(C) squarings."""
    c = x.shape[-1]
    eye = jnp.eye(c, dtype=x.dtype)
    inv, power, n = eye + x, x, 1
    while 2 * n < c:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=_HIGHEST)
        n *= 2
    return inv


def delta_rule_chunked(q, k, v, g, beta, s0, *, block: int = SCAN_BLOCK):
    """The same recurrence over a whole sequence, a block at a time.

    ``q, k`` [.., L, dk], ``v`` [.., L, dv], ``g`` [.., L, dk] (log decay),
    ``beta`` [.., L], ``s0`` [.., dk, dv]; ``L`` a multiple of ``block``
    (pad with ``beta`` = 0 and ``g`` = 0: such a token leaves the state as
    it was). Returns ``(o [.., L, dv], s [.., dk, dv])``, float32.

    Inside a block, with ``G_t`` the running sum of ``g`` and ``S`` the
    state at its start, ``u_t = beta_t (v_t - S^T(e^{G_t} k_t) -
    sum_{s<t} A_ts u_s)`` and ``o_t = S^T(e^{G_t} q_t) + sum_{s<=t} P_ts
    u_s`` with ``A_ts = sum_c k_tc k_sc e^{G_tc - G_sc}`` and ``P`` the same
    with ``q_t``: a unit lower-triangular system solved by products
    (``_unit_lower_inverse``). The decays enter pairwise, as ``e^{G_t -
    G_s}`` with ``s <= t``, so nothing is divided by a decay that
    underflowed. The block's state is ``e^{G_C}(S + sum_s e^{-G_s} k_s
    u_s^T)``, likewise formed from ``e^{G_C - G_s}``.
    """
    *lead, ln, dk = q.shape
    n = ln // block
    split = lambda a: jnp.moveaxis(
        a.reshape(*lead, n, block, *a.shape[len(lead) + 1:]), len(lead), 0)
    strict = jnp.tril(jnp.ones((block, block), bool), -1)
    upto = jnp.tril(jnp.ones((block, block), bool))

    def one(s, part):
        qb, kb, vb, gb, bb = part                        # [.., C, *]
        gsum = jnp.cumsum(gb, axis=-2)                   # G_t, [.., C, dk]
        diff = gsum[..., :, None, :] - gsum[..., None, :, :]
        decay = jnp.exp(jnp.where(upto[..., None], diff, -jnp.inf))
        pair = kb[..., None, :, :] * decay               # k_s e^{G_t - G_s}
        a = jnp.sum(kb[..., :, None, :] * pair, axis=-1)  # [.., C, C]
        p = jnp.sum(qb[..., :, None, :] * pair, axis=-1)
        inv = _unit_lower_inverse(
            jnp.where(strict, -bb[..., None] * a, 0.0))
        grow = jnp.exp(gsum)
        rhs = bb[..., None] * (vb - jnp.matmul(kb * grow, s,
                                               precision=_HIGHEST))
        u = jnp.matmul(inv, rhs, precision=_HIGHEST)     # [.., C, dv]
        o = (jnp.matmul(qb * grow, s, precision=_HIGHEST)
             + jnp.matmul(jnp.where(upto, p, 0.0), u, precision=_HIGHEST))
        last = gsum[..., -1:, :]
        tail = kb * jnp.exp(last - gsum)                 # k_s e^{G_C - G_s}
        s = (jnp.exp(last)[..., 0, :, None] * s
             + jnp.einsum("...ck,...cv->...kv", tail, u, precision=_HIGHEST))
        return s, o

    s, o = jax.lax.scan(one, s0, tuple(split(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, len(lead))                    # [.., n, C, dv]
    return o.reshape(*lead, ln, o.shape[-1]), s


def causal_conv(x, tail, taps):
    """Depthwise causal convolution of ``x`` [.., L, C] whose ``K - 1``
    inputs before position 0 are ``tail`` [.., K - 1, C]: ``y_t = sum_j
    taps[j] x_{t - K + 1 + j}``. Returns ``(y, window)`` where ``window``
    [.., K - 1 + L, C] is what a caller cuts the next tail from."""
    k, ln = taps.shape[0], x.shape[-2]
    window = jnp.concatenate([tail, x], axis=-2)
    y = sum(taps[j] * window[..., j:j + ln, :] for j in range(k))
    return y, window


@dataclasses.dataclass(frozen=True, repr=False)
class DeltaAttention(Layer):
    """Kimi-style delta attention: short causal convolution, l2-normed
    ``q``/``k``, a per-channel decay with a lower bound, the delta rule,
    a per-head RMSNorm and a head-wise output gate."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    lower_bound: float = -5.0
    epsilon: float = 1e-6

    @property
    def width(self) -> int:
        return self.num_heads * self.head_dim

    def init(self, key, in_shape):
        d, h, hd = in_shape[-1], self.num_heads, self.width
        ks = jax.random.split(key, 10)
        return ({
            "wq": _glorot(ks[0], (d, hd)), "wk": _glorot(ks[1], (d, hd)),
            "wv": _glorot(ks[2], (d, hd)),
            "conv": jax.random.uniform(ks[3], (self.conv_size, 3 * hd),
                                       jnp.float32, -0.5, 0.5),
            "wbeta": _glorot(ks[4], (d, h)), "wa": _glorot(ks[5], (d, hd)),
            "a_log": jax.random.uniform(ks[6], (h,), jnp.float32, -0.5, 0.5),
            "dt_bias": -3.0 + 0.5 * jax.random.normal(ks[7], (hd,),
                                                      jnp.float32),
            "o_norm": jnp.ones((self.head_dim,), jnp.float32),
            "wgate": _glorot(ks[8], (d, h)), "wo": _glorot(ks[9], (hd, d)),
        }, {}, in_shape)

    def project(self, p, x):
        """``x`` [.., L, d] -> ``(qkv [.., L, 3 * H * dk]`` before the
        convolution, ``beta [.., L, H]``, ``g [.., L, H, dk])``, float32."""
        f32 = lambda w: (x @ p[w].astype(x.dtype)).astype(jnp.float32)
        qkv = jnp.concatenate([f32("wq"), f32("wk"), f32("wv")], axis=-1)
        beta = jax.nn.sigmoid(f32("wbeta"))
        a = (f32("wa") + p["dt_bias"]).reshape(
            *x.shape[:-1], self.num_heads, self.head_dim)
        g = self.lower_bound * jax.nn.sigmoid(
            jnp.exp(p["a_log"])[:, None] * a)
        return qkv, beta, g

    def heads(self, qkv):
        """Convolved ``qkv`` [.., L, 3 * H * dk] -> ``q, k, v``
        [.., L, H, dk]: SiLU, then ``q`` and ``k`` l2-normed, ``q`` scaled
        by ``dk ** -0.5``."""
        y = jax.nn.silu(qkv).reshape(*qkv.shape[:-1], 3, self.num_heads,
                                     self.head_dim)
        q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        return unit(q) / math.sqrt(self.head_dim), unit(k), v

    def output(self, p, x, o):
        """``o`` [.., L, H, dv] -> per-head RMSNorm, head-wise gate,
        ``W_o``."""
        gate = jax.nn.sigmoid(
            (x @ p["wgate"].astype(x.dtype)).astype(jnp.float32))
        o = rms_norm(o, p["o_norm"], self.epsilon) * gate[..., None]
        o = o.astype(x.dtype)
        return o.reshape(*o.shape[:-2], -1) @ p["wo"].astype(x.dtype)

    def scan(self, q, k, v, g, beta, s0):
        """Head-major chunked delta rule over ``[.., L, H, *]`` inputs;
        ``L`` is padded up to a block here (a pad token leaves the state
        alone). Returns ``(o [.., L, H, dv], s [.., H, dk, dv])``."""
        ln = q.shape[-3]
        block = min(SCAN_BLOCK, ln)
        pad = -ln % block
        hm = lambda a: jnp.moveaxis(a, -3, -2)           # [.., H, L, *]
        q, k, v, g = (hm(jnp.pad(a, [(0, 0)] * (a.ndim - 3)
                                 + [(0, pad), (0, 0), (0, 0)]))
                      for a in (q, k, v, g))
        beta = jnp.moveaxis(
            jnp.pad(beta, [(0, 0)] * (beta.ndim - 2) + [(0, pad), (0, 0)]),
            -2, -1)
        o, s = delta_rule_chunked(q, k, v, g, beta, s0, block=block)
        return jnp.moveaxis(o, -3, -2)[..., :ln, :, :], s

    def apply(self, params, state, x, *, training=False, rng=None):
        qkv, beta, g = self.project(params, x)
        tail = jnp.zeros((*x.shape[:-2], self.conv_size - 1, qkv.shape[-1]),
                         jnp.float32)
        qkv, _ = causal_conv(qkv, tail, params["conv"])
        q, k, v = self.heads(qkv)
        s0 = jnp.zeros((*x.shape[:-2], self.num_heads, self.head_dim,
                        self.head_dim), jnp.float32)
        o, _ = self.scan(q, k, v, g, beta, s0)
        return self.output(params, x, o), state


# -- the model ------------------------------------------------------------


def hybrid_layer_kinds(cfg: dict) -> list:
    """``[(attention, ffn)]`` a layer: latent attention closes every
    ``layer_group_size`` layers, the first ``first_k_dense_replace`` FFNs
    are dense."""
    return [("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if i < cfg["first_k_dense_replace"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def build_hybrid_lm(cfg: dict):
    """A causal LM of the ``bailing_hybrid`` shape from the published keys
    of its ``config.json`` (``cfg``): token embedding, pre-norm blocks of
    delta or latent attention and a dense or expert FFN, final RMSNorm, an
    untied bias-free head. No positional table: the latent layers carry
    RoPE. ``experts_held = [first, count]`` and ``num_experts_published``
    say which of the routed experts this chip holds (all of them when
    absent); ``vocab_size`` is the slice it holds."""
    from tpu_dist.models.model import Sequential
    from tpu_dist.parallel.routed_experts import RoutedExperts

    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    heads = cfg["num_attention_heads"]
    routed = cfg.get("num_experts_published", cfg["num_experts"])
    held = tuple(cfg.get("experts_held", (0, routed)))

    def attention(kind):
        if kind == "mla":
            return LatentAttention(
                num_heads=heads, kv_rank=cfg["kv_lora_rank"],
                nope_dim=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                rope_theta=float(cfg["rope_theta"]), epsilon=eps)
        return DeltaAttention(
            num_heads=heads, head_dim=cfg["head_dim"],
            conv_size=cfg["short_conv_kernel_size"],
            lower_bound=float(cfg["kda_lower_bound"]), epsilon=eps)

    def ffn(kind):
        if kind == "dense":
            return GatedMLP(cfg["intermediate_size"])
        return RoutedExperts(
            num_experts=routed, experts_held=held,
            top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            ff_dim=cfg["moe_intermediate_size"],
            shared_ff_dim=cfg["moe_shared_expert_intermediate_size"],
            routed_scaling=float(cfg["routed_scaling_factor"]))

    layers = [StreamEmbedding(cfg["vocab_size"], d)]
    for attn, mlp in hybrid_layer_kinds(cfg):
        layers.append(Block(layers=(
            Residual(main=(RMSNorm(eps), attention(attn)), shortcut=(),
                     activation=None),
            Residual(main=(RMSNorm(eps), ffn(mlp)), shortcut=(),
                     activation=None))))
    layers += [RMSNorm(eps), Dense(cfg["vocab_size"], use_bias=False)]
    return Sequential(layers, input_shape=(cfg.get("served_positions", 64),),
                      name="hybrid_lm")


def exaone_layer_kinds(cfg: dict) -> list:
    """``[(attention, ffn)]`` a layer from ``layer_types`` and
    ``mlp_layer_types`` (the published lists, of which the first
    ``num_hidden_layers`` are built): ``"window"`` | ``"full"``,
    ``"dense"`` | ``"moe"``."""
    n = cfg["num_hidden_layers"]
    attn = {"sliding_attention": "window", "full_attention": "full"}
    ffn = {"dense": "dense", "sparse": "moe"}
    return [(attn[a], ffn[f]) for a, f in zip(cfg["layer_types"][:n],
                                              cfg["mlp_layer_types"][:n])]


def build_exaone_moe_lm(cfg: dict):
    """A causal LM of the ``exaone_moe`` shape from the published keys of
    its ``config.json`` (``cfg``): token embedding, blocks of grouped-query
    attention (window layers rotate ``q`` and ``k`` and see
    ``sliding_window`` keys; full layers carry no rotary positions) and a
    dense or expert FFN, final RMSNorm, an untied bias-free head. **The
    norms sit on the sublayers' OUTPUT** (``x <- x + RMSNorm(Attn(x))``,
    ``x <- x + RMSNorm(FFN(x))``, the family's own placement): no norm on
    a sublayer's input, so the float32 stream reaches it through
    :class:`ComputeCast`. ``experts_held``, ``num_experts_published`` and
    ``vocab_size`` as :func:`build_hybrid_lm` reads them."""
    from tpu_dist.models.model import Sequential
    from tpu_dist.parallel.routed_experts import RoutedExperts

    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    routed = cfg.get("num_experts_published", cfg["num_experts"])
    held = tuple(cfg.get("experts_held", (0, routed)))
    kinds = exaone_layer_kinds(cfg)
    if [f for _, f in kinds] != [
            "dense" if i < cfg["first_k_dense_replace"] else "moe"
            for i in range(len(kinds))]:
        raise ValueError("first_k_dense_replace and mlp_layer_types disagree")

    def attention(kind):
        window = kind == "window"
        return GroupedQueryAttention(
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            window=int(cfg["sliding_window"]) if window else None,
            rope_theta=(float(cfg["rope_parameters"]["rope_theta"])
                        if window else None),
            epsilon=eps)

    def ffn(kind):
        if kind == "dense":
            return GatedMLP(cfg["intermediate_size"])
        return RoutedExperts(
            num_experts=routed, experts_held=held,
            top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            ff_dim=cfg["moe_intermediate_size"],
            shared_ff_dim=(cfg["num_shared_experts"]
                           * cfg["moe_intermediate_size"]),
            routed_scaling=float(cfg["routed_scaling_factor"]))

    layers = [StreamEmbedding(cfg["vocab_size"], d)]
    for attn, mlp in kinds:
        layers.append(Block(layers=(
            Residual(main=(ComputeCast(), attention(attn), RMSNorm(eps)),
                     shortcut=(), activation=None),
            Residual(main=(ComputeCast(), ffn(mlp), RMSNorm(eps)),
                     shortcut=(), activation=None))))
    layers += [RMSNorm(eps), Dense(cfg["vocab_size"], use_bias=False)]
    return Sequential(layers, input_shape=(cfg.get("served_positions", 64),),
                      name="exaone_moe_lm")
