"""Preallocated KV cache + incremental decode for the transformer LM family.

Training runs the causal LM as one full-sequence forward pass; serving
cannot afford O(L^2) work per generated token. This module gives the
``models/transformer.py`` family an inference path that is numerically
identical to the training forward pass (tests pin allclose in fp32) while
doing O(L) work per new token:

* **prefill** — one full causal forward over the (padded) prompt, routed
  through the SAME attention dispatch training uses
  (``transformer._default_attention``: the fused flash kernel from
  ``ops/flash_attention.py`` on TPU for supported shapes, dense softmax
  elsewhere), capturing every layer's K/V projections into a
  preallocated per-layer cache as it goes. Emits the logits of the last
  *valid* prompt position — the first generated token, i.e. the
  time-to-first-token datum.
* **decode_step** — one token per active slot: Q/K/V are computed for the
  single new position, K/V appended to the cache at each slot's current
  length, and attention runs against the cached keys/values under a
  per-slot validity mask. Padding slots/positions beyond a slot's length
  are masked out, so cache rows left over from an evicted request are
  never read.

The cache is a plain pytree — ``{"k": [layers, slots, heads, max_len,
key_dim], "v": ...}`` — so engines can donate it into jitted programs
(in-place append, no per-step copy) and shardcheck can price its HBM
footprint like any other entry point.

Rather than re-deriving the transformer math, the interpreter is built
from a :func:`build_plan` walk over the ``Sequential``'s layer tree: the
frozen layer dataclasses ARE the architecture description, so the plan
reuses each layer's own ``apply`` (LayerNorm/Dense/Embedding are
position-wise) and ``MultiHeadAttention._heads`` projection — the decode
path shares weights *and code* with training, which is what makes the
equivalence test meaningful. Models outside the servable family
(pipelined stages, the GShard ``MixtureOfExperts``, custom
``attention_fn`` hooks, non-causal attention) are rejected at plan-build
time with a pointed error.

The plan gives every attention layer a **cache kind**: K/V pages
(``MultiHeadAttention``; a ``GroupedQueryAttention`` layer without a
window, whose pages hold its K/V heads, fewer than its query heads), latent
pages (``LatentAttention``: one row a token for all heads, the same
tables), a per-slot recurrent state (``DeltaAttention``) or **window K/V**
(a ``GroupedQueryAttention`` layer with a window: a ring of its last
``window`` keys a slot, whatever the request's length). The last three
exist on the paged path only (see "latent pages and per-slot state" and
"grouped-query layers" below); ``RoutedExperts`` is a token-wise op that
also returns its routing counts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from tpu_dist.models.hybrid import (ComputeCast, DeltaAttention, GatedMLP,
                                    GroupedQueryAttention, LatentAttention,
                                    RMSNorm, causal_conv, delta_rule_step)
from tpu_dist.models.layers import (Block, Dense, Layer, Residual,
                                    _activation)
from tpu_dist.models.model import Sequential
from tpu_dist.models.transformer import (Embedding, LayerNormalization,
                                         MultiHeadAttention,
                                         PositionalEmbedding,
                                         _default_attention)
from tpu_dist.ops import paged_attention
from tpu_dist.parallel.routed_experts import RoutedExperts
from tpu_dist.utils import profiler

# -- plan: a flat, servable description of the Sequential ---------------------

#: Plan op tags. Ops are plain tuples so the plan stays hashable/static
#: under jit closures: ("embed"|"pos"|"point"|"moe", layer, path),
#: ("attn"|"gqa"|"latent"|"state"|"window", layer, path, index among its
#: cache kind), ("res_start",), ("res_end", activation_name). The attention
#: tags are the CACHE KINDS: K/V pages ("attn", and "gqa" where the pages
#: hold fewer K/V heads than the layer has query heads), latent pages (one
#: row a token shared by all heads), a per-slot recurrent state, and a
#: per-slot ring of the last ``window`` keys.
_POINTWISE = (LayerNormalization, Dense, RMSNorm, GatedMLP, ComputeCast)


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """Static decode description of one servable Sequential."""

    ops: tuple
    num_layers: int  #: K/V attention layers == KV-cache depth
    num_heads: int
    key_dim: int
    max_position: int  #: PositionalEmbedding.max_len — hard cap on length
    vocab_size: int
    latent_layers: int = 0  #: layers caching one latent row a token
    latent_width: int = 0
    state_layers: int = 0  #: layers holding a per-slot recurrent state
    state_heads: int = 0
    state_dim: int = 0  #: the state is [heads, state_dim, state_dim]
    conv_taps: int = 0  #: the state layers' short convolution
    moe_layers: int = 0
    kv_heads: int = 0  #: K/V heads a page row holds (0: ``num_heads``)
    window_layers: int = 0  #: layers holding a ring of keys a slot
    window: int = 0  #: keys a window layer's query sees, itself included

    @property
    def conv_width(self) -> int:
        return 3 * self.state_heads * self.state_dim

    @property
    def kv_width(self) -> int:
        """Values of one cached K (or V) row: every K/V head."""
        return (self.kv_heads or self.num_heads) * self.key_dim

    @property
    def paged_only(self) -> bool:
        """Some layer's cache is a kind that only the paged pool holds."""
        return bool(self.latent_layers or self.state_layers
                    or self.window_layers or self.kv_heads)

    @property
    def recurrent(self) -> bool:
        """A slot holds what its pages do not (a recurrent state, a ring
        of window keys): prefix reuse would attach pages whose matching
        state the slot does not have."""
        return self.state_layers > 0 or self.window_layers > 0


def _unsupported(layer: Layer, why: str) -> TypeError:
    return TypeError(
        f"serve: {type(layer).__name__} is not servable ({why}); the KV-"
        "cache decode path covers token/positional embeddings, pre-norm "
        "residual blocks with the norm before or behind the sublayer, "
        "LayerNorm/RMSNorm, Dense and gated MLPs, default causal attention "
        "over K/V pages, and on the paged path grouped-query attention "
        "(q/k norm, RoPE; full layers over K/V pages, window layers over a "
        "per-slot ring), latent attention over latent pages, delta-rule "
        "attention over a per-slot state, and routed experts held by share")


def build_plan(model: Sequential) -> DecodePlan:
    """Flatten a Sequential into decode ops, validating servability."""
    if not isinstance(model, Sequential):
        raise TypeError(
            f"serve supports Sequential models, got {type(model).__name__}")
    ops: list = []
    attn_layers: list[MultiHeadAttention] = []
    latent_layers: list[LatentAttention] = []
    state_layers: list[DeltaAttention] = []
    gqa_layers: list[GroupedQueryAttention] = []
    window_layers: list[GroupedQueryAttention] = []
    pos_layers: list[PositionalEmbedding] = []
    moe_layers: list[RoutedExperts] = []

    def walk(layers, names, path):
        for layer, name in zip(layers, names):
            p = path + (name,)
            if isinstance(layer, Embedding):
                ops.append(("embed", layer, p))
            elif isinstance(layer, PositionalEmbedding):
                pos_layers.append(layer)
                ops.append(("pos", layer, p))
            elif isinstance(layer, MultiHeadAttention):
                if not layer.causal:
                    raise _unsupported(
                        layer, "non-causal attention cannot decode "
                        "incrementally — future tokens would change past "
                        "activations")
                if layer.attention_fn is not None:
                    raise _unsupported(
                        layer, "custom attention_fn hooks (ring attention "
                        "etc.) have no cache-aware decode path")
                ops.append(("attn", layer, p, len(attn_layers)))
                attn_layers.append(layer)
            elif isinstance(layer, LatentAttention):
                ops.append(("latent", layer, p, len(latent_layers)))
                latent_layers.append(layer)
            elif isinstance(layer, DeltaAttention):
                ops.append(("state", layer, p, len(state_layers)))
                state_layers.append(layer)
            elif isinstance(layer, GroupedQueryAttention):
                kind = window_layers if layer.window else gqa_layers
                ops.append(("window" if layer.window else "gqa", layer, p,
                            len(kind)))
                kind.append(layer)
            elif isinstance(layer, RoutedExperts):
                ops.append(("moe", layer, p))
                moe_layers.append(layer)
            elif isinstance(layer, Residual):
                if layer.shortcut:
                    raise _unsupported(
                        layer, "projection shortcuts are a ResNet shape, "
                        "not a transformer residual")
                ops.append(("res_start",))
                walk(layer.main, layer._main_names, p + ("main",))
                ops.append(("res_end", layer.activation))
            elif isinstance(layer, Block):
                walk(layer.layers, layer._names, p)
            elif isinstance(layer, _POINTWISE):
                ops.append(("point", layer, p))
            else:
                raise _unsupported(layer, "no decode rule for this layer")

    walk(model.layers, model.layer_names, ())
    if not (attn_layers or latent_layers or state_layers or gqa_layers
            or window_layers):
        raise TypeError("serve: model has no attention layers to cache")
    if attn_layers and (gqa_layers or window_layers):
        raise TypeError(
            "serve: MultiHeadAttention beside GroupedQueryAttention layers "
            "in one model has no pool layout yet")
    # (query heads, K/V heads, key_dim): one K/V pool and one ring serve
    # every layer, so full and window layers alike agree on all three.
    heads = ({(l.num_heads, l.num_heads, l.key_dim) for l in attn_layers}
             | {(l.num_heads, l.num_kv_heads, l.head_dim)
                for l in gqa_layers + window_layers}) or {(0, 0, 0)}
    windows = {l.window for l in window_layers} or {0}
    if len(heads) > 1 or len(windows) > 1:
        raise TypeError(
            f"serve: attention layers disagree on (query heads, K/V heads, "
            f"key_dim) ({sorted(heads)}) or on their window "
            f"({sorted(windows)}); a stacked KV cache needs uniform shapes "
            "(head counts that differ by layer kind have no layout yet)")
    widths = {l.latent_width for l in latent_layers} or {0}
    states = ({(l.num_heads, l.head_dim, l.conv_size) for l in state_layers}
              or {(0, 0, 0)})
    if len(widths) > 1 or len(states) > 1:
        raise TypeError(
            "serve: latent or state layers disagree on their shapes "
            f"({sorted(widths)}, {sorted(states)}); a cache kind is one "
            "stacked array")
    last = model.layers[-1]
    if not isinstance(last, Dense):
        raise TypeError(
            "serve: expected a Dense vocabulary head as the final layer, "
            f"got {type(last).__name__}")
    (num_heads, kv_heads, key_dim), = heads
    (state_heads, state_dim, conv_taps), = states
    max_position = min((l.max_len for l in pos_layers),
                      default=2 ** 30)
    return DecodePlan(ops=tuple(ops),
                      num_layers=len(attn_layers) + len(gqa_layers),
                      num_heads=num_heads, key_dim=key_dim,
                      max_position=max_position, vocab_size=last.units,
                      latent_layers=len(latent_layers),
                      latent_width=widths.pop(),
                      state_layers=len(state_layers),
                      state_heads=state_heads, state_dim=state_dim,
                      conv_taps=conv_taps, moe_layers=len(moe_layers),
                      kv_heads=kv_heads if gqa_layers or window_layers else 0,
                      window_layers=len(window_layers),
                      window=windows.pop())


def init_cache(plan: DecodePlan, *, max_batch: int, max_len: int,
               dtype=jnp.float32, budget_bytes: Optional[int] = None) -> dict:
    """Zeros cache pytree: ``k``/``v`` of
    ``[num_layers, max_batch, num_heads, max_len, key_dim]``.

    ``budget_bytes`` turns the advisory :func:`cache_nbytes` math into a
    hard guard: when the cache would not fit, raise a loud error naming
    how many slots DO fit instead of letting XLA OOM at first prefill.
    """
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError(
            "serve: int8 KV is a paged-pool feature (the quantized pages "
            "carry per-page scale rows the contiguous cache has no layout "
            "for) — use ServeEngine(paged=True, kv_dtype='int8')")
    if max_len > plan.max_position:
        raise ValueError(
            f"max_len {max_len} exceeds the model's positional table "
            f"({plan.max_position})")
    if budget_bytes is not None:
        need = cache_nbytes(plan, max_batch=max_batch, max_len=max_len,
                            dtype=dtype)
        if need > budget_bytes:
            per_slot = need // max_batch
            fits = int(budget_bytes // per_slot)
            raise ValueError(
                f"serve: contiguous KV cache needs {need} B for "
                f"{max_batch} slots x {max_len} positions but "
                f"budget_bytes={budget_bytes} — at this max_len the "
                f"budget fits {fits} slot(s). Lower max_batch/max_len, "
                "raise the budget, or switch to the paged cache "
                "(ServeEngine(paged=True)), which allocates per page "
                "instead of max_len per slot.")
    shape = (plan.num_layers, max_batch, plan.num_heads, max_len,
             plan.key_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_nbytes(plan: DecodePlan, *, max_batch: int, max_len: int,
                 dtype=jnp.float32) -> int:
    """HBM the cache will pin, for capacity planning / logs."""
    n = (2 * plan.num_layers * max_batch * plan.num_heads * max_len
         * plan.key_dim)
    return n * jnp.dtype(dtype).itemsize


# -- shared layer helpers -----------------------------------------------------


def _params_at(params, path):
    node = params
    for key in path:
        node = node.get(key, {}) if isinstance(node, dict) else {}
    return node


def _qkv(layer: MultiHeadAttention, p, x):
    """The training projection, verbatim: [.., L, D] -> three
    [.., H, L, key_dim] head tensors."""
    b = (lambda n: p[n]) if layer.use_bias else (lambda n: None)
    return (layer._heads(x, p["wq"], b("bq")),
            layer._heads(x, p["wk"], b("bk")),
            layer._heads(x, p["wv"], b("bv")))


def _attn_out(layer: MultiHeadAttention, p, out):
    """[.., H, L, dk] attention output -> [.., L, D] through wo/bo."""
    out = jnp.moveaxis(out, -3, -2)
    *lead, ln, h, dk = out.shape
    out = out.reshape(*lead, ln, h * dk)
    y = out @ p["wo"].astype(out.dtype)
    if layer.use_bias:
        y = y + p["bo"].astype(y.dtype)
    return y


def _learned_positions(params, path, x, pos):
    """The learned-table rule of the ``"pos"`` op, once: ``x`` gains the
    table's rows at ``pos``, clamped to the table. ``pos`` holds absolute
    positions: ``[L]`` for one sequence ``x`` [1, L, D], ``[b]`` for one
    token a row ``x`` [b, 1, D]. Every body computes ``pos`` once and hands
    the same vector here and to the layers that rotate by it (RoPE in
    ``LatentAttention.project``): positions have one source."""
    table = _params_at(params, path)["table"]
    at = jnp.minimum(pos, table.shape[0] - 1)
    rows = table[at].astype(x.dtype)
    return x + (rows[None] if x.shape[0] == 1 and pos.shape[0] == x.shape[1]
                else rows[:, None, :])


_PAGED_TAGS = ("latent", "state", "moe", "gqa", "window")


def _scope(plan: DecodePlan, op):
    """The device scope of one plan op, entered once a walk and op: its
    tag's (``profiler.LAYER_SCOPES``); the residual's add a point op's;
    the vocabulary head (the plan's last op) its own."""
    if op is plan.ops[-1]:
        return profiler.layer_scope("head")
    return profiler.layer_scope(
        {"res_start": None, "res_end": "point"}.get(op[0], op[0]))


def _paged_only(op):
    return TypeError(
        f"serve: {type(op[1]).__name__} keeps its cache in latent pages, "
        "grouped K/V pages or per-slot state, which the paged engine "
        "manages — pass paged=True")


# -- prefill ------------------------------------------------------------------


def prefill(plan: DecodePlan, params, cache: dict, tokens, length, slot,
            *, attention_fn: Optional[Callable] = None):
    """Full causal forward over one padded prompt, filling cache slot
    ``slot``.

    Args:
      tokens: int32 ``[pad_len]`` prompt, padded past ``length`` with any
        token id (padded positions' K/V land in the cache but decode's
        validity mask never reads them before they are overwritten).
      length: scalar int32, number of valid prompt tokens (>= 1).
      slot: scalar int32 cache row to fill.
      attention_fn: override for the prefill attention inner loop
        (signature ``fn(q, k, v, causal=..., scale=...)``); defaults to
        the training dispatch — the fused flash kernel on TPU for
        supported shapes, dense softmax otherwise.

    Returns:
      ``(cache, last_logits)`` — logits ``[vocab]`` of position
      ``length - 1``, i.e. the distribution over the first generated
      token.
    """
    attend = attention_fn or _default_attention
    x = tokens[None]  # [1, pad_len]
    pad_len = tokens.shape[0]
    residuals: list = []
    for op in plan.ops:
        tag = op[0]
        with _scope(plan, op):
            if tag == "res_start":
                residuals.append(x)
            elif tag == "res_end":
                x = _activation(op[1])(residuals.pop() + x)
            elif tag == "attn":
                _, layer, path, idx = op
                p = _params_at(params, path)
                q, k, v = _qkv(layer, p, x)  # [1, H, pad_len, dk]
                scale = 1.0 / math.sqrt(layer.key_dim)
                out = attend(q, k, v, causal=True, scale=scale)
                dt = cache["k"].dtype
                for name, new in (("k", k), ("v", v)):
                    cache[name] = jax.lax.dynamic_update_slice(
                        cache[name], new.astype(dt)[None],
                        (idx, slot, 0, 0, 0))
                x = _attn_out(layer, p, out)
            elif tag == "pos":
                x = _learned_positions(params, op[2], x, jnp.arange(pad_len))
            elif tag in _PAGED_TAGS:
                raise _paged_only(op)
            else:  # "embed" / "point": the layer's own stateless apply
                _, layer, path = op
                x, _ = layer.apply(_params_at(params, path), {}, x)
    # x: [1, pad_len, vocab]; take the last VALID position's logits.
    with profiler.layer_scope("head"):
        last = jax.lax.dynamic_slice(
            x, (0, jnp.maximum(length - 1, 0), 0), (1, 1, plan.vocab_size))
    return cache, last[0, 0]


def prefill_chunk_step(plan: DecodePlan, params, cache: dict, tokens,
                       length, slot, start):
    """Causal forward over ONE chunk of a prompt — the contiguous-cache
    half of chunked prefill.

    The first ``start`` positions' K/V are already in cache slot
    ``slot`` (written by earlier chunks); this pass computes positions
    ``start .. length - 1``, writes their K/V at a traced window offset
    via ``dynamic_update_slice``, and attends each chunk query over the
    whole cached row under the absolute-position causal mask — exactly
    what a full prefill would compute for those positions, so chunked
    and whole-prompt prefill stay token-identical (the paged path gets
    the same semantics for free from :func:`paged_prefill`'s traced
    ``start``).

    Args:
      tokens: int32 ``[chunk_pad]`` — chunk tokens for absolute
        positions ``start .. length - 1``, padded past
        ``length - start``. Padded positions write garbage K/V at
        ``[length, start + chunk_pad)``; positions there are beyond
        every mask until a later chunk or decode append overwrites them
        (the same argument that covers whole-prompt prefill padding).
        The caller must guarantee ``start + chunk_pad <= max_len`` —
        ``dynamic_update_slice`` would otherwise clamp the window start
        and silently corrupt earlier positions.
      length: scalar int32 total valid positions through the end of
        this chunk (prefix + chunk).
      slot: scalar int32 cache row.
      start: scalar int32 already-cached positions (``< length``).

    Returns:
      ``(cache, last_logits)`` — logits ``[vocab]`` of position
      ``length - 1`` (the first-generated-token distribution when this
      is the final chunk; intermediate chunks' logits are discarded).
    """
    pad = tokens.shape[0]
    x = tokens[None]                       # [1, pad]
    valid = length - start
    pos = start + jnp.arange(pad)          # absolute positions [pad]
    max_len = cache["k"].shape[3]
    key_pos = jnp.arange(max_len)
    residuals: list = []
    for op in plan.ops:
        tag = op[0]
        with _scope(plan, op):
            if tag == "res_start":
                residuals.append(x)
            elif tag == "res_end":
                x = _activation(op[1])(residuals.pop() + x)
            elif tag == "pos":
                x = _learned_positions(params, op[2], x, pos)
            elif tag in _PAGED_TAGS:
                raise _paged_only(op)
            elif tag == "attn":
                _, layer, path, idx = op
                p = _params_at(params, path)
                q, k, v = _qkv(layer, p, x)    # [1, H, pad, dk]
                dt = cache["k"].dtype
                # Window write at the traced chunk offset, then attend over
                # the whole row (earlier chunks' K/V plus this one's).
                for name, new in (("k", k), ("v", v)):
                    cache[name] = jax.lax.dynamic_update_slice(
                        cache[name], new.astype(dt)[None],
                        (idx, slot, 0, start, 0))
                keys = jnp.take(cache["k"][idx], slot, axis=0)  # [H, S, dk]
                vals = jnp.take(cache["v"][idx], slot, axis=0)
                scale = 1.0 / math.sqrt(layer.key_dim)
                s = jnp.einsum("hqd,hkd->hqk", q[0].astype(jnp.float32),
                               keys.astype(jnp.float32)) * scale
                # Key j is position j: <= the query's own absolute position
                # covers causality and prefix validity in one mask.
                mask = key_pos[None, :] <= pos[:, None]         # [pad, S]
                s = jnp.where(mask[None], s, -jnp.inf)
                prob = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum("hqk,hkd->hqd", prob,
                                 vals.astype(jnp.float32))
                x = _attn_out(layer, p, out.astype(q.dtype)[None])
            else:  # "embed" / "point"
                _, layer, path = op
                x, _ = layer.apply(_params_at(params, path), {}, x)
    # x: [1, pad, vocab]; last valid chunk position is valid - 1.
    with profiler.layer_scope("head"):
        last = jax.lax.dynamic_slice(
            x, (0, jnp.maximum(valid - 1, 0), 0), (1, 1, plan.vocab_size))
    return cache, last[0, 0]


# -- incremental decode -------------------------------------------------------


def decode_step(plan: DecodePlan, params, cache: dict, tokens, lengths,
                *, bucket: int):
    """One generated token for the first ``bucket`` cache slots.

    Args:
      tokens: int32 ``[cap]`` — each slot's most recent token (prompt tail
        or last generated); only ``[:bucket]`` is read.
      lengths: int32 ``[cap]`` — tokens already cached per slot; the new
        token is written at this position. Only ``[:bucket]`` is read.
      bucket: static slot count this compiled program covers — the
        engine compiles one program per padded batch bucket so
        steady-state serving never retraces.

    Returns:
      ``(cache, logits)`` with logits ``[bucket, vocab]`` fp32.
    """
    x = tokens[:bucket][:, None]          # [b, 1]
    pos = lengths[:bucket]                # [b]
    rows = jnp.arange(bucket)
    max_len = cache["k"].shape[3]
    residuals: list = []
    for op in plan.ops:
        tag = op[0]
        with _scope(plan, op):
            if tag == "res_start":
                residuals.append(x)
            elif tag == "res_end":
                x = _activation(op[1])(residuals.pop() + x)
            elif tag == "pos":
                x = _learned_positions(params, op[2], x, pos)
            elif tag in _PAGED_TAGS:
                raise _paged_only(op)
            elif tag == "attn":
                _, layer, path, idx = op
                p = _params_at(params, path)
                q, k, v = _qkv(layer, p, x)   # [b, H, 1, dk]
                dt = cache["k"].dtype
                # Append this position's K/V at each slot's length (batched
                # scatter; advanced indices around the head slice put the
                # broadcast [b, H, dk] dims in front, matching the operand).
                cache["k"] = cache["k"].at[idx, rows, :, pos, :].set(
                    k[:, :, 0, :].astype(dt))
                cache["v"] = cache["v"].at[idx, rows, :, pos, :].set(
                    v[:, :, 0, :].astype(dt))
                keys = cache["k"][idx, :bucket]      # [b, H, S, dk]
                vals = cache["v"][idx, :bucket]
                scale = 1.0 / math.sqrt(layer.key_dim)
                s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                               keys.astype(jnp.float32)) * scale
                # Valid keys: cached prefix plus the just-appended position.
                valid = jnp.arange(max_len)[None, :] <= pos[:, None]  # [b, S]
                s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
                prob = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum("bhqk,bhkd->bhqd", prob,
                                 vals.astype(jnp.float32)).astype(q.dtype)
                x = _attn_out(layer, p, out)
            else:  # "embed" / "point"
                _, layer, path = op
                x, _ = layer.apply(_params_at(params, path), {}, x)
    with profiler.layer_scope("head"):
        logits = x[:, 0, :].astype(jnp.float32)  # [b, vocab]
    return cache, logits


def swap_slots(cache: dict, i, j):
    """Exchange cache rows ``i`` and ``j`` (every layer, k and v) — the
    compaction move the scheduler uses to keep active slots a contiguous
    prefix so smaller buckets stay usable. ``i``/``j`` are traced
    scalars: one compiled program serves every swap."""
    out = {}
    for name, a in cache.items():
        ri = jnp.take(a, i, axis=1)
        rj = jnp.take(a, j, axis=1)
        out[name] = a.at[:, i].set(rj).at[:, j].set(ri)
    return out


# -- paged cache --------------------------------------------------------------
#
# The paged variant replaces the contiguous [layers, slots, heads, max_len,
# key_dim] preallocation with a pool of fixed-size pages — [layers,
# num_pages + 1, page_size, heads * key_dim] — addressed through a per-slot
# page table of page indices (host-managed by serve/paging.py). A page is
# position-major: one position's K (or V) of every head is one row, so a
# page is one contiguous slab whose two minor dimensions fill whole TPU
# tiles at serving widths. That is what lets the decode kernel
# (ops/paged_attention.py) copy a page in one DMA, and what keeps XLA
# from choosing one layout for the pool at the program's boundary and
# another inside it (under [.., heads, page_size, key_dim] it re-laid the
# whole pool out on the way in and out of every program). Row
# ``num_pages`` is a reserved scratch page: every index a program might
# compute for an invalid position (prompt padding, inactive decode slots
# whose stale page-table rows could otherwise alias pages reallocated to
# other requests) is routed there, so garbage writes land where nothing
# ever reads. A key at flattened gather position j of a slot's table is
# absolute sequence position j, so the contiguous validity mask
# ``arange <= pos`` carries over unchanged and the paged math stays
# allclose-equal to the contiguous path (tests pin it).
#
# int8 pool (``dtype=jnp.int8``): pages store K/V as int8 with fp32
# per-page scale ROWS — ``k_scale``/``v_scale`` of ``[num_layers,
# num_pages + 1, page_size, num_heads]``, one amax-derived symmetric
# scale per written position per head. Quantization happens at write
# time (prefill scatter, decode tail-append; ``copy_page`` clones the
# scale rows along with the int8 payload through the same generic loop).
# The XLA bodies fuse dequantization into the page gather, so their fp32
# attention math is byte-for-byte the float path on the dequantized
# values; the decode kernel multiplies scores and probabilities by the
# scale rows instead and never forms a dequantized value.
# Scaling per POSITION rather than per whole page is
# what makes quantization write-order independent: a position's stored
# bytes depend only on its own K/V projection — never on what else
# landed in the page before or after — so journal replay (one big
# re-prefill) reproduces the exact pool bytes of the crashed run
# (prefill + many appends), and chunked prefill reproduces whole-prompt
# prefill, bit for bit. A per-page running-amax scale would break both:
# every amax bump re-rounds the page's older positions, making the
# bytes a function of write history.

#: Symmetric int8 range; scale = amax / _QMAX, values in [-127, 127].
_QMAX = 127.0


def _quantized(pool: dict) -> bool:
    """True for an int8 pool (fp32 scale planes present)."""
    return "k_scale" in pool


def _quant_rows(x):
    """Quantize ``[..., dk]`` fp rows to (int8 ``[..., dk]``, fp32 scale
    ``[...]``) — one symmetric amax scale per row. All-zero rows get
    scale 1 so they round-trip to exact zeros."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / _QMAX
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / safe[..., None]), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale


def page_nbytes(plan: DecodePlan, *, page_size: int,
                dtype=jnp.float32) -> int:
    """HBM one page pins across every layer, k and v. An int8 page also
    carries its fp32 scale rows (k and v, per head per position)."""
    n = (2 * plan.num_layers * page_size * plan.kv_width
         + plan.latent_layers * page_size * plan.latent_width)
    dt = jnp.dtype(dtype)
    if dt == jnp.int8:
        scales = 2 * plan.num_layers * plan.num_heads * page_size * 4
        return n * dt.itemsize + scales
    return n * dt.itemsize


def state_nbytes_per_slot(plan: DecodePlan) -> int:
    """HBM one slot's recurrent layers pin beside its pages: the float32
    state and the convolution's tail, a state layer."""
    s = plan.state_heads * plan.state_dim * plan.state_dim
    tail = max(plan.conv_taps - 1, 0) * plan.conv_width
    return plan.state_layers * (s + tail) * 4


def window_nbytes_per_slot(plan: DecodePlan, dtype=jnp.float32) -> int:
    """HBM one slot's window layers pin beside its pages: a ring of
    ``window`` K and V rows a layer, whatever the request's length."""
    return (2 * plan.window_layers * plan.window * plan.kv_width
            * jnp.dtype(dtype).itemsize)


def page_pool_nbytes(plan: DecodePlan, *, num_pages: int, page_size: int,
                     dtype=jnp.float32) -> int:
    """HBM the pool will pin, scratch page included."""
    return page_nbytes(plan, page_size=page_size, dtype=dtype) \
        * (num_pages + 1)


def pages_for_budget(plan: DecodePlan, *, page_size: int, budget_bytes: int,
                     dtype=jnp.float32) -> int:
    """Largest ``num_pages`` whose pool (plus scratch) fits the budget."""
    per = page_nbytes(plan, page_size=page_size, dtype=dtype)
    return max(int(budget_bytes // per) - 1, 0)


def init_page_pool(plan: DecodePlan, *, num_pages: int, page_size: int,
                   dtype=jnp.float32, budget_bytes: Optional[int] = None,
                   slots: int = 0) -> dict:
    """Zeros page pool pytree: ``k``/``v`` of
    ``[num_layers, num_pages + 1, page_size, num_heads * key_dim]`` —
    the extra row is the write-off scratch page. A plan with latent
    layers gets ``latent`` ``[latent_layers, num_pages + 1, page_size,
    latent_width]`` (the same pages, addressed by the same tables); one
    with state layers gets, for ``slots`` slots, ``state``
    ``[state_layers, slots, heads, dim, dim]`` and ``conv``
    ``[state_layers, slots, taps - 1, conv_width]``, float32 and indexed
    by SLOT, not by page. A plan with grouped-query layers gets ``k``/``v``
    of ``[num_layers, num_pages + 1, page_size, kv_heads * key_dim]`` for
    its full layers and, for its window layers, the rings ``wk``/``wv``
    ``[window_layers, slots, window, kv_heads * key_dim]``, indexed by slot:
    position ``p`` of a slot's sequence lies at ring row ``p % window``.

    Like :func:`init_cache`, ``budget_bytes`` raises a loud sizing error
    (how many pages DO fit) instead of deferring to an XLA OOM.
    """
    if num_pages < 1:
        raise ValueError(f"num_pages must be >= 1, got {num_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    if budget_bytes is not None:
        need = page_pool_nbytes(plan, num_pages=num_pages,
                                page_size=page_size, dtype=dtype)
        if need > budget_bytes:
            fits = pages_for_budget(plan, page_size=page_size,
                                    budget_bytes=budget_bytes, dtype=dtype)
            raise ValueError(
                f"serve: page pool needs {need} B for {num_pages} pages of "
                f"{page_size} positions (plus the scratch page) but "
                f"budget_bytes={budget_bytes} — the budget fits {fits} "
                "page(s). Lower num_pages/page_size or raise the budget.")
    if plan.latent_layers or plan.state_layers:
        return _init_hybrid_pool(plan, num_pages, page_size, dtype, slots)
    if plan.kv_heads:
        return _init_grouped_pool(plan, num_pages, page_size, dtype, slots)
    shape = (plan.num_layers, num_pages + 1, page_size,
             plan.num_heads * plan.key_dim)
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        # fp32 scale rows, one per (layer, page, position, head). Zero
        # pages decode to exact zeros under any scale; real scales are
        # written alongside every K/V write.
        sshape = shape[:-1] + (plan.num_heads,)
        pool["k_scale"] = jnp.zeros(sshape, jnp.float32)
        pool["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return pool


def _init_hybrid_pool(plan: DecodePlan, num_pages: int, page_size: int,
                      dtype, slots: int) -> dict:
    if plan.num_layers:
        raise TypeError(
            "serve: K/V attention beside latent or state layers in one "
            "model has no pool layout yet")
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError(
            "serve: int8 pages carry per-head scale rows; a latent page "
            "has no heads — use kv_dtype 'bf16' or 'fp32'")
    if not plan.latent_layers:
        raise TypeError(
            "serve: a model whose every attention layer is recurrent has "
            "nothing to page; the paged engine needs a latent layer")
    pool = {"latent": jnp.zeros(
        (plan.latent_layers, num_pages + 1, page_size, plan.latent_width),
        dtype)}
    if plan.state_layers:
        if slots < 1:
            raise ValueError("serve: a recurrent state is held by slot — "
                             "pass slots")
        dim = plan.state_dim
        pool["state"] = jnp.zeros(
            (plan.state_layers, slots, plan.state_heads, dim, dim),
            jnp.float32)
        pool["conv"] = jnp.zeros(
            (plan.state_layers, slots, plan.conv_taps - 1, plan.conv_width),
            jnp.float32)
    return pool


def _init_grouped_pool(plan: DecodePlan, num_pages: int, page_size: int,
                       dtype, slots: int) -> dict:
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError(
            "serve: int8 pages carry a scale row a head, laid out for equal "
            "query and K/V heads; grouped-query pages and window rings have "
            "none — use kv_dtype 'bf16' or 'fp32'")
    if not plan.num_layers:
        raise TypeError(
            "serve: a model whose every attention layer has a window has "
            "nothing to page; the paged engine needs a full layer")
    shape = (plan.num_layers, num_pages + 1, page_size, plan.kv_width)
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if plan.window_layers:
        if slots < 1:
            raise ValueError("serve: a window's ring is held by slot — "
                             "pass slots")
        ring = (plan.window_layers, slots, plan.window, plan.kv_width)
        pool["wk"], pool["wv"] = jnp.zeros(ring, dtype), jnp.zeros(ring, dtype)
    return pool


#: Pool entries addressed by page (the rest are indexed by slot).
_PAGED_ENTRIES = ("k", "v", "k_scale", "v_scale", "latent")


def _pages(pool: dict):
    """The array whose shape gives the pool's pages and page size."""
    return pool["k"] if "k" in pool else pool["latent"]


def _gather_pages(pool_arr, layer_idx: int, page_rows, num_heads: int):
    """One layer's pages in position order, per head.

    ``pool_arr``: ``[L, P, ps, H * dk]``; ``page_rows``: int32
    ``[..., max_pages]`` page-table row(s). Returns
    ``[..., H, max_pages * ps, dk]`` where flattened index j holds
    absolute position j of that slot's sequence (table entries are
    position-ordered; unallocated entries point at scratch, whose
    garbage the caller's validity mask never admits).
    """
    g = pool_arr[layer_idx][page_rows]     # [..., max_pages, ps, H * dk]
    *lead, mp, ps, width = g.shape
    g = g.reshape(*lead, mp * ps, num_heads, width // num_heads)
    return jnp.moveaxis(g, -2, -3)         # [..., H, S, dk]


def _gather_scales(pool: dict, name: str, layer_idx: int, page_rows):
    """``pool[name + "_scale"]``'s rows in position order:
    ``[..., H, max_pages * ps]`` fp32."""
    s = pool[name + "_scale"][layer_idx][page_rows]  # [..., mp, ps, H]
    *lead, mp, ps, h = s.shape
    return jnp.moveaxis(s.reshape(*lead, mp * ps, h), -1, -2)


def _gather_kv(pool: dict, name: str, layer_idx: int, page_rows,
               num_heads: int):
    """Position-ordered gather of ``pool[name]``, dequantized for int8
    pools (int8 payload × per-position fp32 scale row → fp32); float
    pools pass straight through :func:`_gather_pages`."""
    g = _gather_pages(pool[name], layer_idx, page_rows, num_heads)
    if not _quantized(pool):
        return g
    return (g.astype(jnp.float32)
            * _gather_scales(pool, name, layer_idx, page_rows)[..., None])


def _write_rows(pool: dict, layer_idx: int, pages, offsets, k, v):
    """Write one position's K/V per row of ``k``/``v`` (``[n, H, dk]``;
    for a float pool also the heads side by side, ``[n, H * dk]``) at
    ``(pages[i], offsets[i])`` of layer ``layer_idx``, quantizing for an
    int8 pool. Returns the per-row max-abs dequantization error (``[n]``
    fp32) for an int8 pool, else None."""
    n = k.shape[0]
    err = None
    for name, new in (("k", k), ("v", v)):
        if _quantized(pool):
            rows = new.astype(jnp.float32)
            qv, sc = _quant_rows(rows)                       # [n, H, dk], [n, H]
            pool[name + "_scale"] = pool[name + "_scale"].at[
                layer_idx, pages, offsets, :].set(sc)
            e = jnp.max(jnp.abs(rows - qv.astype(jnp.float32)
                                * sc[..., None]), axis=(1, 2))
            err = e if err is None else jnp.maximum(err, e)
        else:
            qv = new.astype(pool[name].dtype)
        pool[name] = pool[name].at[layer_idx, pages, offsets, :].set(
            qv.reshape(n, -1))
    return err


# -- latent pages and per-slot state (the hybrid family) -----------------------
#
# A latent layer caches ONE row a token, shared by all heads, in pages the
# same tables address; a state layer holds, for each slot, a float32 state
# and the last inputs of its short convolution, read and written whole at
# every step of that slot: a decode step walks the slots in blocks from
# slot 0 to the highest one that decodes. Prefill runs the layers'
# sequence forms (keys and values rebuilt from the latent rows; the
# chunked delta rule), decode their one-token forms (W_kvb absorbed into
# the query and the output; one step of the rule). The mathematics is the
# layers' own (models/hybrid.py).


def _latent_prefill(op, params, pool, page_row, x, pos, valid_q):
    _, layer, path, idx = op
    p = _params_at(params, path)
    num_pages, ps = pool["latent"].shape[1] - 1, pool["latent"].shape[2]
    max_pages = page_row.shape[0]
    q_nope, q_rope, latent = layer.project(p, x, pos)
    pg = jnp.where(valid_q,
                   page_row[jnp.minimum(pos // ps, max_pages - 1)],
                   num_pages)
    pool["latent"] = pool["latent"].at[idx, pg, pos % ps, :].set(
        latent[0].astype(pool["latent"].dtype))
    rows = pool["latent"][idx][page_row].reshape(max_pages * ps, -1)
    # Key j is position j: <= the query's own absolute position.
    mask = jnp.arange(max_pages * ps)[None, :] <= pos[:, None]
    o = layer.attend_expanded(p, q_nope[0], q_rope[0], rows, mask)
    return layer.output(p, x, jnp.moveaxis(o, 0, 1)[None])


def _latent_decode(op, params, pool, tables, x, pos, active):
    _, layer, path, idx = op
    p = _params_at(params, path)
    num_pages, ps = pool["latent"].shape[1] - 1, pool["latent"].shape[2]
    b, max_pages = tables.shape
    q_nope, q_rope, latent = layer.project(p, x, pos[:, None])
    pg = tables[jnp.arange(b), jnp.minimum(pos // ps, max_pages - 1)]
    if active is not None:
        pg = jnp.where(active, pg, num_pages)
    pool["latent"] = pool["latent"].at[idx, pg, pos % ps, :].set(
        latent[:, 0].astype(pool["latent"].dtype))
    rows = pool["latent"][idx][tables].reshape(b, max_pages * ps, -1)
    valid = jnp.arange(max_pages * ps)[None, :] <= pos[:, None]
    o = layer.attend_absorbed(p, q_nope[:, :, 0], q_rope[:, :, 0], rows,
                              valid)
    return layer.output(p, x, o[:, None])


def _state_prefill(op, params, pool, slot, x, start, valid_q):
    _, layer, path, idx = op
    p = _params_at(params, path)
    taps = layer.conv_size
    qkv, beta, g = layer.project(p, x)
    fresh = start == 0
    tail = jnp.where(fresh, 0.0, pool["conv"][idx, slot])
    s0 = jnp.where(fresh, 0.0, pool["state"][idx, slot])
    qkv, window = causal_conv(qkv[0], tail, p["conv"])
    q, k, v = layer.heads(qkv)                         # [pad, H, dk]
    # A padded position leaves the state as it was.
    beta = jnp.where(valid_q[:, None], beta[0], 0.0)
    g = jnp.where(valid_q[:, None, None], g[0], 0.0)
    o, s = layer.scan(q, k, v, g, beta, s0)
    pool["state"] = pool["state"].at[idx, slot].set(s)
    pool["conv"] = pool["conv"].at[idx, slot].set(
        jax.lax.dynamic_slice_in_dim(window, jnp.sum(valid_q), taps - 1))
    return layer.output(p, x, o[None])


#: Slots of the state pool one visit of a decode step reads and writes:
#: 16.8 MB of float32 state a layer at the hybrid family's 32 heads of
#: 128 x 128, and a divisor of the batches the cells decode (32, 64).
STATE_SLOT_BLOCK = 8


def state_slot_block(batch: int) -> int:
    """Slots of one block of the decode step's walk over a state layer:
    ``STATE_SLOT_BLOCK``, or the largest divisor it shares with a batch it
    does not divide."""
    return math.gcd(batch, STATE_SLOT_BLOCK)


def state_slots_visited(hi, batch: int):
    """Slots of a state layer a decode step of ``batch`` rows reads and
    writes when its highest decoding slot is ``hi - 1``: whole blocks from
    slot 0 up, and nothing behind them. ``hi`` may be traced."""
    block = state_slot_block(batch)
    return -(-hi // block) * block


def _state_decode(op, params, pool, x, active):
    _, layer, path, idx = op
    p = _params_at(params, path)
    b = x.shape[0]
    block = state_slot_block(b)
    qkv, beta, g = layer.project(p, x)                 # [b, 1, *]
    # The engine keeps live slots at the front (``_retire`` moves the
    # last one into a freed slot): nothing decodes behind ``hi``.
    hi = b if active is None else jnp.max(
        jnp.where(active, jnp.arange(1, b + 1), 0))

    def one(i, carry):
        state, conv, o = carry
        at = i * block
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, at, block)
        # Out of the whole pool, not out of a layer's copy of it.
        old = jax.lax.dynamic_slice(
            state, (idx, at, 0, 0, 0), (1, block) + state.shape[2:])[0]
        tail = jax.lax.dynamic_slice(
            conv, (idx, at, 0, 0), (1, block) + conv.shape[2:])[0]
        y, window = causal_conv(rows(qkv), tail, p["conv"])
        q, k, v = layer.heads(y)                       # [block, 1, H, dk]
        o_new, s = delta_rule_step(q[:, 0], k[:, 0], v[:, 0],
                                   rows(g)[:, 0], rows(beta)[:, 0], old)
        tail_new = window[:, 1:]
        if active is not None:
            # A slot that is not decoding (empty, or mid-prefill with a
            # real state) keeps what it holds.
            live = rows(active)
            s = jnp.where(live[:, None, None, None], s, old)
            tail_new = jnp.where(live[:, None, None], tail_new, tail)
        return (jax.lax.dynamic_update_slice(state, s[None],
                                             (idx, at, 0, 0, 0)),
                jax.lax.dynamic_update_slice(conv, tail_new[None],
                                             (idx, at, 0, 0)),
                jax.lax.dynamic_update_slice_in_dim(o, o_new, at, 0))

    # A row behind the last visited block keeps a zero output: it has
    # no expert and the host reads no token of it.
    pool["state"], pool["conv"], o = jax.lax.fori_loop(
        0, state_slots_visited(hi, b) // block, one,
        (pool["state"], pool["conv"],
         jnp.zeros((b, layer.num_heads, layer.head_dim), jnp.float32)))
    return layer.output(p, x, o[:, None])


# -- grouped-query layers: K/V pages by length, window rings by slot ------------
#
# A grouped-query layer without a window keeps its K/V heads' rows in pages
# under the one table ("gqa"): a decode step walks a slot's pages through
# the kernel (query head h in the columns of K/V head h // group) or
# gathers them in XLA off the TPU; a prefill chunk walks them in KEY BLOCKS
# up to its own last position with a running softmax, so it neither builds
# a score over the table row nor reads the row behind its length. A layer
# with a window ("window") keeps a RING of its last ``window`` keys a slot,
# position p at ring row p % window: what slid out of the window is
# overwritten, so a slot's window layers hold ``window`` rows whatever the
# request's length. A decode step writes one row and attends the ring; a
# chunk attends the ring's rows and its own under the band mask and leaves
# the newest ``window`` positions behind. A ring row's position is known
# from the slot's length alone; a request's first chunk (``start == 0``)
# finds every row empty, whatever the slot's last holder left there. The
# mathematics is the layer's own (``GroupedQueryAttention``).

#: Keys a block of a prefill chunk's walk over a full layer's pages holds
#: at most: a [heads, chunk, block] float32 score, never the table row's.
PREFILL_KEY_BLOCK = 512


def prefill_key_block(max_pages: int, page_size: int) -> int:
    """Keys of one block of the prefill walk: whole pages, at most
    ``PREFILL_KEY_BLOCK`` keys and never more than the table row."""
    return max(1, min(max_pages, PREFILL_KEY_BLOCK // page_size)) * page_size


def prefill_keys_visited(max_pages: int, page_size: int, length) -> int:
    """Key positions a full layer's chunk that ends at ``length`` walks:
    whole blocks up to its last position (the table row addresses
    ``max_pages * page_size``)."""
    block = prefill_key_block(max_pages, page_size)
    return -(-length // block) * block


def _ring_positions(upto, window: int):
    """The position each ring row holds once positions ``0 .. upto`` are
    written: the newest one congruent to the row, negative for a row not
    reached yet. ``upto`` [..] -> [.., window]."""
    upto = jnp.asarray(upto)[..., None]
    return upto - (upto - jnp.arange(window)) % window


def _gqa_prefill(op, params, pool, page_row, x, pos, valid_q, length):
    _, layer, path, idx = op
    p = _params_at(params, path)
    num_pages, ps = pool["k"].shape[1] - 1, pool["k"].shape[2]
    max_pages = page_row.shape[0]
    block = prefill_key_block(max_pages, ps)
    ppb = block // ps
    q, k, v = layer.project(p, x, pos)
    pg = jnp.where(valid_q,
                   page_row[jnp.minimum(pos // ps, max_pages - 1)],
                   num_pages)
    _write_rows(pool, idx, pg, pos % ps, k[0], v[0])
    # Whole blocks of pages; what lies behind the row is the scratch
    # page, behind every query's own position like all that it holds.
    row = jnp.pad(page_row, (0, -max_pages % ppb),
                  constant_values=num_pages)

    def fetch(i):
        pages = jax.lax.dynamic_slice_in_dim(row, i * ppb, ppb)
        return (pool["k"][idx, pages].reshape(block, -1),
                pool["v"][idx, pages].reshape(block, -1))

    o = layer.attend_blocks(q[0], pos, fetch, -(-length // block), block)
    return layer.output(p, x, o[None])


def _gqa_decode(op, params, pool, tables, x, pos, active, n_keys, walk):
    _, layer, path, idx = op
    p = _params_at(params, path)
    num_pages, ps = pool["k"].shape[1] - 1, pool["k"].shape[2]
    b, max_pages = tables.shape
    q, k, v = layer.project(p, x, pos[:, None])      # q [b, H, 1, dk]
    pg = tables[jnp.arange(b), jnp.minimum(pos // ps, max_pages - 1)]
    if active is not None:
        pg = jnp.where(active, pg, num_pages)
    _write_rows(pool, idx, pg, pos % ps, k[:, 0], v[:, 0])
    if walk:
        o = _walked_attention({"k": pool["k"], "v": pool["v"]},
                              jnp.int32(idx), tables,
                              q.astype(pool["k"].dtype), n_keys)
    else:
        rows = lambda name: pool[name][idx][tables].reshape(
            b, max_pages * ps, -1)
        valid = jnp.arange(max_pages * ps)[None, :] < n_keys[:, None]
        o = layer.attend(q, rows("k"), rows("v"), valid[:, None, :])
    return layer.output(p, x, o)


def _window_prefill(op, params, pool, slot, x, pos, start, length):
    _, layer, path, idx = op
    p = _params_at(params, path)
    window, pad = pool["wk"].shape[2], pos.shape[0]
    q, k, v = layer.project(p, x, pos)
    ring = {n: pool[n][idx, slot] for n in ("wk", "wv")}
    new = {"wk": k[0].astype(ring["wk"].dtype),
           "wv": v[0].astype(ring["wv"].dtype)}
    # The ring as earlier chunks left it, then the chunk's own rows: a
    # padded row keeps its position, which no valid query reaches.
    k_pos = jnp.concatenate([_ring_positions(start - 1, window), pos])
    o = layer.attend(q[0], jnp.concatenate([ring["wk"], new["wk"]]),
                     jnp.concatenate([ring["wv"], new["wv"]]),
                     layer.sees(pos, k_pos))
    # What the chunk leaves behind: each ring row's newest position up
    # to the chunk's last valid one, from the chunk where it is there.
    held = _ring_positions(length - 1, window)
    src = jnp.clip(held - start, 0, pad - 1)
    for n in ("wk", "wv"):
        pool[n] = pool[n].at[idx, slot].set(
            jnp.where((held >= start)[:, None], new[n][src], ring[n]))
    return layer.output(p, x, o[None])


def _window_decode(op, params, pool, x, pos, active):
    _, layer, path, idx = op
    p = _params_at(params, path)
    window, b = pool["wk"].shape[2], x.shape[0]
    q, k, v = layer.project(p, x, pos[:, None])
    # A slot that is not decoding (empty, or mid-prefill with a real
    # ring) writes nowhere: a row behind the ring is dropped.
    at = pos % window
    if active is not None:
        at = jnp.where(active, at, window)
    for name, new in (("wk", k), ("wv", v)):
        pool[name] = pool[name].at[idx, jnp.arange(b), at].set(
            new[:, 0].astype(pool[name].dtype), mode="drop")
    held = _ring_positions(pos, window)                  # [b, window]
    o = layer.attend(q, pool["wk"][idx, :b], pool["wv"][idx, :b],
                     layer.sees(pos[:, None], held))
    return layer.output(p, x, o)


def _keys_attended(plan: DecodePlan, pos, active):
    """int32 ``[all layers, the window layers' part]``: key positions
    the grouped-query layers of one decode step attend for its live
    slots."""
    live = jnp.ones_like(pos, bool) if active is None else active
    full = jnp.sum(jnp.where(live, pos + 1, 0))
    ring = jnp.sum(jnp.where(live, jnp.minimum(pos + 1, plan.window), 0))
    ring = plan.window_layers * ring
    return jnp.stack([plan.num_layers * full + ring, ring]).astype(jnp.int32)


def paged_prefill(plan: DecodePlan, params, pool: dict, page_row, tokens,
                  length, start, slot=None):
    """Causal forward over the UNCACHED suffix of one prompt, writing
    K/V through the page table.

    With a prefix-cache hit the first ``start`` positions' K/V already
    sit in (shared) pages referenced by ``page_row``; only the suffix is
    computed. The suffix queries attend over the gathered cached prefix
    plus their own causally-masked keys, so the result is numerically
    identical to a full prefill — cached K/V are exactly what the full
    forward would recompute. ``start=0`` is the cold path; one compiled
    program per padded suffix length serves both.

    Args:
      pool: page pool from :func:`init_page_pool`.
      page_row: int32 ``[max_pages]`` — this slot's page-table row.
        Entries covering ``[0, length)`` must be real pages (suffix
        pages writable, i.e. unshared); the rest point at scratch.
      tokens: int32 ``[pad]`` — suffix tokens for absolute positions
        ``start .. length - 1``, padded past ``length - start``.
      length: scalar int32 total valid positions (prefix + suffix).
      start: scalar int32 cached-prefix length (``< length``).
      slot: scalar int32, the slot whose recurrent state or window rings
        this chunk carries (plans with state or window layers only).
        ``start == 0`` is a request's first chunk: its state starts from
        zero and its rings empty whatever the slot's last holder left
        there, and nothing of it is read.

    Returns:
      ``(pool, last_logits)`` for float pools; int8 pools return
      ``(pool, last_logits, quant_error)`` where ``quant_error`` is the
      max-abs dequantization error over this call's valid suffix
      positions (fp32 scalar — the ``serve.kv.quant_error`` datum).
    """
    num_pages = _pages(pool).shape[1] - 1  # last row is scratch
    ps = _pages(pool).shape[2]
    max_pages = page_row.shape[0]
    pad = tokens.shape[0]
    x = tokens[None]                       # [1, pad]
    suffix = length - start
    pos = start + jnp.arange(pad)          # absolute positions [pad]
    valid_q = jnp.arange(pad) < suffix     # [pad]
    key_pos = jnp.arange(max_pages * ps)
    qerr = jnp.zeros((), jnp.float32)
    residuals: list = []
    for op in plan.ops:
        tag = op[0]
        with _scope(plan, op):
            if tag == "res_start":
                residuals.append(x)
            elif tag == "res_end":
                x = _activation(op[1])(residuals.pop() + x)
            elif tag == "pos":
                x = _learned_positions(params, op[2], x, pos)
            elif tag == "latent":
                x = _latent_prefill(op, params, pool, page_row, x, pos,
                                    valid_q)
            elif tag == "state":
                x = _state_prefill(op, params, pool, slot, x, start, valid_q)
            elif tag == "gqa":
                x = _gqa_prefill(op, params, pool, page_row, x, pos, valid_q,
                                 length)
            elif tag == "window":
                x = _window_prefill(op, params, pool, slot, x, pos, start,
                                    length)
            elif tag == "moe":
                _, layer, path = op
                x, _ = layer.forward(_params_at(params, path), x, valid_q)
            elif tag == "attn":
                _, layer, path, idx = op
                p = _params_at(params, path)
                q, k, v = _qkv(layer, p, x)    # [1, H, pad, dk]
                # Scatter each suffix position into (its page, its offset);
                # padding positions are routed to the scratch page.
                pg = jnp.where(
                    valid_q,
                    page_row[jnp.minimum(pos // ps, max_pages - 1)],
                    num_pages)                 # [pad]
                off = pos % ps
                err = _write_rows(pool, idx, pg, off,
                                  jnp.moveaxis(k[0], 1, 0),      # [pad, H, dk]
                                  jnp.moveaxis(v[0], 1, 0))
                if err is not None:
                    qerr = jnp.maximum(
                        qerr, jnp.max(jnp.where(valid_q, err, 0.0)))
                keys = _gather_kv(pool, "k", idx, page_row,
                                  plan.num_heads)               # [H, S, dk]
                vals = _gather_kv(pool, "v", idx, page_row, plan.num_heads)
                scale = 1.0 / math.sqrt(layer.key_dim)
                s = jnp.einsum("hqd,hkd->hqk", q[0].astype(jnp.float32),
                               keys.astype(jnp.float32)) * scale
                # Key j is position j: <= the query's own absolute position
                # covers both causality and prefix validity in one mask.
                mask = key_pos[None, :] <= pos[:, None]         # [pad, S]
                s = jnp.where(mask[None], s, -jnp.inf)
                prob = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum("hqk,hkd->hqd", prob,
                                 vals.astype(jnp.float32))
                x = _attn_out(layer, p, out.astype(q.dtype)[None])
            else:  # "embed" / "point"
                _, layer, path = op
                x, _ = layer.apply(_params_at(params, path), {}, x)
    # x: [1, pad, vocab]; last valid suffix position is suffix - 1.
    with profiler.layer_scope("head"):
        last = jax.lax.dynamic_slice(
            x, (0, jnp.maximum(suffix - 1, 0), 0), (1, 1, plan.vocab_size))
    if _quantized(pool):
        return pool, last[0, 0], qerr
    return pool, last[0, 0]


def _gathered_attention(plan: DecodePlan, pool: dict, layer_idx: int,
                        tables, q, n_keys):
    """The plain XLA body of paged decode attention: every slot's whole
    table row gathered (and dequantized) into ``[b, H, S, dk]``, softmax
    over all ``S`` positions under the validity mask. What runs off the
    TPU, and the reference the kernel is held to."""
    keys = _gather_kv(pool, "k", layer_idx, tables, plan.num_heads)
    vals = _gather_kv(pool, "v", layer_idx, tables, plan.num_heads)
    scale = 1.0 / math.sqrt(plan.key_dim)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   keys.astype(jnp.float32)) * scale
    valid = jnp.arange(keys.shape[2])[None, :] < n_keys[:, None]  # [b, S]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", prob,
                      vals.astype(jnp.float32)).astype(q.dtype)


@jax.jit
def _walked_attention(pool: dict, layer_idx, tables, q, n_keys):
    """Paged decode attention through the page-walking kernel
    (ops/paged_attention.py): only the pages a slot holds are read, and
    an int8 pool is never dequantized into HBM. ``layer_idx`` is traced
    and the function jitted, so a program's layers share one trace and
    one lowering of it (the kernel's is the expensive one). Off the TPU
    the kernel runs through the Pallas interpreter."""
    scales = None
    if _quantized(pool):
        scales = (_gather_scales(pool, "k", layer_idx, tables),
                  _gather_scales(pool, "v", layer_idx, tables))
    out = paged_attention.paged_attention(
        q[:, :, 0, :], pool["k"], pool["v"], layer_idx, tables, n_keys,
        scales=scales)
    return out[:, :, None, :]


def walks_pages(pool: dict, max_pages: int, *, devices: int = 1) -> bool:
    """Kernel or XLA body, from what the program's builder can see: the
    platform, the pool's shapes, and the number of devices the program
    spans (a Pallas call is opaque to the partitioner, and the kernel
    has run on one chip only). On a TPU a declined kernel is said once,
    with the shapes and the reason."""
    if jax.default_backend() != "tpu" or "k" not in pool:
        return False  # latent pages are gathered in XLA
    reason = paged_attention.decline_reason(pool["k"], max_pages)
    if reason is None and devices > 1:
        reason = (f"the program spans {devices} devices and the "
                  "partitioner cannot see into the kernel")
    if reason is not None:
        paged_attention.log_declined(tuple(pool["k"].shape), max_pages,
                                     reason)
    return reason is None


def _paged_decode_core(plan: DecodePlan, params, pool: dict, tables,
                       tokens, pos, active, walk: Optional[bool]):
    """Shared body of the bucketed and ragged paged decode programs.

    ``walk`` picks the attention: the page-walking kernel or the
    gathered XLA body; None asks :func:`walks_pages`. A builder that has
    to know what it built (the engine counts pages by it) asks once and
    passes the answer.

    ``active`` (bool ``[b]`` or None) marks the slots that really decode.
    The bucketed path passes None: inactive slots there carry all-scratch
    table rows by host invariant. On the ragged path a mid-chunked-
    prefill slot holds REAL pages a stray decode write must not touch, so
    an inactive slot's tail write goes to the scratch page; the kernel
    visits none of its pages (the XLA body attends whatever its stale
    length admits: nobody reads either).
    """
    num_pages = _pages(pool).shape[1] - 1  # last row is scratch
    ps = _pages(pool).shape[2]
    max_pages = tables.shape[1]
    b = tokens.shape[0]
    rows = jnp.arange(b)
    if walk is None:
        walk = walks_pages(pool, max_pages)
    # The new position is written before the attention: keys 0 .. pos.
    n_keys = pos + 1
    if walk and active is not None:
        n_keys = jnp.where(active, n_keys, 0)
    x = tokens[:, None]                    # [b, 1]
    residuals: list = []
    moe_stats: list = []
    for op in plan.ops:
        tag = op[0]
        with _scope(plan, op):
            if tag == "res_start":
                residuals.append(x)
            elif tag == "res_end":
                x = _activation(op[1])(residuals.pop() + x)
            elif tag == "pos":
                x = _learned_positions(params, op[2], x, pos)
            elif tag == "latent":
                x = _latent_decode(op, params, pool, tables, x, pos, active)
            elif tag == "state":
                x = _state_decode(op, params, pool, x, active)
            elif tag == "gqa":
                x = _gqa_decode(op, params, pool, tables, x, pos, active,
                                n_keys, walk)
            elif tag == "window":
                x = _window_decode(op, params, pool, x, pos, active)
            elif tag == "moe":
                _, layer, path = op
                x, stats = layer.forward(_params_at(params, path), x, active)
                moe_stats.append(stats)
            elif tag == "attn":
                _, layer, path, idx = op
                p = _params_at(params, path)
                q, k, v = _qkv(layer, p, x)    # [b, H, 1, dk]
                # Tail-page append: clamping the page-table column keeps the
                # gather in range.
                pg = tables[rows, jnp.minimum(pos // ps, max_pages - 1)]  # [b]
                if active is not None:
                    pg = jnp.where(active, pg, num_pages)
                _write_rows(pool, idx, pg, pos % ps, k[:, :, 0, :],
                            v[:, :, 0, :])
                if walk:
                    out = _walked_attention(pool, jnp.int32(idx), tables, q,
                                            n_keys)
                else:
                    out = _gathered_attention(plan, pool, idx, tables, q,
                                              n_keys)
                x = _attn_out(layer, p, out)
            else:  # "embed" / "point"
                _, layer, path = op
                x, _ = layer.apply(_params_at(params, path), {}, x)
    with profiler.layer_scope("head"):
        logits = x[:, 0, :].astype(jnp.float32)  # [b, vocab]
    counts = None
    if moe_stats:
        # Summed over the expert layers; the fullest expert is a maximum.
        stacked = jnp.stack(moe_stats)
        sums = jnp.sum(stacked, axis=0)
        counts = jnp.concatenate(
            [sums[:3], jnp.max(stacked[:, 3:4], axis=0), sums[4:]])
    if plan.kv_heads:
        # Behind the experts' six counts (noughts where there are none).
        counts = jnp.concatenate([
            jnp.zeros((6,), jnp.int32) if counts is None else counts,
            _keys_attended(plan, pos, active)])
    if counts is not None:
        return pool, logits, counts
    return pool, logits


def paged_decode_step(plan: DecodePlan, params, pool: dict, page_tables,
                      tokens, lengths, *, bucket: int,
                      walk: Optional[bool] = None):
    """One generated token for the first ``bucket`` slots through the
    page tables.

    The new K/V land at offset ``length % page_size`` of the slot's tail
    page ``page_tables[slot, length // page_size]``; attention then runs
    over the slot's pages under the same ``arange <= pos`` validity
    mask as the contiguous path: on the TPU through the page-walking
    kernel, elsewhere over the gathered pages. Inactive slots inside the
    bucket must have all-scratch table rows so their garbage writes are
    absorbed.

    Args:
      page_tables: int32 ``[cap, max_pages]``; only ``[:bucket]`` read.
      tokens / lengths / bucket: as :func:`decode_step`.
      walk: attend through the page-walking kernel (off the TPU under
        the Pallas interpreter, how the CPU tests hold it to the XLA
        body) or the gathered XLA body; None decides from the platform
        and the pool's shapes (:func:`walks_pages`).

    Returns:
      ``(pool, logits)`` with logits ``[bucket, vocab]`` fp32.
    """
    return _paged_decode_core(plan, params, pool, page_tables[:bucket],
                              tokens[:bucket], lengths[:bucket], None, walk)


def paged_decode_ragged(plan: DecodePlan, params, pool: dict, page_tables,
                        tokens, lengths, active, *,
                        walk: Optional[bool] = None):
    """One generated token for every ACTIVE slot, full capacity in one
    program.

    The ragged replacement for the pow2-bucket program family: the page-
    table gather already erased contiguity, so batch size can be the
    engine's whole slot capacity with per-slot masking — ONE compiled
    decode program, zero steady-state retrace. Inactive rows (empty
    slots, slots mid-chunked-prefill whose table rows hold REAL pages)
    have their tail writes routed to the scratch page and their logits
    are garbage the host never reads; active rows compute exactly what
    :func:`paged_decode_step` computes for them, so ragged and bucketed
    streams are token-identical (tests pin it).

    Args:
      page_tables: int32 ``[cap, max_pages]``.
      tokens / lengths: int32 ``[cap]``, all rows read, inactive ignored.
      active: bool ``[cap]`` — which slots are really decoding.
      walk: as :func:`paged_decode_step`.

    Returns:
      ``(pool, logits)`` with logits ``[cap, vocab]`` fp32.
    """
    return _paged_decode_core(plan, params, pool, page_tables, tokens,
                              lengths, active, walk)


def copy_page(pool: dict, src, dst):
    """Copy page row ``src`` over ``dst`` (every layer, k and v — and,
    for int8 pools, the fp32 scale rows riding in the same pytree) — the
    device half of copy-on-write: the allocator clones a shared
    prefix-cache page into a private one the moment a request needs to
    write into it. ``src``/``dst`` are traced scalars: one compiled
    program serves every copy."""
    out = {}
    for name, a in pool.items():
        out[name] = (a.at[:, dst].set(jnp.take(a, src, axis=1))
                     if name in _PAGED_ENTRIES else a)
    return out


def swap_state(pool: dict, i, j):
    """Exchange slots ``i`` and ``j`` of the entries held by slot (the
    recurrent state and its convolution tail, the window layers' rings):
    the device half of a compaction move under paging, whose pages move by
    a host pointer swap. Traced scalars: one compiled program serves every
    swap."""
    out = dict(pool)
    for name in pool:
        if name in _PAGED_ENTRIES:
            continue
        a = pool[name]
        ri, rj = jnp.take(a, i, axis=1), jnp.take(a, j, axis=1)
        out[name] = a.at[:, i].set(rj).at[:, j].set(ri)
    return out
