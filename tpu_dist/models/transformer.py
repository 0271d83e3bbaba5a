"""Transformer layers: the long-context model family.

Beyond the reference's parity scope (its model zoo is a 2-conv CNN +
benchmark ResNets, SURVEY.md R5/§2.3) — this family exists so the
sequence-parallel axis (tpu_dist.parallel.sequence) has a first-class model
to drive: :class:`MultiHeadAttention` takes a pluggable ``attention_fn``, so
the same block runs dense softmax attention on one device or EXACT ring
attention over a ``seq`` mesh axis for contexts that don't fit one device:

    from functools import partial
    from tpu_dist.parallel import make_mesh, ring_attention

    mesh = make_mesh({"data": 2, "seq": 4})
    attn = partial(ring_attention, mesh=mesh, axis_name="seq",
                   causal=True, batch_axis="data")
    block = TransformerBlock(d_model=512, num_heads=8, ff_dim=2048,
                             attention_fn=attn)

All layers follow the pure-functional Layer protocol (layers.py): immutable
dataclass descriptions, params/state pytrees owned by the caller, everything
jit-traceable. TPU notes: attention and MLP matmuls are MXU-shaped; under
``set_policy("mixed_bfloat16")`` activations run bf16 with fp32 params and
LayerNorm statistics.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpu_dist.models.layers import Block, Dense, Layer, Residual
from tpu_dist.ops import initializers
# Re-exported here so model.json deserialization (models/serialize.py
# resolves layer classes from this module) can round-trip pipelined and
# mixture-of-experts LMs.
from tpu_dist.parallel.expert import MixtureOfExperts  # noqa: F401
from tpu_dist.parallel.pipeline_parallel import PipelinedBlocks  # noqa: F401


@dataclasses.dataclass(frozen=True, repr=False)
class Embedding(Layer):
    """Token embedding: int [L] -> float [L, dim] lookup table."""

    vocab_size: int
    dim: int
    #: GPT-style init scale (normal); Keras' uniform(-0.05, 0.05) converges
    #: slower at transformer depth.
    init_scale: float = 0.02

    def init(self, key, in_shape):
        table = self.init_scale * jax.random.normal(
            key, (self.vocab_size, self.dim), jnp.float32)
        return {"table": table}, {}, (*in_shape, self.dim)

    def apply(self, params, state, x, *, training=False, rng=None):
        from tpu_dist.models.policy import compute_dtype

        return params["table"].astype(compute_dtype())[x], state


@dataclasses.dataclass(frozen=True, repr=False)
class PositionalEmbedding(Layer):
    """Learned absolute positions, added to a [.., L, D] stream."""

    max_len: int
    init_scale: float = 0.02

    def init(self, key, in_shape):
        ln, d = in_shape[-2], in_shape[-1]
        if ln > self.max_len:
            raise ValueError(
                f"sequence length {ln} exceeds max_len {self.max_len}")
        table = self.init_scale * jax.random.normal(
            key, (self.max_len, d), jnp.float32)
        return {"table": table}, {}, in_shape

    def apply(self, params, state, x, *, training=False, rng=None):
        ln = x.shape[-2]
        return x + params["table"][:ln].astype(x.dtype), state


@dataclasses.dataclass(frozen=True, repr=False)
class LayerNormalization(Layer):
    """LayerNorm over the last axis; statistics in float32 always."""

    epsilon: float = 1e-5

    def init(self, key, in_shape):
        d = in_shape[-1]
        return ({"gamma": jnp.ones((d,), jnp.float32),
                 "beta": jnp.zeros((d,), jnp.float32)}, {}, in_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = xf.mean(axis=-1, keepdims=True)
        var = xf.var(axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * params["gamma"] + params["beta"]
        return y.astype(x.dtype), state


def _dense_attention(q, k, v, *, causal: bool, scale: float):
    s = jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        ln = q.shape[-2]
        mask = jnp.tril(jnp.ones((ln, ln), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _mesh_mapped_flash(q, *, causal: bool, scale: float,
                       interpret: bool | None = None):
    """shard_map'd flash attention over the active strategy's mesh, or
    None when inapplicable.

    The fused kernel's custom call is opaque to XLA's SPMD partitioner:
    left unwrapped on a >1-device mesh, GSPMD all-gathers the sharded
    q/k/v around it and every device recomputes the GLOBAL batch's
    attention — silently, in the most common distributed configurations.
    Batch entries and heads are independent attention instances, so
    mapping the kernel per data-shard (batch dim) and per model-shard
    (head dim) is exact — the same composition the ring path uses for its
    seq axis. Declines (returns None) when: no strategy scope / 1-device
    mesh; a mesh axis is already bound (e.g. applied inside
    ``strategy.run`` — binding it twice would raise); no divisible
    data/model axis; or the per-shard shape is outside the kernel's
    envelope."""
    from tpu_dist.ops import flash_attention as fa
    from tpu_dist.parallel import mesh as mesh_lib
    from tpu_dist.parallel.strategy import get_strategy, has_strategy

    if q.ndim != 4 or not has_strategy():
        return None
    strategy = get_strategy()
    mesh = strategy.mesh
    if mesh.devices.size <= 1 or mesh_lib.inside_manual_axes(mesh):
        return None
    b, h, _, _ = q.shape

    def usable(axis, dim):
        size = mesh.shape.get(axis, 1)
        return axis if size > 1 and dim % size == 0 else None

    d_axis = usable(strategy.data_axis, b)
    m_axis = usable(mesh_lib.MODEL_AXIS, h)
    if d_axis is None and m_axis is None:
        return None
    d_size = mesh.shape.get(d_axis, 1)
    m_size = mesh.shape.get(m_axis, 1)
    # The kernel must support the PER-SHARD shape.
    shard = jax.ShapeDtypeStruct((b // d_size, h // m_size, *q.shape[2:]),
                                 q.dtype)
    if not fa.supported(shard):
        return None

    spec = P(d_axis, m_axis, None, None)
    body = functools.partial(fa.flash_attention, causal=causal, scale=scale,
                             interpret=interpret)
    # pallas_call's out_shape carries no varying-mesh-axes type, so the
    # vma checker can't see through the custom call; the body is
    # per-shard pure, which is exactly what disabling the check asserts.
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def _unwrapped_flash_safe() -> bool:
    """Whether the RAW (un-shard_map'd) Pallas kernel can run without GSPMD
    silently all-gathering its operands: true when nothing is sharded (no
    strategy scope / 1-device mesh) or when the caller is already inside the
    mesh's manual axes (``strategy.run`` / shard_map — operands are per-shard
    values there). On a >1-device mesh OUTSIDE manual axes the custom call is
    opaque to the partitioner, so the only safe fallbacks are a mapped kernel
    or dense attention. NOTE the polarity on an unreadable axis env:
    ``manual_axes_state() is True`` — "can't confirm" must gate the raw
    kernel OFF here, the opposite of inside_manual_axes's decline default."""
    from tpu_dist.parallel import mesh as mesh_lib
    from tpu_dist.parallel.strategy import get_strategy, has_strategy

    if not has_strategy():
        return True
    mesh = get_strategy().mesh
    return (mesh.devices.size <= 1
            or mesh_lib.manual_axes_state(mesh) is True)


def _default_attention(q, k, v, *, causal: bool, scale: float):
    """Attention dispatch: the fused flash kernel (ops/flash_attention.py)
    on TPU for supported shapes — O(L) memory, tiled online softmax; on a
    >1-device mesh the kernel maps per data/model shard via shard_map
    (batch entries and heads are independent). When no shard mapping
    applies (indivisible batch/heads, per-shard shape outside the kernel
    envelope) the UNWRAPPED kernel runs only where it cannot be silently
    all-gathered (single device, or already inside manual axes); otherwise
    dense attention runs — GSPMD partitions it natively (ADVICE r3).
    TPU_DIST_FLASH=0 forces dense for A/B measurement."""
    from tpu_dist.ops import flash_attention as fa

    if fa.use_flash(q):
        mapped = _mesh_mapped_flash(q, causal=causal, scale=scale)
        if mapped is not None:
            return mapped(q, k, v)
        if _unwrapped_flash_safe():
            return fa.flash_attention(q, k, v, causal=causal, scale=scale)
        fa.log_declined(
            tuple(q.shape), jnp.dtype(q.dtype).name,
            "no data/model shard mapping applies on this mesh and the "
            "unmapped kernel would be all-gathered")
    return _dense_attention(q, k, v, causal=causal, scale=scale)


@dataclasses.dataclass(frozen=True, repr=False)
class MultiHeadAttention(Layer):
    """Multi-head self-attention on a [.., L, D] stream.

    ``attention_fn(q, k, v, causal=...) -> out`` (shapes [B, H, L, key_dim])
    swaps the attention inner loop: default is dense softmax (``causal``
    applies the autoregressive mask); pass ``functools.partial(ring_attention,
    mesh=..., axis_name='seq')`` for sequence-parallel exact attention — the
    layer forwards its own ``causal`` flag (a partial that already binds
    ``causal=`` must agree or apply() raises), so the flag can never be
    silently dropped. The projections stay identical, so the two paths are
    numerically interchangeable (tests assert it). For full-model save use
    the declarative spec (``tpu_dist.parallel.RingAttention``) — arbitrary
    callables can't serialize; save weights and rebuild in code instead.
    """

    num_heads: int
    key_dim: int
    causal: bool = False
    use_bias: bool = True
    kernel_initializer: str = "glorot_uniform"
    attention_fn: Optional[Callable] = None

    def init(self, key, in_shape):
        d = in_shape[-1]
        h, dk = self.num_heads, self.key_dim
        ks = jax.random.split(key, 4)
        mk = initializers.get(self.kernel_initializer)
        params = {
            "wq": mk(ks[0], (d, h * dk)),
            "wk": mk(ks[1], (d, h * dk)),
            "wv": mk(ks[2], (d, h * dk)),
            "wo": mk(ks[3], (h * dk, d)),
        }
        if self.use_bias:
            z = lambda n: jnp.zeros((n,), jnp.float32)
            params.update(bq=z(h * dk), bk=z(h * dk), bv=z(h * dk), bo=z(d))
        return params, {}, in_shape

    def _heads(self, x, w, b):
        y = x @ w.astype(x.dtype)
        if b is not None:
            y = y + b.astype(y.dtype)
        *lead, ln, _ = y.shape
        y = y.reshape(*lead, ln, self.num_heads, self.key_dim)
        return jnp.moveaxis(y, -2, -3)  # [.., H, L, dk]

    def apply(self, params, state, x, *, training=False, rng=None):
        b = (lambda n: params[n]) if self.use_bias else (lambda n: None)
        q = self._heads(x, params["wq"], b("bq"))
        k = self._heads(x, params["wk"], b("bk"))
        v = self._heads(x, params["wv"], b("bv"))
        if self.attention_fn is not None:
            # Forward the layer's causal flag so attention_fn models can't
            # silently be non-causal (ADVICE r2). A functools.partial chain
            # that already binds causal= must agree with the layer. Walk the
            # whole chain: at call time an OUTER partial's kwargs override an
            # inner one's, so the effective binding is innermost-first with
            # outer layers winning.
            chain, fn = [], self.attention_fn
            while isinstance(fn, functools.partial):
                chain.append(fn.keywords or {})
                fn = fn.func
            bound: dict = {}
            for kw in reversed(chain):
                bound.update(kw)
            if "causal" in bound:
                if bool(bound["causal"]) != bool(self.causal):
                    raise ValueError(
                        f"MultiHeadAttention(causal={self.causal}) conflicts "
                        f"with attention_fn binding causal={bound['causal']}")
                out = self.attention_fn(q, k, v)
            else:
                out = self.attention_fn(q, k, v, causal=self.causal)
        else:
            out = _default_attention(q, k, v, causal=self.causal,
                                     scale=1.0 / math.sqrt(self.key_dim))
        out = jnp.moveaxis(out, -3, -2)  # [.., L, H, dk]
        *lead, ln, h, dk = out.shape
        out = out.reshape(*lead, ln, h * dk)
        y = out @ params["wo"].astype(out.dtype)
        if self.use_bias:
            y = y + params["bo"].astype(y.dtype)
        return y, state


def TransformerBlock(d_model: int, num_heads: int, ff_dim: int,
                     key_dim: Optional[int] = None, causal: bool = False,
                     activation: str = "gelu",
                     attention_fn: Optional[Callable] = None,
                     epsilon: float = 1e-5,
                     moe=None) -> Block:
    """Pre-LN transformer block: x + MHA(LN(x)), then x + MLP(LN(x)) —
    built from the existing Residual container (identity shortcut), so
    params nest exactly like the ResNet blocks. ``d_model`` is the residual
    stream width (the MLP projects ff_dim back to it); ``key_dim`` defaults
    to d_model / num_heads. ``moe`` (a
    :class:`tpu_dist.parallel.MixtureOfExperts`) replaces the dense MLP
    with the expert-parallel FFN — the Switch-transformer block shape."""
    if key_dim is None:
        if d_model % num_heads:
            raise ValueError(
                f"d_model {d_model} not divisible by num_heads {num_heads}; "
                "pass key_dim explicitly")
        key_dim = d_model // num_heads
    attn = Residual(
        main=(LayerNormalization(epsilon=epsilon),
              MultiHeadAttention(num_heads=num_heads, key_dim=key_dim,
                                 causal=causal, attention_fn=attention_fn)),
        shortcut=(), activation=None)
    ffn = ((moe,) if moe is not None
           else (Dense(ff_dim, activation=activation), Dense(d_model)))
    mlp = Residual(
        main=(LayerNormalization(epsilon=epsilon), *ffn),
        shortcut=(), activation=None)
    return Block(layers=(attn, mlp))


def build_transformer_lm(vocab_size: int, seq_len: int, *, d_model: int = 128,
                         depth: int = 2, num_heads: int = 4,
                         ff_dim: Optional[int] = None,
                         attention_fn: Optional[Callable] = None,
                         pipeline_stages: Optional[int] = None,
                         pipeline_microbatches: int = 4,
                         moe_experts: Optional[int] = None,
                         moe_top_k: int = 2,
                         moe_capacity_factor: float = 1.25,
                         moe_groups: Optional[int] = None,
                         moe_every: int = 1):
    """A small causal (GPT-style) language model: token + position
    embeddings, ``depth`` pre-LN blocks, final LN, vocab head. Inputs are
    int token ids [B, L]; outputs are logits [B, L, vocab].

    ``pipeline_stages=S`` wraps the block stack in
    :class:`tpu_dist.parallel.PipelinedBlocks` (``depth`` must divide by
    S): under a mesh with a ``pipe`` axis of size S the stages GPipe-
    pipeline with ``pipeline_microbatches`` microbatches; elsewhere the
    same stacked weights run sequentially.

    ``moe_experts=E`` makes every ``moe_every``-th block a
    Switch-transformer block (:class:`tpu_dist.parallel.MixtureOfExperts`
    replaces the dense MLP; ``ff_dim`` sizes each expert): under a mesh
    with an ``expert`` axis the experts shard and tokens all_to_all;
    elsewhere the same stacked experts run locally. MoE and
    ``pipeline_stages`` are mutually exclusive (the aux loss is state the
    pipeline cannot thread)."""
    from tpu_dist.models.model import Sequential

    ff_dim = ff_dim or 4 * d_model
    if moe_experts and pipeline_stages:
        raise ValueError("moe_experts and pipeline_stages are mutually "
                         "exclusive (see docstring)")
    layers = [Embedding(vocab_size, d_model),
              PositionalEmbedding(max_len=seq_len)]

    def mk_moe():
        return MixtureOfExperts(
            num_experts=moe_experts, ff_dim=ff_dim, top_k=moe_top_k,
            capacity_factor=moe_capacity_factor, groups=moe_groups)

    def mk_block(i: int = 0):
        moe = (mk_moe() if moe_experts and i % max(moe_every, 1) == 0
               else None)
        return TransformerBlock(
            d_model, num_heads, ff_dim, causal=True,
            attention_fn=attention_fn, moe=moe)
    if pipeline_stages:
        if depth % pipeline_stages:
            raise ValueError(
                f"depth {depth} not divisible by pipeline_stages "
                f"{pipeline_stages}")
        per_stage = depth // pipeline_stages
        stage = (mk_block() if per_stage == 1
                 else Block(layers=tuple(mk_block()
                                         for _ in range(per_stage))))
        layers.append(PipelinedBlocks(block=stage,
                                      num_stages=pipeline_stages,
                                      microbatches=pipeline_microbatches))
    else:
        for i in range(depth):
            layers.append(mk_block(i))
    layers += [LayerNormalization(), Dense(vocab_size)]
    return Sequential(layers, input_shape=(seq_len,),
                      name="transformer_lm")
