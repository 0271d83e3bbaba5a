"""Sequence/context parallelism: ring attention over a named mesh axis.

The reference exercises data parallelism only (SURVEY.md §2.3, §5.7 — "the
mesh API should simply not preclude adding a sequence axis later"); this
module is that sequence axis, built the TPU-native way so long-context
training is first-class rather than bolted on:

* activations are sharded along the sequence dimension over a mesh axis
  (``'seq'``), so a context of global length L costs each device only
  L/P memory;
* attention over the full context is computed with **ring attention**:
  K/V shards rotate around the mesh axis via ``jax.lax.ppermute`` (ICI
  neighbor exchange — the cheapest collective on a TPU torus) while each
  device's queries stay put, and partial softmax results merge with the
  numerically-stable online (flash-style) accumulator, so no device ever
  materializes the full [L, L] score matrix or the full K/V;
* everything is a pure function under ``shard_map`` + ``jit``: XLA sees a
  static ``lax.scan`` of P ring steps and overlaps each step's ppermute
  with the previous step's block computation.

The communication pattern is the sequence-parallel analog of the gradient
ring all-reduce the reference's README recommends for DP (README.md:5-7):
bandwidth-optimal neighbor exchange, total bytes per device independent of
ring size.

No reference citation exists for this capability (it has none); parity scope
is untouched — ``tpu_dist.parallel.sequence`` is additive.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist.parallel.axes import SEQ_AXIS  # noqa: F401 - canonical home


def _online_merge(m, l, acc, scores, v):
    """Fold one block of attention scores/values into the running
    (max, normalizer, unnormalized-output) accumulator — the standard
    numerically-stable streaming-softmax update.

    Masked-out entries arrive as -inf scores. A row whose every score so far
    is masked keeps m == -inf; the shifts below substitute 0 for the max in
    that case so no -inf - -inf = nan is produced (exp(-inf - 0) = 0 and a
    zero correction keep the row's l/acc at exactly zero)."""
    m_new = jnp.maximum(m, scores.max(axis=-1))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    correction = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    p = jnp.exp(scores - m_safe[..., None])
    l_new = l * correction + p.sum(axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v.astype(p.dtype))
    return m_new, l_new, acc_new


def _mark_varying(x, axes):
    """Mark ``x`` as device-varying over ``axes`` (shard_map type system)."""
    return jax.lax.pcast(x, axes, to="varying")


#: Within-shard K/V chunking threshold/size: shards longer than the
#: threshold fold their block in C-sized chunks via an inner scan, so the
#: live score temp is [B, H, Lc, C] instead of [B, H, Lc, Lc]. 2048 keeps
#: the matmuls MXU-sized while cutting the dominant temp Lc/C-fold.
_KV_CHUNK_AUTO_THRESHOLD = 4096
_KV_CHUNK_DEFAULT = 2048


def _ring_attention_shard(q, k, v, *, axis_name: str, axis_size: int,
                          varying_axes: tuple, causal: bool, scale: float,
                          kv_chunk: Optional[int]):
    """Per-shard body (runs under shard_map): full-context attention for this
    device's query block, K/V shards rotating around ``axis_name``.

    Shapes (per device): q, k, v — [B, H, Lc, D] with Lc = L_global / P.
    """
    my_idx = jax.lax.axis_index(axis_name)
    b, h, lc, d = q.shape
    qf = q.astype(jnp.float32) * scale

    if kv_chunk is not None and (kv_chunk <= 0 or lc % kv_chunk):
        kv_chunk = None  # indivisible/degenerate: fall through to auto
    if kv_chunk is None and lc > _KV_CHUNK_AUTO_THRESHOLD:
        # Auto-chunk long shards (also the fallback for an indivisible
        # explicit kv_chunk — silently losing chunking at exactly the
        # sizes a user reaches for it would invite the OOM they were
        # avoiding). _KV_CHUNK_DEFAULT divides any power-of-two lc above
        # the threshold; for non-power-of-two lc it only applies if it
        # divides.
        if lc % _KV_CHUNK_DEFAULT == 0:
            kv_chunk = _KV_CHUNK_DEFAULT

    # Global positions of this device's queries / of a kv shard from source s.
    q_pos = my_idx * lc + jnp.arange(lc)  # [Lc]

    def fold(m, l, acc, k_blk, v_blk, kv_start):
        """One online-softmax fold of q against a K/V slab whose global
        positions begin at ``kv_start``."""
        scores = jnp.einsum("...qd,...kd->...qk", qf,
                            k_blk.astype(jnp.float32))
        if causal:
            kv_pos = kv_start + jnp.arange(k_blk.shape[2])
            mask = q_pos[:, None] >= kv_pos[None, :]  # [Lq, Lk]
            scores = jnp.where(mask, scores, -jnp.inf)
        return _online_merge(m, l, acc, scores, v_blk)

    def step(carry, t):
        m, l, acc, k_cur, v_cur = carry
        # At ring step t this device holds the shard originating at
        # source = (my_idx - t) mod P (shards travel source -> source+1).
        src = (my_idx - t) % axis_size

        def consume(mla):
            m, l, acc = mla
            if kv_chunk is None:
                return fold(m, l, acc, k_cur, v_cur, src * lc)

            # Long shard: fold in C-chunks via an inner (checkpointed)
            # scan, bounding the live score temp to [B, H, Lc, C]. No
            # chunk of this block is ever fully masked for causal
            # self-attention (future SOURCES are skipped below), so no
            # per-chunk dead-block cond is needed.
            def chunk_step(mla, j):
                m, l, acc = mla
                k_blk = jax.lax.dynamic_slice_in_dim(
                    k_cur, j * kv_chunk, kv_chunk, axis=2)
                v_blk = jax.lax.dynamic_slice_in_dim(
                    v_cur, j * kv_chunk, kv_chunk, axis=2)
                return fold(m, l, acc, k_blk, v_blk,
                            src * lc + j * kv_chunk), None
            return jax.lax.scan(jax.checkpoint(chunk_step), (m, l, acc),
                                jnp.arange(lc // kv_chunk))[0]

        if causal:
            # A shard from a strictly-future source is entirely masked:
            # skip its matmuls instead of computing blocks that contribute
            # exactly zero — that dead work would approach HALF the
            # attention FLOPs at large ring sizes. (src == my_idx is the
            # diagonal block: half-masked, must still be computed.)
            m, l, acc = jax.lax.cond(src > my_idx, lambda mla: mla, consume,
                                     (m, l, acc))
        else:
            m, l, acc = consume((m, l, acc))
        # Rotate AFTER consuming: shard moves to the next device so that at
        # step t+1 we hold source (my_idx - t - 1). The last rotation is
        # redundant but keeps the scan body uniform; XLA overlaps it with
        # the final merge and the result is discarded.
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m, l, acc, k_nxt, v_nxt), None

    # The accumulators become device-varying inside the scan (their updates
    # mix in q/k/v, which vary over every sharded mesh axis), so the initial
    # carry must be cast to the same varying type or scan rejects the carry
    # signature.
    m0 = _mark_varying(jnp.full((b, h, lc), -jnp.inf, jnp.float32),
                       varying_axes)
    l0 = _mark_varying(jnp.zeros((b, h, lc), jnp.float32), varying_axes)
    acc0 = _mark_varying(jnp.zeros((b, h, lc, d), jnp.float32), varying_axes)
    # Rematerialize each ring step on the backward pass: without this, grad
    # saves every step's [Lc, Lc] score block (O(L^2/P) memory — exactly
    # what ring attention exists to avoid); with it, backward memory is
    # O(L/P) and the scores are recomputed per step (the flash-attention
    # trade, cheap next to the ppermute ring).
    (m, l, acc, _, _), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, acc0, k, v), jnp.arange(axis_size))

    # Fully-masked rows (can't happen for self-attention with causal=True,
    # since position i always attends to itself) would give l == 0; guard
    # anyway so padding schemes don't NaN.
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, mesh: Mesh, axis_name: str = SEQ_AXIS,
                   causal: bool = False, scale: Optional[float] = None,
                   batch_axis: Optional[str] = None,
                   kv_chunk: Optional[int] = None):
    """Exact multi-head attention over a sequence-sharded context.

    Args:
      q, k, v: [B, H, L, D] arrays whose L dimension is (or will be) sharded
        over ``axis_name`` of ``mesh``. H is num heads, D head dim.
      mesh: the device mesh; ``axis_name`` must be one of its axes.
      axis_name: mesh axis carrying the sequence shards.
      causal: apply an autoregressive mask over GLOBAL positions.
      scale: score scale; default 1/sqrt(D).
      batch_axis: optional mesh axis sharding the batch dimension (combine
        sequence parallelism with data parallelism).
      kv_chunk: fold each ring step's K/V shard in chunks of this many
        positions (inner checkpointed scan), bounding the live score temp
        to ``[B, H, Lc, kv_chunk]`` instead of ``[B, H, Lc, Lc]``. Default
        None auto-chunks at 2048 when the per-device shard exceeds 4096;
        pass a value to force or widen it (must divide Lc — an
        indivisible value falls back to the auto policy).

    Returns:
      [B, H, L, D] attention output, sequence-sharded like q.

    Exactness: identical (up to float32 accumulation order) to
    ``softmax(q k^T * scale [+ causal mask]) v`` on the gathered arrays —
    asserted by tests/test_sequence.py against the dense reference.
    """
    axis_size = mesh.shape[axis_name]
    # Self-attention contract (ADVICE r2): the causal kv_pos computation
    # derives K/V global positions from q's per-shard length, so a K/V with
    # a different (even if divisible) sequence length would silently get a
    # wrong mask. Enforce the contract instead.
    if k.shape != v.shape or k.shape[2] != q.shape[2]:
        raise ValueError(
            f"ring_attention is self-attention: q/k/v sequence lengths must "
            f"match and k.shape == v.shape; got q={q.shape} k={k.shape} "
            f"v={v.shape}")
    if q.shape[2] % axis_size:
        raise ValueError(
            f"sequence length {q.shape[2]} not divisible by mesh axis "
            f"{axis_name!r} size {axis_size}")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    spec = P(batch_axis, None, axis_name, None)
    varying = (axis_name,) if batch_axis is None else (axis_name, batch_axis)
    body = functools.partial(
        _ring_attention_shard, axis_name=axis_name, axis_size=axis_size,
        varying_axes=varying, causal=causal, scale=scale,
        kv_chunk=kv_chunk)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)


@dataclasses.dataclass(frozen=True)
class RingAttention:
    """Declarative ring-attention spec: ``ring_attention`` with the mesh
    resolved LATE — at call time, from the innermost strategy scope —
    instead of bound eagerly with ``functools.partial``.

    Two consequences, both deliberate:

    * a model holding one as its ``attention_fn`` can full-model
      ``save``/``load_model`` (the spec is plain data; VERDICT r2 asked for
      exactly this), and the restored model binds to whatever mesh the
      RESTORING job's strategy scope provides — checkpoint on 8 devices,
      resume on 32;
    * one model object works under different scopes without rebuilding.

    ``mesh=`` still accepts an explicit mesh for scope-free use (tests,
    custom loops); an explicit mesh is NOT serialized — the saved spec
    always re-resolves at load time.
    """

    axis_name: str = SEQ_AXIS
    batch_axis: Optional[str] = None
    scale: Optional[float] = None
    kv_chunk: Optional[int] = None
    mesh: Optional[Mesh] = None

    def resolve_mesh(self) -> Mesh:
        if self.mesh is not None:
            return self.mesh
        from tpu_dist.parallel.strategy import get_strategy

        mesh = get_strategy().mesh
        if self.axis_name not in mesh.shape:
            raise ValueError(
                f"RingAttention(axis_name={self.axis_name!r}) needs the "
                f"active strategy's mesh to carry that axis; the current "
                f"scope's mesh has axes {dict(mesh.shape)}. Enter a scope "
                f"like MultiWorkerMirroredStrategy(axis_shapes={{'data': 1, "
                f"{self.axis_name!r}: P}}).scope(), or pass mesh= "
                f"explicitly.")
        return mesh

    def __call__(self, q, k, v, *, causal: bool = False):
        return ring_attention(
            q, k, v, mesh=self.resolve_mesh(), axis_name=self.axis_name,
            causal=causal, scale=self.scale, batch_axis=self.batch_axis,
            kv_chunk=self.kv_chunk)


def sequence_sharding(mesh: Mesh, *, axis_name: str = SEQ_AXIS,
                      batch_axis: Optional[str] = None,
                      ndim: int = 4, seq_dim: int = 2) -> NamedSharding:
    """NamedSharding placing an activation's sequence dimension on
    ``axis_name`` (and optionally batch on ``batch_axis``) — use with
    ``jax.device_put`` / ``jit`` in/out shardings to keep long-context
    activations distributed end to end."""
    spec = [None] * ndim
    spec[seq_dim] = axis_name
    if batch_axis is not None:
        spec[0] = batch_axis
    return NamedSharding(mesh, P(*spec))
