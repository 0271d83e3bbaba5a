"""Fused flash attention — Pallas TPU kernel for the single-device path.

The transformer family's default attention materialized the full
``[B, H, L, L]`` fp32 score matrix through softmax
(models/transformer.py:_dense_attention) — an O(L^2) HBM round-trip that
capped the LM at ~26-30 % MFU (round-2 verdict). This module is the fused
replacement: the tiled online-softmax computation (same math as the ring
attention accumulator, parallel/sequence.py:43-59) as ONE Pallas kernel per
pass, so scores live only in VMEM a [G, TQ, TK] tile at a time.

Reference parity note: the reference's equivalent is TF/cuDNN fused
attention inside the XLA/StreamExecutor stack; SURVEY.md §2.4 reserves
hand-written kernels for ops "profiling demands" — the round-2 MFU audit
demanded this one.

Design (forward):
  * collapse [B, H] into one dimension of B*H independent attention
    instances; each program owns a HEAD GROUP of G consecutive instances
    (batched ``dot_general`` over the leading G axis) — v5e measurement:
    ~1.1 us fixed cost per grid program, so at the LM's shape (B*H = 512,
    L = 512) a one-head-per-program grid spent more time on program
    overhead than on math; grouping divides program count by G;
  * grid = (B*H/G, L/TQ, L/TK) with the KEY axis innermost: Pallas's
    pipeline streams one [G, TK, D] K/V tile at a time from HBM
    (double-buffered DMA) while the (row-max, normalizer, unnormalized
    output) accumulator lives in VMEM scratch across the key-axis steps.
    Residency is per-TILE, not per-sequence — r3's design kept the whole
    [G, L, D] K/V resident, so growing L collapsed the head group to 1
    and MFU with it (34.6 % -> 10.9 % over seq 512 -> 8192, the r3
    longcontext sweep); with streaming, the layout is L-independent;
  * matmuls keep the INPUT dtype on the MXU (bf16 stays bf16) with fp32
    accumulation via ``preferred_element_type``; only the softmax
    statistics and accumulators are fp32 — forcing operands to fp32 would
    halve bf16 MXU throughput for nothing;
  * causal masking skips strictly-future key tiles with ``pl.when`` on
    the key-axis grid step — ~half the FLOPs of dense, matching the
    dead-block skip in the ring path (their tile DMA rides the pipeline
    either way; FLOPs, not bandwidth, are the scarce resource here);
  * the log-sum-exp per query row is written out as a residual;
  * G and the tile sizes are picked per call against a VMEM budget:
    bigger tiles amortize per-program overhead, bounded by the [G, TQ, TK]
    fp32 score tile's footprint plus the double-buffered per-tile streams
    and the scratch accumulator (all L-independent).

Backward recomputes probabilities from the saved lse (the flash trade:
O(L) residual memory instead of O(L^2) saved scores) in two kernels:
  * dq kernel — same grid/loop structure as forward;
  * dk/dv kernel — grid over KEY tiles, inner loop over query tiles
    starting at the diagonal (for causal, earlier query tiles are masked).
Both consume delta = rowsum(dO * O), the standard softmax-backward
rank-1 correction, computed outside the kernel (one cheap fused
elementwise-reduce XLA handles well).

All entry points take ``interpret=`` so the CPU test suite runs the exact
kernel logic through the Pallas interpreter (tests/test_flash_attention.py
asserts fwd + grads match the dense reference).
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

#: Tile-size candidates, largest first. Square [T, T] score tiles: the v5e
#: sweep showed causal skipping needs TK <= TQ to bite, and MXU efficiency
#: wants the biggest tile that compiles. r4 (streaming layout) re-swept
#: with 1024 in the pool: it wins at every L >= 1024 it divides
#: (+6-13 % tok/s; seq 8192 went 22.8 -> 27.1 % MFU with the bigger
#: budget below), while 512 keeps the short-sequence crown.
_T_CANDIDATES = (1024, 512, 256, 128)
_G_CANDIDATES = (8, 4, 2, 1)

#: VMEM bytes the layout estimator may plan against. The physical VMEM is
#: 128 MB; XLA's default SCOPED limit is 16 MB, which the kernel raises via
#: vmem_limit_bytes below. r3's resident-K/V design throttled this to
#: 13 MB; with per-tile streaming (r4) the estimate tracks reality much
#: closer, and the 26 MB re-calibration lets the backward pair take
#: [1024, 1024] score tiles (measured: seq 16384 22.6 -> 25.6 % MFU)
#: while staying far under the raised scoped limit.
_VMEM_BUDGET = 26 * 1024 * 1024

#: Scoped-VMEM ceiling passed to Mosaic (< the 128 MB physical so XLA keeps
#: room for its own buffers). Without this, shapes whose true footprint
#: lands in (16, ~32] MB — e.g. the LM at seq >= 1024 — fail AOT compile
#: with a scoped-vmem stack OOM even though the chip has 8x the memory.
_VMEM_LIMIT = 100 * 1024 * 1024


def _compiler_params(interpret):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _fits(g, t, ln, d, itemsize, n_score):
    """VMEM estimate, L-INDEPENDENT by design: the pipeline keeps ~2
    double-buffered [G, T, D] tiles per streamed operand (K and V — q/o
    and the stats are one tile each) plus ~n_score live fp32 [G, T, T]
    score-shaped stack temporaries (s/p/dp/ds and the dot operands Mosaic
    keeps alive; 2.5 measured adequate for the fwd kernel, 4 for the
    backward pair) plus the fp32 scratch accumulator [G, T, D]."""
    tiles = 6 * g * t * d * itemsize
    scratch = g * t * d * 4 + 2 * g * t * 4
    stack = n_score * g * t * t * 4
    return tiles + scratch + stack <= _VMEM_BUDGET


def _pick_layout(bh: int, ln: int, d: int, itemsize: int, n_score: float):
    """Choose (G, T): the largest square tile that divides L, then the
    largest head group that fits the budget. Tile size dominates (MXU
    shapes); the group then amortizes the ~1.1 us/program fixed cost.
    Returns None if L has no 128-multiple tiling that fits. Streaming
    makes the choice independent of L, so the layout (and the MFU) no
    longer degrades as sequences grow."""
    for t in _T_CANDIDATES:
        if ln % t:
            continue
        for g in _G_CANDIDATES:
            if bh % g == 0 and _fits(g, t, ln, d, itemsize, n_score):
                return g, t
    return None


def _mask_tile(s, q_start, k_start):
    """Causal mask for one [G, TQ, TK] score tile at global offsets."""
    g, tq, tk = s.shape
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (g, tq, tk), 1)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g, tq, tk), 2)
    return jnp.where(q_pos >= k_pos, s, -jnp.inf)


def _bdot(a, b, contract, out_dtype=jnp.float32):
    """Batched-over-leading-axis dot: a [G, M, N] x b [G, P, Q]."""
    return jax.lax.dot_general(
        a, b, ((contract[0], contract[1]), ((0,), (0,))),
        preferred_element_type=out_dtype)


# -- forward ------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, causal, scale, nk, tq, tk):
    """One (head-group, query-tile, KEY-tile) grid step. The key axis is
    the innermost grid dimension: Pallas streams each [G, TK, D] K/V tile
    from HBM while the online-softmax state (m, l, acc) persists in VMEM
    scratch across the key steps of one query tile."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Key tiles strictly past this query tile's diagonal are fully masked —
    # skip their matmuls (the same dead-block cut as the ring path). Their
    # DMA is part of the pipeline either way; the FLOPs are the scarce
    # resource here.
    live = (j * tk < (qi + 1) * tq) if causal else True

    @pl.when(live)
    def _consume():
        q = q_ref[:]                                       # (G, TQ, D)
        k_blk = k_ref[:]                                   # (G, TK, D)
        v_blk = v_ref[:]
        s = _bdot(q, k_blk, ((2,), (2,))) * scale          # (G, TQ, TK) f32
        if causal:
            s = _mask_tile(s, qi * tq, j * tk)
        # Online-softmax fold. m starts at -inf: first step's correction is
        # exp(-inf - finite) = 0, which cleanly zeroes the empty l/acc; m
        # itself becomes finite after any unmasked entry (causal tiles at or
        # before the diagonal always contain the self position), so no
        # -inf - -inf NaN path exists here.
        m = m_ref[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (G, TQ, TK) f32
        corr = jnp.exp(m - m_new)                          # (G, TQ, 1)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + _bdot(p.astype(v_blk.dtype),
                                               v_blk, ((2,), (1,)))

    @pl.when(j == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = m_ref[:] + jnp.log(l_safe)


def _fwd(q3, k3, v3, causal, scale, interpret, g, tq, tk):
    """q3/k3/v3: [BH, L, D] -> (o [BH, L, D], lse [BH, L, 1])."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, ln, d = q3.shape
    nq, nk = ln // tq, ln // tk
    space = pl.ANY if interpret else pltpu.VMEM
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               nk=nk, tq=tq, tk=tk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh // g, nq, nk),
        in_specs=[
            pl.BlockSpec((g, tq, d), lambda b, i, j: (b, i, 0),
                         memory_space=space),
            pl.BlockSpec((g, tk, d), lambda b, i, j: (b, j, 0),
                         memory_space=space),
            pl.BlockSpec((g, tk, d), lambda b, i, j: (b, j, 0),
                         memory_space=space),
        ],
        out_specs=[
            pl.BlockSpec((g, tq, d), lambda b, i, j: (b, i, 0),
                         memory_space=space),
            pl.BlockSpec((g, tq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=space),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, ln, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, ln, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, tq, 1), jnp.float32),
            pltpu.VMEM((g, tq, 1), jnp.float32),
            pltpu.VMEM((g, tq, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q3, k3, v3)
    return o, lse


# -- backward: dq -------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *, causal, scale, nk, tq, tk):
    """Grid (BH/G, L/TQ, L/TK), key axis innermost and streamed; the dq
    accumulator persists in VMEM scratch across the key steps."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    live = (j * tk < (qi + 1) * tq) if causal else True

    @pl.when(live)
    def _consume():
        q = q_ref[:]                                       # (G, TQ, D)
        do = do_ref[:]                                     # (G, TQ, D)
        lse = lse_ref[:]                                   # (G, TQ, 1) f32
        delta = delta_ref[:]                               # (G, TQ, 1) f32
        k_blk = k_ref[:]                                   # (G, TK, D)
        v_blk = v_ref[:]
        s = _bdot(q, k_blk, ((2,), (2,))) * scale
        if causal:
            # Masked entries: s = -inf -> p = exp(-inf - lse) = 0 exactly.
            s = _mask_tile(s, qi * tq, j * tk)
        p = jnp.exp(s - lse)                               # (G, TQ, TK) f32
        dp = _bdot(do, v_blk, ((2,), (2,)))                # (G, TQ, TK) f32
        ds = (p * (dp - delta) * scale).astype(k_blk.dtype)
        dq_acc_ref[:] = dq_acc_ref[:] + _bdot(ds, k_blk, ((2,), (1,)))

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[:] = dq_acc_ref[:].astype(dq_ref.dtype)


# -- backward: dk, dv ---------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                causal, scale, nq, tq, tk):
    """Grid (BH/G, L/TK, L/TQ): KEY tile per middle index, QUERY axis
    innermost and streamed (q/do/lse/delta tiles DMA per step); dk/dv
    accumulate in VMEM scratch."""
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    # Query tiles strictly before this key tile's diagonal see none of
    # these keys — skip them.
    live = ((i + 1) * tq > ki * tk) if causal else True

    @pl.when(live)
    def _consume():
        k = k_ref[:]                                       # (G, TK, D)
        v = v_ref[:]
        q_blk = q_ref[:]                                   # (G, TQ, D)
        do_blk = do_ref[:]
        lse_blk = lse_ref[:]                               # (G, TQ, 1)
        delta_blk = delta_ref[:]
        s = _bdot(q_blk, k, ((2,), (2,))) * scale          # (G, TQ, TK)
        if causal:
            s = _mask_tile(s, i * tq, ki * tk)
        p = jnp.exp(s - lse_blk)                           # (G, TQ, TK) f32
        dv_acc_ref[:] = dv_acc_ref[:] + _bdot(
            p.astype(do_blk.dtype), do_blk, ((1,), (1,)))
        dp = _bdot(do_blk, v, ((2,), (2,)))                # (G, TQ, TK)
        ds = (p * (dp - delta_blk) * scale).astype(q_blk.dtype)
        dk_acc_ref[:] = dk_acc_ref[:] + _bdot(ds, q_blk, ((1,), (1,)))

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[:] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc_ref[:].astype(dv_ref.dtype)


# -- backward: fused single-tile dq, dk, dv -----------------------------------


def _dqkv_single_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dk_ref, dv_ref, *, causal, scale):
    """When L fits one [G, T, T] score tile (the benchmark LM's shape),
    the split dq / dkv kernels each recompute the same s and p and each
    re-read the operands; this fused variant computes them once and emits
    all three grads — half the backward programs, one shared recompute."""
    q = q_ref[:]                                           # (G, T, D)
    k = k_ref[:]
    v = v_ref[:]
    do = do_ref[:]
    lse = lse_ref[:]                                       # (G, T, 1)
    delta = delta_ref[:]
    s = _bdot(q, k, ((2,), (2,))) * scale                  # (G, T, T) f32
    if causal:
        s = _mask_tile(s, 0, 0)
    p = jnp.exp(s - lse)
    dv_ref[:] = _bdot(p.astype(do.dtype), do,
                      ((1,), (1,))).astype(dv_ref.dtype)
    dp = _bdot(do, v, ((2,), (2,)))                        # (G, T, T) f32
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    dq_ref[:] = _bdot(ds, k, ((2,), (1,))).astype(dq_ref.dtype)
    dk_ref[:] = _bdot(ds, q, ((1,), (1,))).astype(dk_ref.dtype)


def _bwd(q3, k3, v3, o3, lse, g3, causal, scale, interpret, g, tq, tk):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, ln, d = q3.shape
    nq, nk = ln // tq, ln // tk
    space = pl.ANY if interpret else pltpu.VMEM
    # delta_i = dO_i . O_i — the rank-1 softmax-jacobian correction; one
    # fused multiply+reduce, no reason to hand-write it.
    delta = jnp.sum(g3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # (BH, L, 1)

    if nq == 1 and nk == 1:
        return pl.pallas_call(
            functools.partial(_dqkv_single_kernel, causal=causal,
                              scale=scale),
            grid=(bh // g,),
            in_specs=[pl.BlockSpec((g, ln, d), lambda b: (b, 0, 0),
                                   memory_space=space)] * 4
            + [pl.BlockSpec((g, ln, 1), lambda b: (b, 0, 0),
                            memory_space=space)] * 2,
            out_specs=[pl.BlockSpec((g, ln, d), lambda b: (b, 0, 0),
                                    memory_space=space)] * 3,
            out_shape=[jax.ShapeDtypeStruct((bh, ln, d), q3.dtype),
                       jax.ShapeDtypeStruct((bh, ln, d), k3.dtype),
                       jax.ShapeDtypeStruct((bh, ln, d), v3.dtype)],
            interpret=interpret,
            compiler_params=_compiler_params(interpret),
        )(q3, k3, v3, g3, lse, delta)

    # dq: query tile per middle index, key axis innermost (streamed).
    qtile = pl.BlockSpec((g, tq, d), lambda b, i, j: (b, i, 0),
                         memory_space=space)
    ktile_j = pl.BlockSpec((g, tk, d), lambda b, i, j: (b, j, 0),
                           memory_space=space)
    stat_q = pl.BlockSpec((g, tq, 1), lambda b, i, j: (b, i, 0),
                          memory_space=space)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale, nk=nk,
                          tq=tq, tk=tk),
        grid=(bh // g, nq, nk),
        in_specs=[qtile, ktile_j, ktile_j, qtile, stat_q, stat_q],
        out_specs=qtile,
        out_shape=jax.ShapeDtypeStruct((bh, ln, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((g, tq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q3, k3, v3, g3, lse, delta)

    # dk/dv: key tile per middle index, QUERY axis innermost (streamed).
    ktile = pl.BlockSpec((g, tk, d), lambda b, ki, i: (b, ki, 0),
                         memory_space=space)
    qtile_i = pl.BlockSpec((g, tq, d), lambda b, ki, i: (b, i, 0),
                           memory_space=space)
    stat_i = pl.BlockSpec((g, tq, 1), lambda b, ki, i: (b, i, 0),
                          memory_space=space)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, nq=nq,
                          tq=tq, tk=tk),
        grid=(bh // g, nk, nq),
        in_specs=[qtile_i, ktile, ktile, qtile_i, stat_i, stat_i],
        out_specs=[ktile, ktile],
        out_shape=[jax.ShapeDtypeStruct((bh, ln, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, ln, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((g, tk, d), jnp.float32),
                        pltpu.VMEM((g, tk, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q3, k3, v3, g3, lse, delta)
    return dq, dk, dv


# -- custom-vjp op over [BH, L, D] --------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, scale, interpret, fwd_layout, bwd_layout):
    o, _ = _fwd(q3, k3, v3, causal, scale, interpret, *fwd_layout)
    return o


def _flash_fwd(q3, k3, v3, causal, scale, interpret, fwd_layout,
               bwd_layout):
    o, lse = _fwd(q3, k3, v3, causal, scale, interpret, *fwd_layout)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, interpret, fwd_layout, bwd_layout, res,
               dout):
    q3, k3, v3, o3, lse = res
    return _bwd(q3, k3, v3, o3, lse, dout, causal, scale, interpret,
                *bwd_layout)


_flash.defvjp(_flash_fwd, _flash_bwd)


# -- public wrapper -----------------------------------------------------------


from tpu_dist.ops.pallas_kernels import _on_tpu


def decline_reason(q) -> str | None:
    """Why the fused kernel does not take this shape, or None when it does:
    it handles [B, H, L, D] with L a tile multiple and the streamed
    operands within the VMEM budget."""
    if q.ndim != 4:
        return f"rank {q.ndim}, the kernel takes [B, H, L, D]"
    b, h, ln, d = q.shape
    if ln % _T_CANDIDATES[-1]:
        return f"L={ln} is not a multiple of {_T_CANDIDATES[-1]}"
    isz = jnp.dtype(q.dtype).itemsize
    if (_pick_layout(b * h, ln, d, isz, 2.5) is None
            or _pick_layout(b * h, ln, d, isz, 4.0) is None):
        return (f"no (head group, tile) layout of D={d} fits the "
                f"{_VMEM_BUDGET >> 20} MiB VMEM budget")
    return None


def supported(q) -> bool:
    """Whether the fused kernel handles this shape (see decline_reason)."""
    return decline_reason(q) is None


@functools.lru_cache(maxsize=None)
def log_declined(shape: tuple, dtype: str, reason: str) -> None:
    """Say ONCE per (shape, dtype, reason) that attention on a TPU left the
    fused kernel — the cache is the once. Dense attention materializes the
    [B, H, L, L] scores, so a silent switch reads as a slow chip."""
    logger.warning("flash attention declined for %s %s on TPU: %s; "
                   "running dense attention", dtype, shape, reason)


def flash_attention(q, k, v, *, causal: bool = False, scale: float,
                    interpret: bool | None = None,
                    tile_q: int | None = None, tile_k: int | None = None,
                    head_group: int | None = None):
    """Fused scaled-dot-product attention, [B, H, L, D] -> [B, H, L, D].

    Differentiable w.r.t. q/k/v via flash backward kernels (probabilities
    recomputed from the saved per-row logsumexp — O(L) residuals).
    ``interpret=True`` runs the Pallas interpreter (CPU-testable); default
    dispatches the compiled kernel (callers gate on TPU + ``supported()``).
    ``tile_q``/``tile_k``/``head_group`` override the measured-default
    layout selection (used by tests to force multi-tile loops at small L).
    """
    if interpret is None:
        interpret = False
    b, h, ln, d = q.shape
    bh = b * h
    isz = jnp.dtype(q.dtype).itemsize

    def resolve(n_score):
        picked = _pick_layout(bh, ln, d, isz, n_score)
        if picked is None and not (tile_q and tile_k):
            raise ValueError(
                f"flash_attention: no tile layout for shape {q.shape}; "
                "check supported() before dispatching")
        g, t = picked if picked is not None else (1, None)
        g = head_group or g
        tq = tile_q or t
        tk = tile_k or t
        if bh % g or ln % tq or ln % tk:
            raise ValueError(
                f"flash_attention: layout G={g} TQ={tq} TK={tk} does not "
                f"divide shape {q.shape}")
        return g, tq, tk

    fold = lambda x: x.reshape(bh, ln, d)
    o = _flash(fold(q), fold(k), fold(v), causal, scale, interpret,
               resolve(2.5), resolve(4.0))
    return o.reshape(b, h, ln, d)


def analytic_train_flops(batch: int, heads: int, seq_len: int,
                         head_dim: int, *, causal: bool = True) -> float:
    """Model FLOPs of one attention layer's train step (fwd + 2x bwd, the
    standard MFU convention — the backward RE-computation of scores the
    flash trade makes is deliberately NOT counted; it is overhead, not
    model math). Needed because the fused kernel is an XLA custom call,
    which ``cost_analysis()`` scores as ZERO flops — without this
    correction a flash program's reported MFU decays with L purely as an
    accounting artifact (the r3 longcontext sweep's 34.6 % -> 10.9 %
    "decay" was mostly this). Causal counts the half the kernel actually
    computes (dead blocks are skipped)."""
    fwd = 4.0 * batch * heads * seq_len * seq_len * head_dim
    total = 3.0 * fwd
    return total * (0.5 if causal else 1.0)


def use_flash(q) -> bool:
    """Dispatch predicate for the default attention path: fused kernel on
    TPU for supported shapes unless TPU_DIST_FLASH=0 (A/B escape hatch).
    A decline on TPU is logged once with the shape and the reason."""
    if not _on_tpu():
        return False
    reason = ("TPU_DIST_FLASH=0"
              if os.environ.get("TPU_DIST_FLASH", "").strip() == "0"
              else decline_reason(q))
    if reason is None:
        return True
    log_declined(tuple(q.shape), jnp.dtype(q.dtype).name, reason)
    return False
