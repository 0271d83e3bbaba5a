"""Paged decode attention — a Pallas TPU kernel that walks the page table.

One decode step attends one new query per slot over that slot's cached
keys. The XLA body in ``serve/kv_cache.py`` gathers every slot's whole
page-table row, dequantises it into an fp32 copy in HBM and runs a
masked softmax over all ``max_pages * page_size`` positions: bytes by
capacity, times four. This kernel reads what a slot holds and nothing
else:

  * grid = one program per slot; the page tables and the per-slot key
    counts are scalar-prefetched, so a program knows its pages before it
    starts. A slot with no keys (inactive on the ragged path) starts no
    copy and writes zeros;
  * a slot's pages ``0 .. (keys - 1) // page_size`` are copied HBM -> VMEM
    one page a DMA (a page is one contiguous ``[page_size, H * dk]`` slab
    of the pool), double-buffered in blocks of ``pages_per_block`` pages:
    block ``i + 1`` is in flight while block ``i`` is computed;
  * all heads of a block go through the MXU at once against a
    block-diagonal query ``[H, H * dk]`` (row ``h`` holds ``q_h`` in its
    own ``dk`` columns, zeros elsewhere): ``S = Qbd . K^T`` is ``[H, T]``
    and ``Qbd``'s zeros cost nothing that matters at decode sizes, where
    the kernel is bound by its copies. The payload enters the MXU in the
    query's dtype (an int8 value is exact in bf16) and a probability as
    two bf16 operands, the value and its rounding error; under an fp32
    query the dots run at ``HIGHEST`` precision;
  * an int8 pool's fp32 scale rows multiply the scores (K) and the
    probabilities (V): ``q . (k_q * s) = (q . k_q) * s``, so nothing
    dequantised is ever written anywhere. A float pool passes no scales
    and the multiply is skipped: one algorithm;
  * running max / sum / accumulator in fp32 across blocks (the online
    softmax of ``ops/flash_attention.py``), the tail of the last page
    masked by the slot's key count: scores, scale rows and payload
    alike, since what no copy filled holds whatever was there.

**Grouped heads** (a float pool whose page rows hold ``G`` K/V heads for
``H = G * r`` query heads): the slab is ``[page_size, G * dk]``, the
block-diagonal query ``[H, G * dk]`` holds ``q_h`` in the columns of K/V
head ``h // r``, so ``S`` is still ``[H, T]`` and one product serves all
the query heads of a block; the accumulator's row ``h`` is read back from
those columns into an ``[H, dk]`` output. With ``r = 1`` it is the kernel
above, instruction for instruction.

The scale rows reach the kernel gathered by table row (``[b, H, S]``
fp32, a sixteenth of the payload's bytes at ``dk = 64``): their pages are
too narrow for a copy of their own (``page_size`` x ``H`` floats against
the DMA's 128-lane tiles; a plane of whole tiles a page would hold
``page_size`` x 128 floats for ``2 H`` used, 3.2 times the scale bytes at
20 heads).

Off the TPU the same kernel runs through the Pallas interpreter
(``interpret=None``), which is how the CPU tests hold it to the XLA body.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

#: Keys a block holds at most: the [H, T] score tile and the two
#: double-buffered [T, H * dk] payload tiles stay a few hundred KB.
_BLOCK_KEYS = 128


def pages_per_block(max_pages: int, page_size: int) -> int:
    """Largest divisor of ``max_pages`` whose pages hold at most
    ``_BLOCK_KEYS`` keys (at least one page)."""
    best = 1
    for n in range(1, max_pages + 1):
        if max_pages % n == 0 and n * page_size <= _BLOCK_KEYS:
            best = n
    return best


def decline_reason(pool_k, max_pages: int) -> str | None:
    """Why the compiled kernel does not take these shapes, or None when
    it does: a page slab of whole (8, 128) tiles and key blocks of whole
    lane tiles (the interpreter has no such limits)."""
    _, _, page_size, width = pool_k.shape
    if page_size % 8:
        return f"page_size={page_size} is not a multiple of 8"
    if width % 128:
        return f"heads * key_dim = {width} is not a multiple of 128"
    block = pages_per_block(max_pages, page_size) * page_size
    if block % 128:
        return (f"{max_pages} pages of {page_size} a table row give key "
                f"blocks of {block}, not a multiple of 128")
    return None


def supported(pool_k, max_pages: int) -> bool:
    """Whether the compiled kernel takes these shapes (see
    :func:`decline_reason`)."""
    return decline_reason(pool_k, max_pages) is None


@functools.lru_cache(maxsize=None)
def log_declined(shape: tuple, max_pages: int, reason: str) -> None:
    """Say ONCE per (pool shape, table width, reason) that paged decode
    on a TPU left the kernel — the cache is the once. The XLA body reads
    and dequantises the cache by capacity, so a silent switch reads as a
    slow chip."""
    logger.warning("paged decode attention declined for pool %s, %d pages "
                   "a row on TPU: %s; running the gathered XLA body",
                   shape, max_pages, reason)


def _kernel(layer_ref, n_keys_ref, tables_ref, q_ref, *refs, num_heads: int,
            max_pages: int, ppb: int, sm_scale: float, quantized: bool,
            group: int = 1):
    if quantized:
        ks_ref, vs_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
    b = pl.program_id(0)
    layer = layer_ref[0]
    n_keys = n_keys_ref[b]
    ps, width = k_buf.shape[2], k_buf.shape[3]
    block = ppb * ps
    dk = width * group // num_heads
    n_pages = (n_keys + ps - 1) // ps
    n_blocks = (n_keys + block - 1) // block
    mxu = q_ref.dtype
    precision = (jax.lax.Precision.HIGHEST if mxu == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def page_copy(blk, buf, j, do):
        """Start or wait for the K and V copies of page ``j`` of block
        ``blk``; a page past the slot's last is never moved."""
        page = blk * ppb + j

        @pl.when(page < n_pages)
        def _():
            row = tables_ref[b * max_pages + page]
            for hbm, vmem, sem in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                do(pltpu.make_async_copy(hbm.at[layer, row], vmem.at[buf, j],
                                         sems.at[sem, buf]))

    def start(blk, buf):
        jax.lax.fori_loop(
            0, ppb, lambda j, _: page_copy(blk, buf, j, lambda c: c.start()),
            None)

    def wait(blk, buf):
        jax.lax.fori_loop(
            0, ppb, lambda j, _: page_copy(blk, buf, j, lambda c: c.wait()),
            None)

    # Row h of the block-diagonal query keeps q's columns of head h: its
    # own where every query head has a K/V head, else those of the K/V
    # head it shares (``group`` query heads to one).
    head_of_col = jax.lax.broadcasted_iota(
        jnp.int32, (num_heads, width), 1) // dk
    head_of_row = jax.lax.broadcasted_iota(jnp.int32, (num_heads, width), 0)
    q = q_ref[...].astype(jnp.float32)
    if group > 1:
        head_of_row = head_of_row // group
        q = jnp.concatenate([q] * (width // dk), axis=1)   # [H, G * dk]
    own = head_of_col == head_of_row
    qbd = jnp.where(own, q, 0.0).astype(mxu)

    @pl.when(n_keys > 0)
    def _():
        start(0, 0)

    def body(i, carry):
        m, l, acc = carry
        buf = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            start(i + 1, 1 - buf)

        wait(i, buf)
        first = i * block
        key = first + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        k = k_buf[buf].astype(mxu).reshape(block, width)
        s = jax.lax.dot_general(
            qbd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)  # [H, T]
        at = pl.ds(pl.multiple_of(first, block), block)
        if quantized:
            s = s * ks_ref[:, at]
        s = jnp.where(key < n_keys, s * sm_scale, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        # A masked key's scale row is the scratch page's, and rows of
        # pages that were never moved hold whatever the buffer held:
        # either may be NaN, and 0 * NaN is not 0.
        if quantized:
            p = p * jnp.where(key < n_keys, vs_ref[:, at], 0.0)
        row = first + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        v = v_buf[buf].astype(mxu).reshape(block, width)
        v = jnp.where(row < n_keys, v, jnp.zeros((), mxu))
        pv = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        p_hi = p.astype(mxu)
        acc = alpha * acc + pv(p_hi, v)                             # [H, W]
        if mxu != jnp.float32:
            # A probability is no bf16 value as the payload is: its
            # rounding error goes through the MXU as a second operand,
            # so P . V is what the fp32 XLA body computes.
            acc = acc + pv((p - p_hi.astype(jnp.float32)).astype(mxu), v)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((num_heads, 1), -jnp.inf, jnp.float32),
         jnp.zeros((num_heads, 1), jnp.float32),
         jnp.zeros((num_heads, width), jnp.float32)))
    # A slot with no keys has l == 0: zeros, never NaN (its row goes on
    # through the layers and is written to the scratch page).
    out = jnp.where(own, acc / jnp.where(l > 0.0, l, 1.0), 0.0)
    if group > 1:
        # Row h's values lie in its K/V head's columns: fold the column
        # groups onto one another (all but one are noughts in each row).
        o_ref[...] = sum(out[:, g * dk:(g + 1) * dk]
                         for g in range(width // dk)).astype(o_ref.dtype)
    else:
        o_ref[...] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, layer, tables, n_keys, *,
                    scales=None, interpret: bool | None = None):
    """Attention of one query per slot over the slot's pages.

    Args:
      q: ``[b, H, dk]``, the compute dtype.
      k_pages / v_pages: the pool's arrays, ``[L, P + 1, page_size,
        H * dk]``, int8 or float; only layer ``layer`` is read (an
        operand, so every layer's call is the same kernel). A float pool
        may hold fewer K/V heads, ``[.., G * dk]`` with ``G`` dividing
        ``H``: query head ``h`` then reads K/V head ``h // (H // G)``.
      tables: int32 ``[b, max_pages]`` page-table rows.
      n_keys: int32 ``[b]`` — positions ``0 .. n_keys - 1`` of a slot are
        attended; 0 visits no page and yields zeros, and a count past
        the table row's capacity (a retired slot's stale length) stops
        at it.
      scales: ``(k_scale, v_scale)``, fp32 ``[b, H, max_pages *
        page_size]`` in table-row order, for an int8 pool; None for a
        float pool.
      interpret: run through the Pallas interpreter; None does so off
        the TPU, where the kernel cannot compile.

    Returns:
      ``[b, H, dk]`` in ``q``'s dtype.
    """
    b, num_heads, dk = q.shape
    _, _, ps, width = k_pages.shape
    max_pages = tables.shape[1]
    ppb = pages_per_block(max_pages, ps)
    quantized = scales is not None
    group = num_heads * dk // width
    if group * width != num_heads * dk or (group > 1 and quantized):
        raise ValueError(
            f"paged attention: {num_heads} query heads of {dk} over page "
            f"rows of {width} values (grouped heads take a float pool)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    row = lambda i, *_: (i, 0, 0)
    # One query row a slot, all heads side by side; grouped heads come as
    # [H, dk] and leave so.
    q_block = (None, 1, width) if group == 1 else (None, num_heads, dk)
    in_specs = [pl.BlockSpec(q_block, row)]
    operands = [q.reshape(b, *q_block[1:])]
    if quantized:
        in_specs += [pl.BlockSpec((None, num_heads, max_pages * ps), row)] * 2
        operands += list(scales)
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands += [k_pages, v_pages]
    out = pl.pallas_call(
        functools.partial(
            _kernel, num_heads=num_heads, max_pages=max_pages, ppb=ppb,
            sm_scale=dk ** -0.5, quantized=quantized,
            **({"group": group} if group > 1 else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(q_block, row),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, ps, width), k_pages.dtype),
                pltpu.VMEM((2, ppb, ps, width), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, *q_block[1:]), q.dtype),
        name="paged_decode_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.clip(n_keys.astype(jnp.int32), 0, max_pages * ps),
      tables.reshape(-1).astype(jnp.int32), *operands)
    return out.reshape(b, num_heads, dk)
