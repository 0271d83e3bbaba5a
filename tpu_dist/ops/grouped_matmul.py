"""Grouped matmul — a Pallas TPU kernel for rows sorted by group.

``rows`` [M, a] are sorted by group and ``w`` [G, a, b] holds a matrix a
group: the first ``sizes[0]`` rows times ``w[0]``, the next ``sizes[1]``
times ``w[1]``, and so on (``jax.lax.ragged_dot``). Routed experts make
many groups of few rows (one or two a decode step, five to eight a prefill
chunk, ``parallel/routed_experts.py``), and then what a grouped matmul
costs is what it reads of ``w`` and how it tiles:

  * XLA's ragged-dot kernel sizes its row tile by the rows it is HANDED
    (``min(M, 512)``), not by the rows that have a group, makes every
    (group, row tile) it visits pay that whole tile, and cuts a group's
    matrix into tiles of 512 x 256: fifteen grid steps a visit at 2560 x
    768, each with its own overhead;
  * this kernel's row tile is ``ROW_TILE`` and a group's WHOLE matrix is
    one tile, so a visit is one grid step, one matrix read at the speed of
    the memory and one product with no accumulation across steps.

**A matrix over** ``_WEIGHT_TILE_BYTES`` (two tiles are in fast memory at
a time) is cut along its COLUMNS, halved while a tile is over that and
its half is still whole lane tiles of 128: the grid gains an outer axis of column
passes, each of which makes every visit again on its own columns. The
depth stays whole in every tile (no accumulation across steps), so a pass
re-reads a visit's row tile (``ROW_TILE x depth``) and reads its own
columns of the group's matrix once: the matrix's bytes are read once in
all, the rows once a pass. At 2560 x 768 in bfloat16 (3.9 MB) there is one
pass; at 6144 x 2048 (25 MB) the column tile is 256 (3.1 MB) and at 2048 x
6144 it is 768 (3.1 MB): eight passes each, which re-read a 1.5 MB or
0.5 MB row tile against a 3.1 MB weight tile a step.

The grid is the visits: every (group, row tile) pair in which the group
has a row, in row order, so visits of one row tile are consecutive and its
output block stays in fast memory between them; each writes its own rows.
Group ids, row tiles and group offsets are scalar-prefetched. Rows behind
the groups (``sum(sizes) < M``) lie in no visit's rows and come back
undefined; row tiles with no such row are never visited.

Off the TPU :func:`grouped_dot` is ``jax.lax.ragged_dot``; ``interpret``
runs the kernel under the Pallas interpreter, which is how the CPU tests
hold it to that. The kernel is opaque to the partitioner: a program that
holds it runs on one device. It has no reverse-mode rule: nothing trains
through the routed experts yet, and a ``fit()`` through them on a TPU needs
a ``custom_vjp`` over two more grouped products (``ragged_dot`` has one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dist.ops.pallas_kernels import _on_tpu

#: Rows of a row tile: every visit pays them all, whatever it holds.
ROW_TILE = 128

#: The most of a group's matrix that is one tile (two are in fast memory at
#: a time, beside the row and output tiles).
_WEIGHT_TILE_BYTES = 4 << 20


def visits(sizes, num_tiles: int):
    """``sizes`` [G] -> ``(group [V], tile [V], count)``: the (group, row
    tile) pairs in which the group has a row, in row order; ``V =
    num_tiles + G - 1`` is the most there can be and ``count`` how many
    there are (entries behind it name the last group and are never
    visited)."""
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // ROW_TILE
    spans = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    upto = jnp.cumsum(spans)                  # visits up to and with a group
    most = num_tiles + sizes.shape[0] - 1
    visit = jnp.arange(most, dtype=jnp.int32)
    # A visit's group is the number of groups whose visits all lie before.
    group = jnp.minimum(jnp.sum(visit[:, None] >= upto[None, :], axis=1),
                        sizes.shape[0] - 1).astype(jnp.int32)
    tile = first[group] + visit - (upto - spans)[group]
    return group, jnp.clip(tile, 0, num_tiles - 1), upto[-1]


def _kernel(group_ref, tile_ref, ends_ref, rows_ref, w_ref, out_ref):
    visit = pl.program_id(1)
    group = group_ref[visit]
    row = tile_ref[visit] * ROW_TILE + jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 0)
    start = jnp.where(group > 0, ends_ref[jnp.maximum(group - 1, 0)], 0)
    mine = (row >= start) & (row < ends_ref[group])
    product = jnp.dot(rows_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, product.astype(out_ref.dtype),
                             out_ref[...])


def grouped_dot(rows, w, sizes, *, interpret: bool = False):
    """``rows`` [M, a], sorted by group, times ``w`` [G, a, b], ``sizes``
    [G] int32 rows a group -> [M, b] in ``rows``' dtype; rows behind the
    groups come back undefined. The kernel on a TPU (or under
    ``interpret``), else ``jax.lax.ragged_dot``."""
    w = w.astype(rows.dtype)
    if not (_on_tpu() or interpret):
        return jax.lax.ragged_dot(rows, w, sizes)
    return _visit_tiles(rows, w, sizes, interpret=interpret)


# A program of many expert layers calls this with few distinct shapes: as a
# function of its own it is traced once a shape and lowered once a program.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _visit_tiles(rows, w, sizes, *, interpret):
    m, depth = rows.shape
    width = w.shape[2]
    cols = width
    while (depth * cols * w.dtype.itemsize > _WEIGHT_TILE_BYTES
           and cols % 256 == 0):
        cols //= 2
    padded = jnp.pad(rows, ((0, -m % ROW_TILE), (0, 0)))
    num_tiles = padded.shape[0] // ROW_TILE
    group, tile, count = visits(sizes, num_tiles)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(width // cols, count),
            in_specs=[
                pl.BlockSpec((ROW_TILE, depth),
                             lambda c, v, group, tile, ends: (tile[v], 0)),
                pl.BlockSpec((None, depth, cols),
                             lambda c, v, group, tile, ends:
                             (group[v], 0, c)),
            ],
            out_specs=pl.BlockSpec(
                (ROW_TILE, cols),
                lambda c, v, group, tile, ends: (tile[v], c))),
        out_shape=jax.ShapeDtypeStruct((padded.shape[0], width), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="grouped_matmul",
        interpret=interpret,
    )(group, tile, jnp.cumsum(sizes), padded, w)
    return out[:m]
