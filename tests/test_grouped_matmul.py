"""The grouped matmul kernel (``ops/grouped_matmul.py``) under the Pallas
interpreter against ``jax.lax.ragged_dot``: the row tiles it visits and the
rows each visit writes. ``tests/test_tpu_compile.py`` compiles it for the
chip; ``tests/test_hybrid_lm.py`` holds the expert layer that uses it to
its unblocked product."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.ops import grouped_matmul
from tpu_dist.ops.grouped_matmul import ROW_TILE, grouped_dot, visits

#: name: (rows, rows a group, the visits as (group, row tile) pairs).
CASES = {
    "no-group-has-a-row": (256, [0] * 8, []),
    "all-in-one-tile": (256, [3, 0, 5, 1, 0, 0, 2, 9],
                        [(0, 0), (2, 0), (3, 0), (6, 0), (7, 0)]),
    "exactly-a-tile": (256, [40, 0, 50, 38, 0, 0, 0, 0],
                       [(0, 0), (2, 0), (3, 0)]),
    "groups-astride-tiles": (384, [100, 29, 100, 60, 0, 0, 11, 0],
                             [(0, 0), (1, 0), (1, 1), (2, 1), (3, 1), (3, 2),
                              (6, 2)]),
    "every-row-has-a-group": (384, [48] * 8,
                              [(0, 0), (1, 0), (2, 0), (2, 1), (3, 1), (4, 1),
                               (5, 1), (5, 2), (6, 2), (7, 2)]),
    "one-group-over-padded-rows": (300, [0] * 7 + [300],
                                   [(7, 0), (7, 1), (7, 2)]),
    "fewer-rows-than-a-tile": (100, [10, 0, 0, 0, 0, 0, 0, 20],
                               [(0, 0), (7, 0)]),
    "groups-ending-on-tile-edges": (512, [127, 1, 1, 127, 0, 130, 0, 126],
                                    [(0, 0), (1, 0), (2, 1), (3, 1), (5, 2),
                                     (5, 3), (7, 3)]),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_is_ragged_dot_on_the_rows_that_have_a_group(case, dtype):
    m, sizes, want_visits = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (m, 64), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 32), dtype)
    group, tile, count = visits(sizes, -(-m // ROW_TILE))
    assert list(zip(np.asarray(group)[:int(count)].tolist(),
                    np.asarray(tile)[:int(count)].tolist())) == want_visits
    assert group.shape == tile.shape == (-(-m // ROW_TILE) + 8 - 1,)
    got = jax.jit(lambda *a: grouped_dot(*a, interpret=True))(rows, w, sizes)
    assert got.shape == (m, 32) and got.dtype == dtype
    held = int(sizes.sum())
    want = jax.lax.ragged_dot(rows, w, sizes)[:held]
    # Sums of 64 products in another order: a few float32 roundings, or
    # one rounding of the result to bfloat16 taken the other way.
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_a_matrix_too_large_for_one_tile_goes_by_columns(monkeypatch):
    """A group's matrix is halved along its columns until two tiles fit
    in fast memory (here a limit of 16 KiB against 64 KiB): every column
    tile walks the same visits."""
    monkeypatch.setattr(grouped_matmul, "_WEIGHT_TILE_BYTES", 16 << 10)
    sizes = jnp.asarray([100, 29, 100, 60, 0, 0, 11, 0], jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(2), (384, 32))
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 32, 512))
    got = grouped_dot(rows, w, sizes, interpret=True)
    want = jax.lax.ragged_dot(rows, w, sizes)
    assert float(jnp.abs(got[:300] - want[:300]).max()) <= 1e-4


def test_off_the_tpu_the_product_is_ragged_dot():
    sizes = jnp.asarray([5, 0, 7], jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(4), (16, 8))
    w = jax.random.normal(jax.random.PRNGKey(5), (3, 8, 4), jnp.bfloat16)
    got = grouped_dot(rows, w, sizes)
    want = jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)
    assert got.dtype == rows.dtype and bool(jnp.array_equal(got, want))
