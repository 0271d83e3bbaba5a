"""Flash-attention kernel correctness vs the dense reference.

Runs the EXACT Pallas kernel logic through the interpreter (same pattern as
the fused-CE tests in test_native_and_pallas.py): forward and all three
input gradients must match the dense softmax path, causal and non-causal,
fp32 and bf16 inputs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.ops import flash_attention as fa
from tpu_dist.models.transformer import _dense_attention


def _qkv(key, b=2, h=2, ln=256, d=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, h, ln, d), jnp.float32).astype(
        dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = fa.flash_attention(q, k, v, causal=causal, scale=scale,
                             interpret=True)
    ref = _dense_attention(q, k, v, causal=causal, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1), b=1, h=2, ln=256, d=32)
    scale = 1.0 / math.sqrt(q.shape[-1])
    # A non-uniform downstream cotangent so dO exercises the delta term.
    w = jnp.linspace(0.5, 1.5, q.shape[-1])

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=True)
        return jnp.sum(o * w)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, causal=causal,
                                        scale=scale) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_bf16_close_to_fp32_dense():
    q, k, v = _qkv(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = fa.flash_attention(q, k, v, causal=True, scale=scale,
                             interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), causal=True, scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("tile_q,tile_k", [(128, 128), (128, 256),
                                           (256, 128)])
def test_multi_tile_causal_boundaries(tile_q, tile_k):
    """ln spanning several tiles — including UNEQUAL tile_q/tile_k —
    exercises the diagonal skip conditions in fwd/dq (j*tk < (qi+1)*tq)
    and dkv ((i+1)*tq > ki*tk); the r3 sweep caught a floor-division bug
    exactly here."""
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, h=1, ln=512, d=32)
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = fa.flash_attention(q, k, v, causal=True, scale=scale,
                             interpret=True, tile_q=tile_q, tile_k=tile_k)
    ref = _dense_attention(q, k, v, causal=True, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    g = jax.grad(lambda *a: fa.flash_attention(
        *a, causal=True, scale=scale, interpret=True, tile_q=tile_q,
        tile_k=tile_k).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: _dense_attention(
        *a, causal=True, scale=scale).sum(), argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g, gr):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   atol=5e-5, rtol=5e-4)


def test_supported_predicate():
    mk = lambda shape: jnp.zeros(shape, jnp.float32)
    assert fa.supported(mk((2, 4, 256, 64)))
    assert fa.supported(mk((2, 4, 2048, 64)))
    assert not fa.supported(mk((2, 4, 200, 64)))      # not a tile multiple
    assert not fa.supported(mk((2, 4, 64, 64)))       # below one tile
    assert not fa.supported(mk((2, 256, 64)))          # wrong rank
    # K/V stream per tile (r4), so the layout is L-independent: sequences
    # far beyond r3's resident-K/V VMEM ceiling are in-envelope.
    assert fa.supported(mk((1, 1, 32768, 64)))


def test_use_flash_env_off(monkeypatch):
    # Stub the backend probe so the env gate is what's actually under test
    # (on the CPU runner _on_tpu() is already False and would mask a broken
    # gate).
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    x = jnp.zeros((2, 4, 256, 64))
    assert fa.use_flash(x)
    monkeypatch.setenv("TPU_DIST_FLASH", "0")
    assert not fa.use_flash(x)


@pytest.mark.parametrize("shape,reason", [
    pytest.param((2, 4, 200, 64), "L=200 is not a multiple of 128",
                 id="ragged-length"),
    pytest.param((2, 256, 64), "rank 3", id="rank"),
    pytest.param((1, 1, 1024, 8192), "VMEM budget", id="vmem"),
])
def test_decline_on_tpu_is_logged_once_with_shape_and_reason(
        monkeypatch, caplog, shape, reason):
    """A TPU backend that leaves the fused kernel says so — once per shape,
    with the reason; off the TPU the dense path is the design and says
    nothing."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    fa.log_declined.cache_clear()
    with caplog.at_level("WARNING", logger=fa.logger.name):
        assert not fa.use_flash(x)                    # CPU: silent
        assert not caplog.records
        monkeypatch.setattr(fa, "_on_tpu", lambda: True)
        assert not fa.use_flash(x)
        assert not fa.use_flash(x)
    (record,) = caplog.records
    assert str(shape) in record.getMessage()
    assert reason in record.getMessage()
    assert reason in fa.decline_reason(x)


def test_mha_layer_unchanged_on_cpu():
    """The default MHA path on CPU still routes to dense (use_flash False
    off-TPU), so existing layer numerics are untouched."""
    assert not fa.use_flash(jnp.zeros((2, 4, 256, 64), jnp.float32))
