"""The main path's Pallas kernels compile for the real chip — pinned.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described and not attached (``v5e:2x2``, the machine the
driver checks on). Interpret mode cannot see what this sees: unaligned
tiles, a kernel's fast-memory budget, a Mosaic lowering jax no longer
accepts. Nothing runs here — a pass is not a chip run.

Shapes are ``chip_smoke.py``'s: GPT-2-small attention ``[8, 12, 1024, 64]``.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_dist.ops import flash_attention as fa
from tpu_dist.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def v5e_chip():
    """One described v5e chip, as a sharding for ``ShapeDtypeStruct``s."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip: the next
    compile would warn. conftest turns the cache off for the whole suite;
    hold that here whatever a caller's environment says."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_loss(q, k, v):
    out = fa.flash_attention(q, k, v, causal=True,
                             scale=q.shape[-1] ** -0.5)
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("shape,dtype,grad", [
    pytest.param((8, 12, 1024, 64), jnp.bfloat16, False, id="fwd-bf16"),
    pytest.param((8, 12, 1024, 64), jnp.bfloat16, True, id="bwd-bf16"),
    pytest.param((8, 12, 1024, 64), jnp.float32, True, id="bwd-fp32"),
    pytest.param((2, 8, 2048, 128), jnp.bfloat16, True, id="bwd-headdim128"),
])
def test_flash_attention_compiles(v5e_chip, shape, dtype, grad):
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
    assert fa.supported(q)
    fn = jax.grad(_flash_loss, argnums=(0, 1, 2)) if grad else _flash_loss
    assert "tpu_custom_call" in _compiled_text(fn, q, q, q)


def test_fused_cross_entropy_compiles(v5e_chip):
    # One LM shape: [batch * seq, vocab] = [8192, 8192] logits.
    logits = jax.ShapeDtypeStruct((8192, 8192), jnp.float32,
                                  sharding=v5e_chip)
    labels = jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=v5e_chip)

    def loss(lg, lb):
        return pk.fused_sparse_cross_entropy(lg, lb, interpret=False).mean()

    text = _compiled_text(jax.value_and_grad(loss), logits, labels)
    assert text.count("tpu_custom_call") >= 2  # forward and backward


def test_fused_adam_compiles(v5e_chip):
    tree = {"w": jax.ShapeDtypeStruct((768, 3072), jnp.float32,
                                      sharding=v5e_chip),
            "b": jax.ShapeDtypeStruct((3072,), jnp.float32,
                                      sharding=v5e_chip),
            "g": jax.ShapeDtypeStruct((7,), jnp.float32, sharding=v5e_chip)}
    scale = jax.ShapeDtypeStruct((), jnp.float32, sharding=v5e_chip)
    fn = functools.partial(pk.fused_adam_apply, interpret=False)
    text = _compiled_text(lambda p, g, m, v, s: fn(p, g, m, v, scale=s),
                          tree, tree, tree, tree, scale)
    assert "tpu_custom_call" in text
