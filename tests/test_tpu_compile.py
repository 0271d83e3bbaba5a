"""The main path's Pallas kernels compile for the real chip — pinned.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described and not attached (``v5e:2x2``, the machine the
driver checks on). Interpret mode cannot see what this sees: unaligned
tiles, a kernel's fast-memory budget, a Mosaic lowering jax no longer
accepts. Nothing runs here — a pass is not a chip run.

Shapes are ``chip_smoke.py``'s: GPT-2-small attention ``[8, 12, 1024, 64]``,
and the chat cell's paged decode program (``tpubench``: GPT-2 large, 32
slots, 1024 int8 pages of 16, 64 pages a table row).
"""

import functools
import os
import re

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


# -- the chat cell's paged ragged decode program -------------------------------

#: Pool-shaped ``copy`` instructions in the parent's (PR 25) compiled
#: decode program at these shapes, all 36 layers: the int8 ``k`` and ``v``
#: (755 MB each) re-laid out on the way in and on the way out, and the
#: same for the two fp32 scale planes (47 MB each). At 2048 pages the
#: parent's count was 294 and a decode step took 5.5 s (PERF.md).
PARENT_POOL_COPIES = 8


@pytest.mark.parametrize("depth", [
    pytest.param(2, id="kernel-2-layers"),
    pytest.param(36, id="pool-copies-36-layers")])
def test_paged_ragged_decode_walks_pages_and_copies_no_pool(
        v5e_chip, monkeypatch, depth):
    from tpu_dist.models.policy import policy, set_policy
    from tpu_dist.models.transformer import build_transformer_lm
    from tpu_dist.ops import paged_attention
    from tpu_dist.serve import kv_cache

    slots, pages, page_size, max_pages = 32, 1024, 16, 64
    # The kernel asks jax for the platform, and jax says "cpu" here:
    # ask for it compiled, for the described chip.
    monkeypatch.setattr(
        paged_attention, "paged_attention",
        functools.partial(paged_attention.paged_attention, interpret=False))
    kv_cache._walked_attention.clear_cache()
    before = policy()
    set_policy("mixed_bfloat16")
    try:
        model = build_transformer_lm(50257, 1024, d_model=1280, depth=depth,
                                     num_heads=20, ff_dim=5120)
        plan = kv_cache.build_plan(model)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=v5e_chip), tree)

        params = on_chip(jax.eval_shape(lambda: model.init(0))["params"])
        pool = on_chip(jax.eval_shape(lambda: kv_cache.init_page_pool(
            plan, num_pages=pages, page_size=page_size, dtype=jnp.int8)))
        assert paged_attention.supported(pool["k"], max_pages)
        row = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=v5e_chip)
        tables = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32,
                                      sharding=v5e_chip)
        text = jax.jit(
            functools.partial(kv_cache.paged_decode_ragged, plan, walk=True),
            donate_argnums=(1,)).lower(
                params, pool, tables, row(jnp.int32), row(jnp.int32),
                row(jnp.bool_)).compile().as_text()
    finally:
        set_policy(before)
        kv_cache._walked_attention.clear_cache()
    # One kernel call a layer, and nothing dequantised by capacity.
    assert text.count("tpu_custom_call") == depth
    assert f"f32[{slots},20,{max_pages * page_size},64]" not in text
    copies = re.findall(
        r"= (?:s8|f32)\[%d,%d,[0-9,]+\]\S* copy\(" % (depth, pages + 1), text)
    assert len(copies) <= PARENT_POOL_COPIES
    # The int8 payload is never re-laid out: what stays are the scale
    # planes' (S1 b).
    assert not any(c.startswith("= s8") for c in copies)
    assert len(copies) <= 4


# -- the hybrid family's decode program at its published widths -----------------


def _grouped_kernels(text) -> int:
    """Calls of the grouped matmul kernel in a compiled program."""
    return len(re.findall(r"%grouped_matmul[.\d]* = .*tpu_custom_call", text))


def test_hybrid_decode_program_compiles_at_published_widths(
        v5e_chip, monkeypatch):
    """One whole period of the ``ling-3.0-flash`` cut (two dense layers,
    then KDA x 3, MLA, so six layers: every kind of layer), 64 slots, 8192
    latent pages: the grouped product over the experts held lowers to the
    chip's grouped matmul kernel (``ops/grouped_matmul.py``; the program
    is built as on a TPU: no chip is attached), the logits stay
    ``f32[slots, vocabulary]`` (how the trace readers find the decode
    program), the KDA layers' state is updated in place by blocks of
    slots, and the program fits beside its weights."""
    import json
    import pathlib

    from tpu_dist.models.hybrid import build_hybrid_lm
    from tpu_dist.models.policy import policy, set_policy
    from tpu_dist.ops import grouped_matmul
    from tpu_dist.serve import kv_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = json.loads(
        (root / "tpubench/configs/ling-3.0-flash.json").read_text())
    cfg = {**cfg, "num_hidden_layers": 6}
    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    slots, pages, page_size, max_pages = 64, 8192, 16, 128
    before = policy()
    set_policy("mixed_bfloat16")
    try:
        model = build_hybrid_lm(cfg)
        plan = kv_cache.build_plan(model)

        def on_chip(tree, matrices=None):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, matrices if matrices and s.ndim >= 2
                    and s.shape[-1] > 512 else s.dtype, sharding=v5e_chip),
                tree)

        # Matrices as the benchmark's family hands them: bfloat16.
        params = on_chip(jax.eval_shape(lambda: model.init(0))["params"],
                         jnp.bfloat16)
        pool = on_chip(jax.eval_shape(lambda: kv_cache.init_page_pool(
            plan, num_pages=pages, page_size=page_size, dtype=jnp.bfloat16,
            slots=slots)))
        row = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=v5e_chip)
        tables = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32,
                                      sharding=v5e_chip)
        compiled = jax.jit(
            functools.partial(kv_cache.paged_decode_ragged, plan, walk=False),
            donate_argnums=(1,)).lower(
                params, pool, tables, row(jnp.int32), row(jnp.int32),
                row(jnp.bool_)).compile()
    finally:
        set_policy(before)
    text = compiled.as_text()
    assert pool["latent"].shape == (1, pages + 1, page_size, 576)
    assert pool["state"].shape == (5, slots, 32, 128, 128)
    # Four expert layers, three grouped products each, all the kernel's.
    assert _grouped_kernels(text) == 4 * 3
    # None is XLA's ragged-dot, which sizes its row tile by the rows it is
    # handed (all 512: "512,512,256") and makes every touched expert pay it.
    assert "ragged_dot_tiling" not in text
    assert f"f32[{slots},{cfg['vocab_size']}]" in text
    # The state update: one loop a KDA layer over blocks of slots, its
    # bound the block that holds the highest decoding slot, each block cut
    # out of the donated pool and written back into it in place: the pool
    # is never copied (1.34 GB here) and no layer's slice of it is either.
    state = ",".join(map(str, pool["state"].shape))
    assert len(re.findall(r"\) while\(", text)) == plan.state_layers == 5
    assert not re.findall(r"= f32\[(?:%s|%s)\]\S* copy\(" % (
        state, state.split(",", 1)[1]), text)
    assert len(re.findall(r"dynamic-update-slice_fusion[.\d]* = f32\[%s\]"
                          % state, text)) == plan.state_layers
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("rows, depth, width", [
    (512, 2560, 768),     # the cell's decode step, gate and up
    (4096, 768, 2560),    # the cell's prefill chunk, down
    (520, 4096, 1536),    # rows padded to whole tiles, the matrix in 4 tiles
    (64, 7168, 2048),     # fewer rows than a tile, the matrix in 8 tiles
    (512, 6144, 2048),    # exaone_moe's decode step, gate and up: 8 tiles
    (4096, 2048, 6144),   # exaone_moe's prefill chunk, down: 8 tiles of 768
])
def test_grouped_matmul_kernel_compiles(v5e_chip, monkeypatch, rows, depth,
                                        width):
    """The routed experts' grouped product on a TPU: row tiles of
    ``ROW_TILE`` and a group's whole matrix a tile, or a part of its
    columns where the whole would not fit twice in fast memory."""
    from tpu_dist.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)
    text = _compiled_text(
        grouped_matmul.grouped_dot, shape((rows, depth), jnp.bfloat16),
        shape((16, depth, width), jnp.bfloat16), shape((16,), jnp.int32))
    assert _grouped_kernels(text) == 1
    assert "ragged_dot_tiling" not in text


# -- the exaone_moe family's programs at their published widths ---------------


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_exaone_programs_compile_at_published_widths(v5e_chip, monkeypatch,
                                                     program):
    """One period of the ``k-exaone-236b-a23b`` cut (layers 0-3: the dense
    layer, three window layers, one full layer), 64 slots of 8192
    positions: the decode step's full layer walks its pages through the
    kernel with 8 query heads a K/V head, the window layers read their
    rings in XLA, the expert layers' products are the grouped kernel's;
    the chunk program walks the full layer's pages in key blocks and builds
    no score over the table row."""
    import json
    import pathlib

    from tpu_dist.models.hybrid import build_exaone_moe_lm
    from tpu_dist.models.policy import policy, set_policy
    from tpu_dist.ops import grouped_matmul, paged_attention
    from tpu_dist.serve import kv_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = json.loads(
        (root / "tpubench/configs/k-exaone-236b-a23b.json").read_text())
    cfg = {**cfg, "num_hidden_layers": 4}
    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        paged_attention, "paged_attention",
        functools.partial(paged_attention.paged_attention, interpret=False))
    kv_cache._walked_attention.clear_cache()
    slots, pages, page_size, max_pages, chunk = 64, 8192, 16, 512, 512
    before = policy()
    set_policy("mixed_bfloat16")
    try:
        model = build_exaone_moe_lm(cfg)
        plan = kv_cache.build_plan(model)

        def on_chip(tree, matrices=None):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, matrices if matrices and s.ndim >= 2
                    and s.shape[-1] > 512 else s.dtype, sharding=v5e_chip),
                tree)

        # Matrices as the benchmark's family hands them: bfloat16.
        params = on_chip(jax.eval_shape(lambda: model.init(0))["params"],
                         jnp.bfloat16)
        pool = on_chip(jax.eval_shape(lambda: kv_cache.init_page_pool(
            plan, num_pages=pages, page_size=page_size, dtype=jnp.bfloat16,
            slots=slots)))
        assert pool["k"].shape == (1, pages + 1, page_size, 8 * 128)
        assert pool["wk"].shape == (3, slots, 128, 8 * 128)
        assert paged_attention.supported(pool["k"], max_pages)
        arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=v5e_chip)
        if program == "decode":
            compiled = jax.jit(
                functools.partial(kv_cache.paged_decode_ragged, plan,
                                  walk=True),
                donate_argnums=(1,)).lower(
                    params, pool, arr((slots, max_pages), jnp.int32),
                    arr((slots,), jnp.int32), arr((slots,), jnp.int32),
                    arr((slots,), jnp.bool_)).compile()
        else:
            compiled = jax.jit(
                functools.partial(kv_cache.paged_prefill, plan),
                donate_argnums=(1,)).lower(
                    params, pool, arr((max_pages,), jnp.int32),
                    arr((chunk,), jnp.int32), arr((), jnp.int32),
                    arr((), jnp.int32), arr((), jnp.int32)).compile()
    finally:
        set_policy(before)
        kv_cache._walked_attention.clear_cache()
    text = compiled.as_text()
    # Three expert layers, three grouped products each.
    assert _grouped_kernels(text) == 3 * 3
    assert "ragged_dot_tiling" not in text
    if program == "decode":
        # One page walk (the full layer), and no slot's table row gathered.
        assert text.count("tpu_custom_call") == 3 * 3 + 1
        assert f"[{slots},{max_pages * page_size},1024]" not in text
        assert f"f32[{slots},{cfg['vocab_size']}]" in text
    else:
        # Scores a key block wide, never the table row's 8192 positions.
        assert f",{chunk},{max_pages * page_size}]" not in text
        assert f",{chunk},{kv_cache.PREFILL_KEY_BLOCK}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
