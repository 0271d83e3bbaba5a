"""The ``exaone_moe`` family on the CPU at rehearsal sizes: grouped-query
attention with window and full layers in one cache manager, against the
layer's own full-sequence form or the benchmark's plain reference
(``tpubench/reference/exaone_moe.py``), seeded random weights, float32.

Every tolerance stands beside its reason, and where a mechanism has an
edge (the window's first and last key) a CONTROL that is off by one key
must FAIL the same tolerance: a comparison that a wrong window passes pins
nothing.
"""

import dataclasses
import functools
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.models import hybrid
from tpu_dist.models.policy import policy, set_policy
from tpu_dist.observe import metrics
from tpu_dist.serve import kv_cache
from tpu_dist.serve.engine import ServeEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def family():
    spec = importlib.util.spec_from_file_location(
        "exaone_moe_for_tests", ROOT / "tpubench/reference/exaone_moe.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cfg():
    full = json.loads(
        (ROOT / "tpubench/configs/k-exaone-236b-a23b.json").read_text())
    return {**full, **full["rehearsal"]}


@pytest.fixture(autouse=True)
def float32_highest():
    before = policy()
    set_policy("float32")
    with jax.default_matmul_precision("highest"):
        yield
    set_policy(before)


# -- the layer ----------------------------------------------------------------


def test_rope_turns_half_split_pairs_and_keeps_relative_position():
    """Pair ``j`` is ``(x[j], x[j + n/2])``, turned by ``pos * theta **
    (-2j / n)``; a score of two rotated vectors depends on the distance
    between their positions alone."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    pos = jnp.array([0, 1, 2, 7, 30])
    got = hybrid.rope(x, pos, 100.0, interleaved=False)
    for j in range(4):
        ang = np.asarray(pos) * 100.0 ** (-2 * j / 8)
        a, b = np.asarray(x[:, j]), np.asarray(x[:, j + 4])
        assert np.allclose(got[:, j], a * np.cos(ang) - b * np.sin(ang),
                           atol=1e-5)
        assert np.allclose(got[:, j + 4], a * np.sin(ang) + b * np.cos(ang),
                           atol=1e-5)
    assert np.allclose(got[0], x[0])                      # position 0
    q, k = x[:1], x[1:2]
    at = lambda p, t: float(jnp.sum(
        hybrid.rope(q, jnp.array([p]), 100.0, interleaved=False)
        * hybrid.rope(k, jnp.array([t]), 100.0, interleaved=False)))
    assert abs(at(9, 4) - at(105, 100)) < 1e-4
    assert abs(at(9, 4) - at(9, 5)) > 1e-3
    # The interleaved pairing is another rotation of the same vector.
    assert float(jnp.abs(got - hybrid.rope(x, pos, 100.0)).max()) > 0.1


def _layer(window=8, heads=4, kv_heads=2, rope_theta=1e4):
    return hybrid.GroupedQueryAttention(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=16, window=window,
        rope_theta=rope_theta, epsilon=1e-5)


def _by_loop(layer, p, x):
    """The layer's equations a query at a time, in numpy float64."""
    ln, dk = x.shape[0], layer.head_dim
    q, k, v = layer.project(p, x[None], jnp.arange(ln))
    q = np.asarray(q[0], np.float64)                            # [H, L, dk]
    k = np.asarray(k[0], np.float64).reshape(ln, -1, dk)
    v = np.asarray(v[0], np.float64).reshape(ln, -1, dk)
    rep = layer.num_heads // layer.num_kv_heads
    out = np.zeros((layer.num_heads, ln, dk))
    for h in range(layer.num_heads):
        for t in range(ln):
            lo = 0 if layer.window is None else max(0, t - layer.window + 1)
            s = k[lo:t + 1, h // rep] @ q[h, t] / np.sqrt(dk)
            w = np.exp(s - s.max())
            out[h, t] = (w / w.sum()) @ v[lo:t + 1, h // rep]
    return np.asarray(layer.output(p, x[None], jnp.asarray(
        out, jnp.float32)[None])[0])


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
def test_grouped_attention_matches_a_loop_over_queries(window):
    """Query head ``h`` reads K/V head ``h // 2``; a window layer's query
    sees itself and the 7 before it."""
    layer = _layer(window=window)
    p, _, _ = layer.init(jax.random.PRNGKey(1), (40, 32))
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 32))
    got, _ = layer.apply(p, {}, x[None])
    # float32 against float64 sums of at most 40 terms.
    assert np.abs(np.asarray(got[0]) - _by_loop(layer, p, x)).max() < 2e-5


@pytest.mark.parametrize("block", [16, 64, 128])
def test_key_blocks_with_a_running_softmax_are_the_whole_row_softmax(block):
    """``attend_blocks`` walks 128 keys in blocks of 16, 64 or all at once
    and never builds a score wider than a block; float32 sums in another
    order (3e-7 measured)."""
    layer = _layer(window=None)
    p, _, _ = layer.init(jax.random.PRNGKey(3), (128, 32))
    x = jax.random.normal(jax.random.PRNGKey(4), (128, 32))
    pos = jnp.arange(128)
    q, k, v = layer.project(p, x[None], pos)
    want = layer.attend(q[0], k[0], v[0], layer.sees(pos, pos))
    fetch = lambda i: (jax.lax.dynamic_slice_in_dim(k[0], i * block, block),
                       jax.lax.dynamic_slice_in_dim(v[0], i * block, block))
    got = jax.jit(lambda n: layer.attend_blocks(q[0], pos, fetch, n, block))(
        jnp.int32(128 // block))
    assert float(jnp.abs(got - want).max()) < 2e-6
    # A chunk at positions 32..47 walks the blocks up to its own last
    # position only: the keys behind it are never fetched.
    part = layer.attend_blocks(q[0][:, 32:48], pos[32:48], fetch,
                               -(-48 // block), block)
    assert float(jnp.abs(part - want[:, 32:48]).max()) < 2e-6


# -- the window's ring by slot ---------------------------------------------------

#: One window layer served through its ring against its own full-sequence
#: form: float32 sums in another order (4e-7 measured); a window off by one
#: key on either side reads 1e-2 and more.
RING_TOL = 5e-6


def _ring_run(case, seq_len=70, chunk=32, window=8):
    """Serve ``seq_len`` positions of one window layer through its ring:
    prefill by chunks of ``chunk`` up to position 50, then decode a token
    at a time. Returns the layer's output at every position."""
    layer = _layer(window=window)
    p, _, _ = layer.init(jax.random.PRNGKey(5), (seq_len, 32))
    params = {"w": p}
    op = ("window", layer, ("w",), 0)
    plan = dataclasses.replace(
        kv_cache.build_plan(_tiny_model(window)), window=window)
    x = jax.random.normal(jax.random.PRNGKey(6), (seq_len, 32))
    pool = kv_cache.init_page_pool(plan, num_pages=4, page_size=16,
                                   dtype=jnp.float32, slots=3)
    pool = {n: pool[n][:1] for n in ("wk", "wv")}        # one window layer
    slot, other = 1, 2
    if case == "slot_reused":
        # A request that came before left its keys in every ring row.
        junk = jax.random.normal(jax.random.PRNGKey(7), (seq_len, 32))
        for start in range(0, 64, chunk):
            kv_cache._window_prefill(
                op, params, pool, jnp.int32(slot), junk[None, start:start
                                                        + chunk],
                start + jnp.arange(chunk), jnp.int32(start),
                jnp.int32(start + chunk))
    out, prefilled = [], 50
    for start in range(0, prefilled, chunk):
        length = min(start + chunk, prefilled)
        xs = jnp.zeros((chunk, 32)).at[:length - start].set(x[start:length])
        y = kv_cache._window_prefill(
            op, params, pool, jnp.int32(slot), xs[None],
            start + jnp.arange(chunk), jnp.int32(start), jnp.int32(length))
        out.append(y[0, :length - start])
    if case == "after_swap":
        # Compaction moves the request: its ring goes with it, and what
        # the other slot held comes back in its place.
        pool = kv_cache.swap_state(pool, jnp.int32(slot), jnp.int32(other))
        slot = other
    for t in range(prefilled, seq_len):
        xb = jnp.zeros((3, 1, 32)).at[slot, 0].set(x[t])
        pos = jnp.zeros((3,), jnp.int32).at[slot].set(t)
        active = jnp.zeros((3,), bool).at[slot].set(True)
        y = kv_cache._window_decode(op, params, pool, xb, pos, active)
        out.append(y[slot])
    return layer, p, x, jnp.concatenate(out)


def _tiny_model(window=8):
    cfg = dict(
        hidden_size=32, rms_norm_eps=1e-5, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window=window,
        rope_parameters={"rope_theta": 1e4},
        layer_types=["sliding_attention", "full_attention"],
        mlp_layer_types=["dense", "dense"], num_hidden_layers=2,
        first_k_dense_replace=2, intermediate_size=64, num_experts=4,
        num_experts_per_tok=2, n_group=1, topk_group=1,
        moe_intermediate_size=16, num_shared_experts=1,
        routed_scaling_factor=2.5, vocab_size=64, served_positions=128)
    return hybrid.build_exaone_moe_lm(cfg)


@pytest.mark.parametrize("case", ["chunk_edge", "after_swap", "slot_reused"])
def test_a_query_sees_exactly_its_window_through_the_ring(case):
    """A query at ``t`` sees keys ``t - 7 .. t``: in a chunk, across the
    edge of two chunks (position 32 reads 25..31 from the ring), in decode
    (position 50 reads what the last chunk left), after ``swap_slots``
    moved the ring to another slot, and in a slot whose last holder filled
    every ring row. The controls see one key more and one key fewer."""
    layer, p, x, got = _ring_run(case)
    want, _ = layer.apply(p, {}, x[None])
    assert float(jnp.abs(got - want[0]).max()) < RING_TOL
    for off_by_one in (7, 9):
        wrong, _ = dataclasses.replace(layer, window=off_by_one).apply(
            p, {}, x[None])
        gap = jnp.abs(got - wrong[0]).max(axis=-1)
        assert float(gap[20:].min()) > 100 * RING_TOL
        # The first 7 positions have no key behind the window to differ by.
        assert float(gap[:7].max()) < RING_TOL


def test_a_ring_row_holds_the_newest_position_congruent_to_it():
    held = np.asarray(kv_cache._ring_positions(jnp.array([-1, 0, 5, 8, 21]),
                                               8))
    assert (held[0] < 0).all()                     # nothing written yet
    assert held[1].tolist() == [0] + list(range(-7, 0))
    assert held[2].tolist() == [0, 1, 2, 3, 4, 5, -2, -1]
    assert held[3].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert held[4].tolist() == [16, 17, 18, 19, 20, 21, 14, 15]


# -- the plan and its pools ------------------------------------------------------


def test_plan_gives_each_layer_its_kind_and_refuses_nothing(family, cfg):
    plan = kv_cache.build_plan(family.build_program(cfg, 1))
    kinds = [op[0] for op in plan.ops if op[0] in (
        "attn", "gqa", "latent", "state", "window")]
    assert kinds == ["window", "window", "window", "gqa"] * 2
    assert [op[3] for op in plan.ops if op[0] == "window"] == list(range(6))
    assert [op[3] for op in plan.ops if op[0] == "gqa"] == [0, 1]
    assert (plan.num_layers, plan.window_layers, plan.moe_layers) == (2, 6, 7)
    assert (plan.num_heads, plan.kv_heads, plan.key_dim) == (4, 2, 16)
    assert plan.window == 16 and plan.recurrent and plan.paged_only
    assert plan.state_layers == plan.latent_layers == 0
    pool = jax.eval_shape(lambda: kv_cache.init_page_pool(
        plan, num_pages=8, page_size=16, dtype=jnp.bfloat16, slots=3))
    assert pool["k"].shape == pool["v"].shape == (2, 9, 16, 32)
    assert pool["wk"].shape == pool["wv"].shape == (6, 3, 16, 32)
    # A cached position pins a K and a V row of the K/V heads in each FULL
    # layer; a slot's rings hold ``window`` rows a window layer.
    assert kv_cache.page_nbytes(plan, page_size=16,
                                dtype=jnp.bfloat16) == 2 * 2 * 16 * 32 * 2
    assert kv_cache.window_nbytes_per_slot(
        plan, jnp.bfloat16) == 2 * 6 * 16 * 32 * 2
    assert kv_cache.state_nbytes_per_slot(plan) == 0


def test_the_plan_of_a_gpt2_model_is_what_it_was():
    from tpu_dist.models.transformer import build_transformer_lm

    plan = kv_cache.build_plan(build_transformer_lm(
        64, 32, d_model=32, depth=2, num_heads=4, ff_dim=64))
    assert (plan.num_layers, plan.num_heads, plan.key_dim) == (2, 4, 8)
    assert plan.kv_heads == 0 and plan.kv_width == 32
    assert not plan.paged_only and not plan.recurrent


@pytest.mark.parametrize("case", ["contiguous", "int8", "no_full_layer",
                                  "heads_differ"])
def test_what_the_grouped_kinds_cannot_serve_is_refused_with_a_reason(
        family, cfg, case):
    if case == "contiguous":
        with pytest.raises(ValueError, match="paged=True"):
            ServeEngine(family.build_program(cfg, 1), max_batch=2, max_len=64)
    elif case == "int8":
        with pytest.raises(ValueError, match="int8"):
            ServeEngine(family.build_program(cfg, 1), max_batch=2,
                        max_len=64, paged=True, kv_dtype="int8")
    elif case == "no_full_layer":
        only = {**cfg, "layer_types": ["sliding_attention"] * 48}
        with pytest.raises(TypeError, match="nothing to page"):
            ServeEngine(hybrid.build_exaone_moe_lm(only), max_batch=2,
                        max_len=64, paged=True)
    else:
        model = family.build_program(cfg, 1)
        block = model.layers[4]
        res = block.layers[0]
        wide = dataclasses.replace(res.main[1], num_kv_heads=4)
        layers = list(model.layers)
        layers[4] = dataclasses.replace(block, layers=(
            dataclasses.replace(res, main=(res.main[0], wide, res.main[2])),
            block.layers[1]))
        from tpu_dist.models.model import Sequential

        with pytest.raises(TypeError, match="head counts that differ"):
            kv_cache.build_plan(Sequential(layers, input_shape=(64,)))


# -- the page walk with grouped heads ------------------------------------------


def test_the_grouped_page_walk_is_the_gathered_body(family, cfg):
    """Eight query heads a K/V head, as the published model has them: the
    kernel under the interpreter (row ``h`` of the block-diagonal query in
    the columns of K/V head ``h // 8``) against the XLA body that gathers
    the table row, for slots of mixed lengths, one of them inactive."""
    wide = {**cfg, "num_attention_heads": 16, "num_key_value_heads": 2}
    model = family.build_program(wide, 2)
    plan = kv_cache.build_plan(model)
    params = model.init()["params"]
    keys = jax.random.split(jax.random.PRNGKey(8), 4)
    pool = kv_cache.init_page_pool(plan, num_pages=32, page_size=16,
                                   dtype=jnp.float32, slots=4)
    pool = {n: jax.random.normal(k, a.shape) for k, (n, a) in zip(
        keys, pool.items())}
    tables = jnp.asarray(np.arange(4 * 8).reshape(4, 8), jnp.int32)
    lengths = jnp.asarray([5, 100, 37, 64], jnp.int32)
    tokens = jnp.asarray([3, 9, 27, 81], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    run = lambda walk: jax.jit(functools.partial(
        kv_cache.paged_decode_ragged, plan, walk=walk))(
            params, dict(pool), tables, tokens, lengths, active)
    (pool_w, logits_w, counts_w), (pool_x, logits_x, counts_x) = (
        run(True), run(False))
    live = np.asarray(active)
    # The kernel's dots at ``highest`` against einsums in float32.
    assert float(jnp.abs(logits_w - logits_x)[live].max()) < 2e-5
    # What the second full layer caches lies behind the first one's output;
    # the scratch page (the last) takes the inactive slot's row, which the
    # kernel leaves at nought and the XLA body does not.
    assert all(np.allclose(pool_w[n][:, :-1], pool_x[n][:, :-1], atol=2e-5)
               for n in ("k", "v"))
    assert all(np.allclose(pool_w[n], pool_x[n], atol=2e-5)
               for n in ("wk", "wv"))
    assert np.array_equal(counts_w, counts_x)
    # Keys attended, live slots: 2 full layers the whole context, 6 window
    # layers 16 keys at most.
    n = [6, 101, 65]
    assert counts_w[6:].tolist() == [
        2 * sum(n) + 6 * sum(min(v, 16) for v in n),
        6 * sum(min(v, 16) for v in n)]


# -- the engine -----------------------------------------------------------------


def _engine(family, cfg, **kw):
    model = family.build_program(cfg, 11)
    args = dict(max_batch=4, max_len=128, paged=True, ragged=True,
                kv_dtype="fp32", page_size=16, num_pages=40,
                prefill_chunk=32)
    args.update(kw)
    return ServeEngine(model, **args)


def _record_logits(engine):
    """Every logits row a token was picked from, by request id."""
    rows: dict = {}
    pick = engine._pick

    def spy(logits):
        spy.last = np.array(logits)
        return pick(logits)

    engine._pick = spy
    record = engine.scheduler.record_token

    def recording(req, token, *, now):
        rows.setdefault(req.rid, []).append(spy.last)
        return record(req, token, now=now)

    engine.scheduler.record_token = recording
    return rows


#: Prefill by chunks of 32 and decode through rings and pages against one
#: full-sequence forward of the plain reference: float32 sums in another
#: order through 8 layers, logits of order 1 (1e-6 measured). The controls
#: below read 1e-3 and more: a window that is one key off, a router that
#: chooses in bfloat16, an expert dropped.
ENGINE_TOL = 2e-5

REQUESTS = [(40, 12), (9, 30), (70, 5), (33, 8), (12, 20), (64, 9), (5, 6),
            (90, 3)]


def _served_against(family, cfg, reqs, rows, ref_cfg=None, weights=None):
    """Widest gap between the logits the engine picked from and the
    reference's teacher-forced full forward."""
    ref_cfg = ref_cfg or cfg
    params = family.make_params(family.seed_key(11), cfg)
    forward = family.forward
    if weights is not None:
        forward = _forward_with(family, weights)
    forward = jax.jit(functools.partial(forward, cfg=ref_cfg))
    worst = 0.0
    for r in reqs:
        seq = r.prompt + r.generated
        x = np.zeros((1, 128), np.int32)
        x[0, :len(seq)] = seq
        ref = np.asarray(forward(params, jnp.asarray(x))[0])
        ref = ref[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.generated)]
        worst = max(worst, float(np.abs(np.stack(rows[r.rid]) - ref).max()))
    return worst


def _forward_with(family, change):
    """The reference's forward with every layer's weights passed through
    ``change`` (a control's planted difference)."""
    def forward(params, tokens, cfg, *, quant=None):
        real = family.layer_weights
        family.layer_weights = lambda key, kind, c: change(real(key, kind, c))
        try:
            return family.forward(params, tokens, cfg, quant=quant)
        finally:
            family.layer_weights = real
    return forward


@pytest.mark.parametrize("key_block", [512, 32], ids=["one_key_block",
                                                      "four_key_blocks"])
def test_engine_prefill_by_chunks_then_decode_matches_the_reference(
        family, cfg, key_block, monkeypatch):
    """Mixed lengths over 4 slots, more requests than slots (slots are
    swapped on retirement and reused), prompts longer than the window of
    16 and than two chunks of 32; the full layers' chunks walk the table
    row whole or in four blocks of 32 keys."""
    monkeypatch.setattr(kv_cache, "PREFILL_KEY_BLOCK", key_block)
    engine = _engine(family, cfg)
    rows = _record_logits(engine)
    swaps = []
    swap_fn = engine._swap_state_fn
    engine._swap_state_fn = lambda c, i, j: (swaps.append((int(i), int(j))),
                                             swap_fn(c, i, j))[1]
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(0, 512, size=n).tolist(),
                          max_new_tokens=new) for n, new in REQUESTS]
    engine.run_until_idle()
    assert swaps and all(r.status == "done" for r in reqs)
    assert not engine._paging.state_live.any()
    assert _served_against(family, cfg, reqs, rows) < ENGINE_TOL
    assert engine.compiled_programs()["paged_decode"] == [4]
    if key_block == 512:
        return
    # The comparison is tight enough to refuse: a window one key wider or
    # narrower, and an expert layer without its routed part.
    for window in (15, 17):
        off = _served_against(family, cfg, reqs, rows,
                              {**cfg, "sliding_window": window})
        assert off > 50 * ENGINE_TOL, (window, off)
    silent = lambda w: ({**w, "ewd": w["ewd"] * 0} if "ewd" in w else w)
    assert _served_against(family, cfg, reqs, rows,
                           weights=silent) > 50 * ENGINE_TOL


def test_the_pipelined_round_serves_what_the_round_by_round_order_serves(
        family, cfg):
    """The window plan pipelined (round n's decode dispatched before round
    n - 1's picks are read; rings swapped on the device after it) against
    the same engine read round by round: token for token, and every served
    row against the reference, with the first request stopping on EOS
    while its extra decode is in flight and later requests reusing its
    slot and ring."""
    reference = _engine(family, cfg)
    reference._pipelined = False   # the round-by-round order
    rng = np.random.default_rng(13)
    shapes = [(40, 9), (9, 12), (70, 5), (33, 8), (12, 6), (5, 6)]
    prompts = [rng.integers(0, 512, size=n).tolist() for n, _ in shapes]
    stream = reference.generate(prompts[0], max_new_tokens=9)
    eos = next(t for k, t in enumerate(stream)
               if 2 <= k <= 5 and t not in stream[:k])
    reg = metrics.get_registry()

    def serve(engine):
        swaps = []
        swap_fn = engine._swap_state_fn
        engine._swap_state_fn = lambda c, i, j: (
            swaps.append(engine._picks is not None), swap_fn(c, i, j))[1]
        reg.reset()
        metrics.enable()
        try:
            reqs = [engine.submit(p, max_new_tokens=new,
                                  eos_id=eos if k == 0 else None)
                    for k, (p, (_, new)) in enumerate(zip(prompts, shapes))]
            engine.run_until_idle()
            return reqs, dict(reg.snapshot()["counters"]), swaps
        finally:
            metrics.disable()
            reg.reset()

    want, theirs, _ = serve(reference)
    engine = _engine(family, cfg)
    rows = _record_logits(engine)
    got, ours, swaps = serve(engine)
    assert engine._pipelined and any(swaps)
    assert got[0].finish_reason == "eos"
    assert [r.generated for r in got] == [r.generated for r in want]
    assert ours["serve.decode.rows_discarded"] == 1
    assert ours["serve.decode.overlapped"] == ours["serve.decode.steps"] - 1
    assert ours["serve.decode.window_keys_read"] > 0
    assert "serve.decode.overlapped" not in theirs
    assert _served_against(family, cfg, got, rows) < ENGINE_TOL


def test_control_a_bfloat16_router_fails_the_engines_tolerance(family, cfg):
    """The router states float32: the reference with the router's product
    rounded to bfloat16 chooses other experts for some tokens and is not
    the program any more."""
    engine = _engine(family, cfg)
    rows = _record_logits(engine)
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(0, 512, size=n).tolist(),
                          max_new_tokens=new) for n, new in REQUESTS[:4]]
    engine.run_until_idle()
    route = family.route

    def low(x, router, bias, c, quant=None):
        return route(x, router, bias, c, "bf16")

    family.route = low
    try:
        assert _served_against(family, cfg, reqs, rows) > 50 * ENGINE_TOL
    finally:
        family.route = route
    assert _served_against(family, cfg, reqs, rows) < ENGINE_TOL


def test_a_window_slots_bytes_do_not_grow_with_length(family, cfg):
    """Prompts of 20 and of 110 tokens: the pages follow the length, the
    window layers' bytes a slot do not, and both counters of keys follow
    what the layers attended."""
    metrics.enable()
    try:
        seen = {}
        for n in (20, 110):
            metrics.get_registry().reset()
            engine = _engine(family, cfg)
            rng = np.random.default_rng(n)
            reqs = [engine.submit(rng.integers(0, 512, size=n).tolist(),
                                  max_new_tokens=4) for _ in range(2)]
            engine.step()
            engine.step()
            gauges = metrics.get_registry().snapshot()["gauges"]
            seen[n] = (gauges["serve.cache.window_bytes"],
                       gauges["serve.cache.window_slots_live"],
                       gauges["serve.pages.in_use"])
            engine.run_until_idle()
            assert all(r.status == "done" for r in reqs)
            counters = metrics.get_registry().snapshot()["counters"]
            # Decode steps at contexts n + 1 .. n + 3 for both requests.
            full = 2 * sum(n + i for i in (1, 2, 3))
            ring = 2 * sum(min(n + i, 16) for i in (1, 2, 3))
            assert counters["serve.decode.keys_read"] == 2 * full + 6 * ring
            assert counters["serve.decode.window_keys_read"] == 6 * ring
            # A chunk walks whole key blocks up to its end: here one block
            # is the table row (8 pages of 16), so visited == addressed.
            chunks = 2 * -(-n // 32)
            assert counters["serve.prefill.keys_addressed"] == (
                2 * 128 * chunks)
            assert counters["serve.prefill.keys_visited"] == (
                counters["serve.prefill.keys_addressed"])
            assert counters["serve.moe.assignments"] > 0
            assert "serve.prefill.scan_chunks" not in counters
            assert "serve.state.slots_visited" not in counters
            assert gauges["serve.prefix.disabled_recurrent"] == 1.0
        assert seen[20][:2] == seen[110][:2] == (
            2 * 2 * 6 * 16 * 32 * 4, 2.0)          # float32 rings, 2 slots
        assert seen[110][2] > seen[20][2]      # pages follow the length
    finally:
        metrics.disable()
        metrics.get_registry().reset()


def test_prefill_visits_key_blocks_up_to_the_chunks_last_position():
    assert kv_cache.prefill_key_block(512, 16) == 512
    assert kv_cache.prefill_key_block(8, 16) == 128       # the whole row
    assert kv_cache.prefill_keys_visited(512, 16, 300) == 512
    assert kv_cache.prefill_keys_visited(512, 16, 513) == 1024
    assert kv_cache.prefill_keys_visited(512, 16, 7000) == 7168


def test_the_new_layers_are_saved_and_loaded_layer_for_layer(family, cfg):
    from tpu_dist.models import serialize

    def flat(layers):
        for layer in layers:
            yield layer
            for name in ("layers", "main"):
                yield from flat(getattr(layer, name, ()))

    seen = set()
    for layer in flat(family.build_program(cfg, 1).layers):
        again = serialize.layer_from_config(serialize.layer_config(layer))
        assert again == layer
        seen.add(type(layer).__name__)
    assert {"GroupedQueryAttention", "ComputeCast", "RoutedExperts",
            "GatedMLP", "RMSNorm"} <= seen
