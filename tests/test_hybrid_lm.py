"""The hybrid LM family on the CPU at rehearsal sizes: each mechanism
against a plain sequential form or the benchmark's plain reference
(``tpubench/reference/ling_hybrid.py``), seeded random weights, float32.

Every tolerance stands beside its reason, and for each place where the
configuration states float32 (the recurrent state, the softmax, the
router) a CONTROL computes that place in bfloat16 and must FAIL the same
tolerance: a comparison that a lower precision passes pins nothing.
"""

import functools
import hashlib
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
from tpu_dist.parallel.routed_experts import RoutedExperts, route
from tpu_dist.serve import kv_cache
from tpu_dist.serve.engine import ServeEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.fixture(scope="module")
def family():
    spec = importlib.util.spec_from_file_location(
        "ling_hybrid_for_tests", ROOT / "tpubench/reference/ling_hybrid.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cfg():
    full = json.loads(
        (ROOT / "tpubench/configs/ling-3.0-flash.json").read_text())
    return {**full, **full["rehearsal"]}


@pytest.fixture(autouse=True)
def float32_highest():
    before = policy()
    set_policy("float32")
    with jax.default_matmul_precision("highest"):
        yield
    set_policy(before)


# -- the delta rule ----------------------------------------------------------


def _delta_inputs(seed, ln, heads=2, dk=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (ln, heads, dk))) / dk ** 0.5
    k = unit(jax.random.normal(ks[1], (ln, heads, dk)))
    v = jax.random.normal(ks[2], (ln, heads, dk))
    # Log decays down to the configuration's bound of -5 a step: 64 such
    # steps underflow e^{G}, which the pairwise form must survive.
    g = -5.0 * jax.random.uniform(ks[3], (ln, heads, dk)) ** 2
    beta = jax.random.uniform(ks[4], (ln, heads))
    return q, k, v, g, beta


def _sequential(q, k, v, g, beta, s):
    """The recurrence as the layer's docstring writes it, token by token,
    in numpy float64."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    s = np.asarray(s, np.float64).copy()
    out = np.zeros_like(v)
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            sh = np.exp(g[t, h])[:, None] * s[h]
            kt = k[t, h]
            sh = sh - beta[t, h] * np.outer(kt, kt @ sh) \
                + beta[t, h] * np.outer(kt, v[t, h])
            s[h] = sh
            out[t, h] = sh.T @ q[t, h]
    return out, s


#: float32 sums over blocks of 64 and a triangular inverse by products
#: against a float64 loop: errors of 1e-6 on outputs of order 1.
DELTA_TOL = 2e-5


@pytest.mark.parametrize("ln", [8, 64, 200])
def test_chunked_delta_rule_matches_the_sequential_rule(ln):
    layer = hybrid.DeltaAttention(num_heads=2, head_dim=16)
    q, k, v, g, beta = _delta_inputs(ln, ln)
    s0 = jnp.zeros((2, 16, 16))
    want_o, want_s = _sequential(q, k, v, g, beta, s0)
    o, s = layer.scan(q, k, v, g, beta, s0)
    assert np.abs(np.asarray(o) - want_o).max() < DELTA_TOL
    assert np.abs(np.asarray(s) - want_s).max() < DELTA_TOL


@pytest.mark.parametrize("state_dtype,passes", [
    pytest.param(jnp.float32, True, id="float32-state"),
    pytest.param(jnp.bfloat16, False, id="control-bfloat16-state-fails")])
def test_state_carried_across_chunks_and_into_decode(state_dtype, passes):
    """Two prefill chunks (70 and 130 tokens), then five one-token steps,
    the state handed from each to the next as a cache would hold it."""
    layer = hybrid.DeltaAttention(num_heads=2, head_dim=16)
    q, k, v, g, beta = _delta_inputs(3, 205)
    want_o, want_s = _sequential(q, k, v, g, beta, jnp.zeros((2, 16, 16)))
    held = lambda s: s.astype(state_dtype).astype(jnp.float32)
    s = jnp.zeros((2, 16, 16))
    outs = []
    for a, b in ((0, 70), (70, 200)):
        o, s = layer.scan(q[a:b], k[a:b], v[a:b], g[a:b], beta[a:b], s)
        outs.append(o)
        s = held(s)
    for t in range(200, 205):
        o, s = hybrid.delta_rule_step(q[t], k[t], v[t], g[t], beta[t], s)
        outs.append(o[None])
        s = held(s)
    err = max(np.abs(np.asarray(jnp.concatenate(outs)) - want_o).max(),
              np.abs(np.asarray(s) - want_s).max())
    assert (err < DELTA_TOL) == passes, err


def test_padded_positions_leave_the_state_alone():
    layer = hybrid.DeltaAttention(num_heads=2, head_dim=16)
    q, k, v, g, beta = _delta_inputs(5, 64)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 16))
    valid = jnp.arange(64) < 40
    _, s_pad = layer.scan(q, k, v, jnp.where(valid[:, None, None], g, 0.0),
                          jnp.where(valid[:, None], beta, 0.0), s0)
    _, s_cut = layer.scan(q[:40], k[:40], v[:40], g[:40], beta[:40], s0)
    # The same 40 tokens in one block of 64 or one of 40: float32 sums in
    # another order.
    assert np.abs(np.asarray(s_pad - s_cut)).max() < DELTA_TOL


# -- the decode step's state update, by blocks of slots -----------------------


def _whole_batch_state_decode(op, params, pool, x, active):
    """``kv_cache._state_decode`` as it stood before it went by slot
    blocks (PR 33): every row of the batch read, stepped, selected back
    and written. The blocked form's reference."""
    _, layer, path, idx = op
    p = kv_cache._params_at(params, path)
    b = x.shape[0]
    qkv, beta, g = layer.project(p, x)
    tail, old = pool["conv"][idx, :b], pool["state"][idx, :b]
    qkv, window = hybrid.causal_conv(qkv, tail, p["conv"])
    q, k, v = layer.heads(qkv)
    o, s = hybrid.delta_rule_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], old)
    tail_new = window[:, 1:]
    if active is not None:
        s = jnp.where(active[:, None, None, None], s, old)
        tail_new = jnp.where(active[:, None, None], tail_new, tail)
    pool["state"] = pool["state"].at[idx, :b].set(s)
    pool["conv"] = pool["conv"].at[idx, :b].set(tail_new)
    return layer.output(p, x, o[:, None])


def _state_case(b, slots, seed=0):
    """One delta layer (the second of two in the pool), a pool of
    ``slots`` slots full of states and tails, ``b`` rows to decode."""
    layer = hybrid.DeltaAttention(num_heads=2, head_dim=16)
    params, _, _ = layer.init(jax.random.PRNGKey(seed), (b, 1, 24))
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    pool = {"state": jax.random.normal(ks[0], (2, slots, 2, 16, 16)),
            "conv": jax.random.normal(ks[1], (2, slots, 3, 96))}
    x = jax.random.normal(ks[2], (b, 1, 24))
    return ("state", layer, ("kda",), 1), {"kda": params}, pool, x


def _run_state_decode(fn, op, params, pool, x, active):
    def program(params, pool, x, active):
        pool = dict(pool)
        out = fn(op, params, pool, x, active)
        return out, pool

    return jax.jit(program)(params, pool, x, active)


#: Decoding slots of a batch of 24 (three blocks of 8) by where the
#: highest one stands, and the slots the step then visits.
ACTIVE_CASES = {
    "highest-0": ([0], 8),
    "highest-7-holes-below": ([2, 7], 8),
    "highest-8-holes-below": ([0, 3, 8], 16),
    "highest-last-holes-below": ([1, 9, 23], 24),
    "none-decodes": ([], 0),
}


@pytest.mark.parametrize("case", list(ACTIVE_CASES))
def test_the_state_update_by_slot_blocks_is_the_whole_batch_one(case):
    """Every ACTIVE slot's state, convolution tail and output equal the
    whole-batch form's bit for bit; every other slot keeps its state and
    tail; behind the last visited block nothing is computed at all (a zero
    output) and the other layer's pool rows are not touched."""
    b = 24
    live, visited = ACTIVE_CASES[case]
    active = np.zeros(b, bool)
    active[live] = True
    op, params, pool, x = _state_case(b, b)
    want, want_pool = _run_state_decode(_whole_batch_state_decode, op,
                                        params, pool, x, active)
    got, got_pool = _run_state_decode(kv_cache._state_decode, op, params,
                                      pool, x, active)
    assert kv_cache.state_slots_visited(max(live, default=-1) + 1,
                                        b) == visited
    for name in ("state", "conv"):
        before, after = np.asarray(pool[name]), np.asarray(got_pool[name])
        assert np.array_equal(after[1, live],
                              np.asarray(want_pool[name])[1, live])
        assert np.array_equal(after[1, ~active], before[1, ~active])
        assert np.array_equal(after[0], before[0])
        assert not live or not np.array_equal(after[1, live],
                                              before[1, live])
    got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(got[live], want[live])
    assert np.all(got[visited:] == 0.0)
    assert not live or np.abs(got[live]).max() > 0.0


@pytest.mark.parametrize("b, slots", [(4, 8), (12, 12), (16, 32)],
                         ids=["bucket-4-of-8", "12-in-blocks-of-4",
                              "bucket-16-of-32"])
def test_without_a_mask_the_state_update_takes_every_row(b, slots):
    """The bucketed program hands no mask: all ``b`` rows are stepped as
    before, in blocks that divide ``b``, and the slots behind the bucket
    keep what they hold."""
    op, params, pool, x = _state_case(b, slots, seed=3)
    want, want_pool = _run_state_decode(_whole_batch_state_decode, op,
                                        params, pool, x, None)
    got, got_pool = _run_state_decode(kv_cache._state_decode, op, params,
                                      pool, x, None)
    assert kv_cache.state_slots_visited(b, b) == b
    assert np.array_equal(np.asarray(got), np.asarray(want))
    for name in ("state", "conv"):
        assert np.array_equal(got_pool[name], want_pool[name])
        assert not np.array_equal(got_pool[name][1, :b], pool[name][1, :b])
        assert np.array_equal(got_pool[name][1, b:], pool[name][1, b:])


def test_a_slot_mid_prefill_keeps_its_state_through_a_decode_step(
        family, cfg, monkeypatch):
    """Slots 0 and 2 decode while slot 1 stands between two chunks of its
    prompt, inside the blocks the step visits (blocks of 2 here: slot 1
    shares its block with a decoding slot): its state, tail and latent
    rows are what its first chunk left, and its second chunk's logits are
    those of a prompt no decode step came between."""
    monkeypatch.setattr(kv_cache, "STATE_SLOT_BLOCK", 2)
    model = family.build_program(cfg, 11)
    plan = kv_cache.build_plan(model)
    params = model.init(0)["params"]
    pool = kv_cache.init_page_pool(plan, num_pages=24, page_size=16,
                                   dtype=jnp.float32, slots=4)
    tables = np.arange(24, dtype=np.int32).reshape(4, 6)
    prefill = jax.jit(functools.partial(kv_cache.paged_prefill, plan))
    decode = jax.jit(functools.partial(kv_cache.paged_decode_ragged, plan,
                                       walk=False))
    rng = np.random.default_rng(4)
    prompt = lambda n: np.pad(rng.integers(0, 512, size=n), (0, 32 - n))
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    tokens = np.zeros(4, np.int32)
    for slot in (0, 2):
        pool, logits = prefill(params, pool, i32(tables[slot]),
                               i32(prompt(20)), i32(20), i32(0), i32(slot))
        tokens[slot] = int(np.argmax(logits))
    long = rng.integers(0, 512, size=50)
    pool, _ = prefill(params, pool, i32(tables[1]), i32(long[:32]), i32(32),
                      i32(0), i32(1))
    second = (i32(tables[1]), i32(np.pad(long[32:], (0, 14))), i32(50),
              i32(32), i32(1))
    _, undisturbed = prefill(params, pool, *second)
    stepped, *_ = decode(params, pool, i32(tables), i32(tokens),
                         i32([20, 32, 20, 0]),
                         jnp.asarray([True, False, True, False]))
    for name in ("state", "conv"):
        assert np.array_equal(stepped[name][:, 1], pool[name][:, 1])
        assert np.array_equal(stepped[name][:, 3], pool[name][:, 3])
        assert not np.array_equal(stepped[name][:, 0], pool[name][:, 0])
        assert not np.array_equal(stepped[name][:, 2], pool[name][:, 2])
    _, after = prefill(params, stepped, *second)
    assert np.array_equal(np.asarray(after), np.asarray(undisturbed))


# -- latent attention ---------------------------------------------------------


def _latent_layer():
    return hybrid.LatentAttention(num_heads=4, kv_rank=32, nope_dim=16,
                                  rope_dim=8, v_dim=16, rope_theta=6e6)


#: The same sums in another order (W_kvb folded into the query and the
#: output), float32: 1e-6 on outputs of order 0.1.
LATENT_TOL = 5e-6


@pytest.mark.parametrize("probabilities,passes", [
    pytest.param(None, True, id="float32-softmax"),
    pytest.param(bf16, False, id="control-bfloat16-softmax-fails")])
def test_absorbed_decode_over_latent_rows_matches_expanded_attention(
        monkeypatch, probabilities, passes):
    layer = _latent_layer()
    p, _, _ = layer.init(jax.random.PRNGKey(0), (40, 64))
    p = {**p, "q_norm": 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), p["q_norm"].shape)}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
    want, _ = layer.apply(p, {}, x)                 # expanded, full causal
    q_nope, q_rope, latent = layer.project(p, x, jnp.arange(40))
    if probabilities is not None:
        softmax = jax.nn.softmax
        monkeypatch.setattr(
            jax.nn, "softmax",
            lambda s, axis=-1: probabilities(softmax(s, axis=axis)))
    # The last token as a decode step sees it: 40 latent rows in a row of
    # 48, the rest masked.
    rows = jnp.pad(latent, ((0, 0), (0, 8), (0, 0)))
    valid = (jnp.arange(48) < 40)[None]
    o = layer.attend_absorbed(p, q_nope[:, :, -1], q_rope[:, :, -1], rows,
                              valid)
    got = layer.output(p, x[:, -1:], o[:, None])
    err = float(jnp.abs(got[0, 0] - want[0, -1]).max())
    assert (err < LATENT_TOL) == passes, err


def test_rope_turns_interleaved_pairs_and_keeps_relative_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8))
    a = hybrid.rope(jnp.tile(x, (6, 1)), jnp.arange(6), 6e6)
    b = hybrid.rope(jnp.tile(x, (6, 1)), jnp.arange(6) + 11, 6e6)
    # Pair i keeps its norm, and q_m . k_n depends on m - n alone.
    pairs = lambda t: t.reshape(6, 4, 2)
    assert np.allclose(np.linalg.norm(pairs(a), axis=-1),
                       np.linalg.norm(pairs(jnp.tile(x, (6, 1))), axis=-1),
                       atol=1e-6)
    assert np.allclose(a[0] @ a[5], b[0] @ b[5], atol=1e-5)
    assert np.allclose(a[0], x[0], atol=1e-7)       # position 0: no turn


# -- the router and the shares -------------------------------------------------


def _route_by_loop(scores, bias, top_k, n_group, topk_group, scaling):
    scores, bias = np.asarray(scores, np.float64), np.asarray(bias)
    out = []
    per = scores.shape[1] // n_group
    for s in scores:
        biased = s + bias
        groups = sorted(range(n_group), key=lambda gi: -np.sort(
            biased[gi * per:(gi + 1) * per])[-2:].sum())[:topk_group]
        allowed = [e for gi in groups for e in range(gi * per, (gi + 1) * per)]
        chosen = sorted(allowed, key=lambda e: -biased[e])[:top_k]
        total = sum(s[e] for e in chosen)
        out.append({e: scaling * s[e] / total for e in chosen})
    return out


def _scores(seed, t=64, e=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.nn.sigmoid(jax.random.normal(ks[0], (t, e))),
            0.05 * jax.random.normal(ks[1], (e,)))


def test_router_matches_a_loop():
    scores, bias = _scores(0)
    chosen, weights = route(scores, bias, top_k=2, n_group=4, topk_group=2,
                            scaling=2.5)
    want = _route_by_loop(scores, bias, 2, 4, 2, 2.5)
    for row, w, ref in zip(np.asarray(chosen), np.asarray(weights), want):
        assert set(row.tolist()) == set(ref)
        # Normalised float32 weights: a rounding of 1e-7 on values near 1.
        assert all(abs(w[j] - ref[int(e)]) < 1e-6 for j, e in enumerate(row))
    # The bias is there to move choices: without it some tokens differ.
    unbiased, _ = route(scores, jnp.zeros_like(bias), top_k=2, n_group=4,
                        topk_group=2, scaling=2.5)
    assert (np.sort(np.asarray(unbiased)) != np.sort(np.asarray(chosen))
            ).any()


def test_control_bfloat16_scores_change_the_choice():
    scores, bias = _scores(1, t=512)
    chosen, _ = route(scores, bias, top_k=2, n_group=4, topk_group=2,
                      scaling=2.5)
    low, _ = route(bf16(scores), bf16(bias), top_k=2, n_group=4,
                   topk_group=2, scaling=2.5)
    assert (np.sort(np.asarray(low)) != np.sort(np.asarray(chosen))).any()


def _expert_layer(held, shared):
    return RoutedExperts(num_experts=16, experts_held=held, top_k=2,
                         n_group=4, topk_group=2, ff_dim=32,
                         shared_ff_dim=shared, routed_scaling=2.5)


#: Sixteen experts' float32 products summed in another order.
SHARE_TOL = 2e-5


#: The two families' expert layers at rehearsal widths: how many experts
#: the router spans, how many a chip holds, and the choice among them.
SHARES = {
    "ling-4x4-of-16": dict(
        experts=16, held=4, top_k=2, n_group=4, topk_group=2,
        reference="ling_hybrid.py", config="ling-3.0-flash.json"),
    "exaone-8x16-of-128": dict(
        experts=128, held=16, top_k=8, n_group=1, topk_group=1,
        reference="exaone_moe.py", config="k-exaone-236b-a23b.json"),
}


@pytest.mark.parametrize("shape", list(SHARES))
def test_the_shares_add_up_to_the_uncut_layer(shape):
    """The chips of a layer hold a range of its experts each: their routed
    parts, plus the shared expert counted once, are the whole layer, in the
    program and against the family's reference of the uncut layer."""
    s = SHARES[shape]
    n, held, k = s["experts"], s["held"], s["top_k"]
    layer = lambda held, shared: RoutedExperts(
        num_experts=n, experts_held=held, top_k=k, n_group=s["n_group"],
        topk_group=s["topk_group"], ff_dim=32, shared_ff_dim=shared,
        routed_scaling=2.5)
    whole = layer((0, n), 32)
    p, _, _ = whole.init(jax.random.PRNGKey(0), (24, 64))
    p["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (n,))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 24, 64))
    want, stats = whole.forward(p, x)
    assert int(stats[0]) == int(stats[1]) == 3 * 24 * k  # all are held here
    routed_only = {k_: v for k_, v in p.items() if not k_.startswith("shared")}
    total = hybrid.swiglu(x, p["shared_wg"], p["shared_wu"], p["shared_wd"])
    held_sum = 0
    for first in range(0, n, held):
        part = {**routed_only, **{k_: p[k_][first:first + held]
                                  for k_ in ("wg", "wu", "wd")}}
        y, stats = layer((first, held), 0).forward(part, x)
        total, held_sum = total + y, held_sum + int(stats[1])
    assert held_sum == 3 * 24 * k          # every assignment on one chip
    assert float(jnp.abs(total - want).max()) < SHARE_TOL
    spec = importlib.util.spec_from_file_location(
        "family_for_shares", ROOT / "tpubench/reference" / s["reference"])
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    full = json.loads((ROOT / "tpubench/configs" / s["config"]).read_text())
    ref_cfg = {**full, **full["rehearsal"], "num_experts": n,
               "num_experts_published": n, "experts_held": [0, n],
               "num_experts_per_tok": k, "moe_intermediate_size": 32,
               "n_group": s["n_group"], "topk_group": s["topk_group"]}
    w = {"router": p["router"], "bias": p["bias"], "ewg": p["wg"],
         "ewu": p["wu"], "ewd": p["wd"], "swg": p["shared_wg"],
         "swu": p["shared_wu"], "swd": p["shared_wd"]}
    ref = family._experts(x, w, ref_cfg, None)
    assert float(jnp.abs(ref - want).max()) < SHARE_TOL


def test_a_share_leaves_out_what_absent_experts_would_add(family, cfg):
    """experts_held = [8, 8) of 16: the program's share is the
    reference's share, and nothing stands in for the other half. Tokens
    that are nobody's (an empty slot, a chunk's padding) reach no routed
    expert, and the counts are of what the grouped product computes."""
    layer = _expert_layer((8, 8), 32)
    p, _, _ = layer.init(jax.random.PRNGKey(3), (24, 64))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    got, stats = layer.forward(p, x, jnp.ones((2, 24), bool).at[1].set(False))
    w = {"router": p["router"], "bias": p["bias"], "ewg": p["wg"],
         "ewu": p["wu"], "ewd": p["wd"], "swg": p["shared_wg"],
         "swu": p["shared_wu"], "swd": p["shared_wd"]}
    ref = family._experts(x, w, {**cfg, "experts_held": [8, 8]}, None)
    assert float(jnp.abs(ref[0] - got[0]).max()) < SHARE_TOL
    shared = hybrid.swiglu(x[1], p["shared_wg"], p["shared_wu"],
                           p["shared_wd"])
    assert float(jnp.abs(shared - got[1]).max()) < SHARE_TOL
    assert float(jnp.abs(ref[1] - got[1]).max()) > 100 * SHARE_TOL
    made, held, touched, fullest = (int(v) for v in stats[:4])
    assert made == 24 * 2 and 0 < held < made    # the valid row only
    assert 1 <= touched <= 8 and fullest >= held / touched
    # Every row valid: twice the tokens, and the other row's experts too.
    _, both = layer.forward(p, x)
    assert int(both[0]) == 2 * made and int(both[1]) > held


def _unblocked(layer, params, x, valid=None):
    """The routed part as one ``ragged_dot`` over ALL the sorted rows (the
    product off the TPU, and on it before the kernel): the oracle.
    Returns ``(y, the first four stats)``."""
    first, count = layer.experts_held
    flat = x.reshape(-1, x.shape[-1])
    t, k = flat.shape[0], layer.top_k
    chosen, weights = layer.choose(params, flat)
    local = chosen - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held &= valid.reshape(-1)[:, None]
    group = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    rows = flat[order // k]
    dot = lambda a, w: jax.lax.ragged_dot(a, w.astype(a.dtype), sizes)
    h = jax.nn.silu(dot(rows, params["wg"])) * dot(rows, params["wu"])
    out = dot(h, params["wd"])
    scale = jnp.where(held, weights, 0.0).reshape(-1)[order]
    out = jnp.where(scale[:, None] != 0.0, out * scale[:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    y = out[back].reshape(t, k, -1).sum(axis=1)
    made = (t if valid is None else jnp.sum(valid)) * k
    return y.reshape(x.shape), [int(made), int(jnp.sum(sizes)),
                                int(jnp.sum(sizes > 0)), int(jnp.max(sizes))]


def _first_tokens(n):
    return lambda shape: (jnp.arange(shape[0] * shape[1]) < n).reshape(shape)


#: name: (experts held, x's leading shape, which tokens are somebody's,
#: row tiles the held rows lie in). Two choices a token, 16 experts, row
#: tiles of ``ROW_TILE`` = 128 sorted rows.
TILE_CASES = {
    "no-row-held": ((0, 16), (2, 70), _first_tokens(0), 0),
    "fewer-than-a-tile": ((12, 4), (1, 100), None, 1),
    "exactly-a-tile": ((0, 16), (1, 100), _first_tokens(64), 1),
    "an-expert-astride-two-tiles": ((0, 8), (4, 100), None, 3),
    "every-assignment-held": ((0, 16), (1, 150), None, 3),
    "a-whole-row-masked": (
        (0, 16), (2, 100), lambda shape: jnp.ones(shape, bool).at[1].set(False),
        2),
}


@pytest.mark.parametrize("case", list(TILE_CASES))
def test_the_tiled_product_is_the_unblocked_one(case, monkeypatch):
    """On a TPU the grouped product is a kernel that walks row tiles of
    ``ROW_TILE`` sorted rows, and only the tiles that hold a row with an
    expert (here under the Pallas interpreter): the layer gives what one
    ``ragged_dot`` over all the rows gives, whatever the held rows number,
    and the fifth of ``stats`` counts the rows of the tiles visited."""
    from tpu_dist.ops.grouped_matmul import ROW_TILE
    from tpu_dist.parallel import routed_experts

    monkeypatch.setattr(routed_experts, "grouped_dot", functools.partial(
        routed_experts.grouped_dot, interpret=True))
    held, lead, mask, tiles = TILE_CASES[case]
    layer = _expert_layer(held, 0)
    p, _, _ = layer.init(jax.random.PRNGKey(5), (*lead, 64))
    p["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(7), (*lead, 64))
    valid = None if mask is None else mask(lead)
    want, counts = _unblocked(layer, p, x, valid)
    got, stats = jax.jit(layer.forward)(p, x, valid)
    assert float(jnp.abs(got - want).max()) <= SHARE_TOL
    assert [int(v) for v in stats[:4]] == counts
    n_rows, n_held = lead[0] * lead[1] * 2, counts[1]
    tile = ROW_TILE
    assert n_rows > tile and -(-n_held // tile) == tiles
    assert [int(v) for v in stats[4:]] == [min(tiles * tile, n_rows), n_rows]
    if case == "exactly-a-tile":
        assert n_held == tile
    if case == "every-assignment-held":
        assert n_held == n_rows and n_rows % tile   # the rows are padded
    if case == "no-row-held":
        assert not np.asarray(got).any()
    if case == "an-expert-astride-two-tiles":
        # Some expert's rows begin in one tile and end in the next.
        first, count = held
        chosen, _ = layer.choose(p, x.reshape(-1, 64))
        ends = np.cumsum(np.bincount(
            np.asarray(chosen).ravel(), minlength=16)[first:first + count])
        assert any(e % tile for e in ends[:-1])


# -- the engine ----------------------------------------------------------------


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


#: Prefill by chunks of 32 and decode through pages and state against one
#: full-sequence forward: float32 sums in another order through 8 layers,
#: logits of order 1 (6e-7 measured; a planted state leak reads 0.2-0.3).
ENGINE_TOL = 2e-5


@pytest.mark.parametrize("slot_block", [8, 2],
                         ids=["one-slot-block", "two-slot-blocks"])
def test_engine_prefill_by_chunks_then_decode_matches_the_full_forward(
        family, cfg, monkeypatch, slot_block):
    """Mixed lengths over 4 slots, more requests than slots, so slots are
    swapped on retirement and reused: a state that leaked from one request
    to the next, or stayed behind in a swap, would show in the logits.
    With slot blocks of 2 the decode step's state update stops after the
    first block whenever slots 2 and 3 do not decode."""
    monkeypatch.setattr(kv_cache, "STATE_SLOT_BLOCK", slot_block)
    engine = _engine(family, cfg)
    rows = _record_logits(engine)
    swaps = []
    swap_fn = engine._swap_state_fn
    engine._swap_state_fn = lambda c, i, j: (swaps.append((int(i), int(j))),
                                             swap_fn(c, i, j))[1]
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(0, 512, size=n).tolist(),
                          max_new_tokens=new)
            for n, new in [(40, 12), (9, 30), (70, 5), (33, 8), (12, 20),
                           (64, 9), (5, 6), (90, 3)]]
    engine.run_until_idle()
    assert swaps and all(r.status == "done" for r in reqs)
    assert not engine._paging.state_live.any()
    params = family.make_params(family.seed_key(11), cfg)
    forward = jax.jit(functools.partial(family.forward, cfg=cfg))
    worst = 0.0
    for r in reqs:
        seq = r.prompt + r.generated
        x = np.zeros((1, 128), np.int32)
        x[0, :len(seq)] = seq
        ref = np.asarray(forward(params, jnp.asarray(x))[0])
        ref = ref[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.generated)]
        worst = max(worst, np.abs(np.stack(rows[r.rid]) - ref).max())
    assert worst < ENGINE_TOL, worst
    assert engine.compiled_programs()["paged_decode"] == [4]


def test_prefix_caching_asked_for_is_served_without_reuse(family, cfg):
    """A repeated prompt under ``prefix_caching=True``: the engine says
    reuse is off and the repeat gets a fresh request's logits, never pages
    over which its state was not built."""
    metrics.enable()
    try:
        metrics.get_registry().reset()
        engine = _engine(family, cfg, prefix_caching=True)
        assert engine._paging.prefix is None
        rows = _record_logits(engine)
        prompt = np.random.default_rng(1).integers(0, 512, size=50).tolist()
        first = engine.submit(prompt, max_new_tokens=6)
        engine.run_until_idle()
        again = engine.submit(prompt, max_new_tokens=6)
        engine.run_until_idle()
        snap = metrics.get_registry().snapshot()
    finally:
        metrics.disable()
    assert snap["gauges"]["serve.prefix.disabled_recurrent"] == 1.0
    assert "serve.prefix.hits" not in snap["counters"]
    assert snap["counters"]["serve.moe.assignments"] > 0
    # The sorted rows in the grouped product's visited row tiles, of all
    # the rows the expert layers sorted in the decode steps.
    assert (0 < snap["counters"]["serve.moe.rows_multiplied"]
            <= snap["counters"]["serve.moe.rows_sorted"])
    assert snap["counters"]["serve.prefill.scan_chunks"] > 0
    assert first.generated == again.generated
    assert np.array_equal(np.stack(rows[first.rid]),
                          np.stack(rows[again.rid]))


def test_the_engine_counts_the_state_slots_its_decode_steps_visit(
        family, cfg, monkeypatch):
    """Blocks of 2 over 4 slots. One request alone decodes in slot 0: a
    block a step. Then four at once: every step's count follows from the
    mask the decode program was handed. A plan without recurrent layers
    counts neither."""
    from tpu_dist.models.transformer import build_transformer_lm

    monkeypatch.setattr(kv_cache, "STATE_SLOT_BLOCK", 2)
    assert [kv_cache.state_slots_visited(hi, 4) for hi in range(5)] == [
        0, 2, 2, 4, 4]
    assert [kv_cache.state_slots_visited(hi, 64) for hi in (1, 14, 64)] == [
        2, 14, 64]
    counters = lambda: metrics.get_registry().snapshot()["counters"]
    rng = np.random.default_rng(2)
    prompt = lambda n: rng.integers(0, 512, size=n).tolist()
    metrics.enable()
    try:
        metrics.get_registry().reset()
        engine = _engine(family, cfg)
        engine.submit(prompt(20), max_new_tokens=6)
        engine.run_until_idle()
        steps = counters()["serve.decode.steps"]
        assert steps == 5       # the first token is the prefill's
        assert counters()["serve.state.slots_visited"] == 2 * steps
        assert counters()["serve.state.slots_addressed"] == 4 * steps

        metrics.get_registry().reset()
        masks = []
        decode_fn = engine._paged_decode_fn
        engine._paged_decode_fn = lambda bucket: (
            lambda params, cache, *args: (
                masks.append(np.asarray(args[-1])),
                decode_fn(bucket)(params, cache, *args))[1])
        for n, new in [(9, 12), (40, 3), (12, 7), (5, 9)]:
            engine.submit(prompt(n), max_new_tokens=new)
        engine.run_until_idle()
        highest = [int(np.flatnonzero(m).max()) for m in masks]
        assert {0, 1} & set(highest) and {2, 3} & set(highest)
        assert counters()["serve.state.slots_visited"] == sum(
            2 if h < 2 else 4 for h in highest)
        assert counters()["serve.state.slots_addressed"] == 4 * len(masks)

        metrics.get_registry().reset()
        gpt2 = ServeEngine(
            build_transformer_lm(512, 128, d_model=64, depth=2, num_heads=4,
                                 ff_dim=256),
            max_batch=4, max_len=128, paged=True, ragged=True,
            kv_dtype="fp32", page_size=16, num_pages=40)
        gpt2.submit(prompt(20), max_new_tokens=4)
        gpt2.run_until_idle()
        assert counters()["serve.decode.steps"] == 3
        assert not [k for k in counters() if k.startswith("serve.state.")]
    finally:
        metrics.disable()
        metrics.get_registry().reset()


def _round_by_round(engine):
    """The reference order on the engine's own programs: every program's
    picks read in the round that made them, a request retired in the round
    its last token was read (a sampling engine's order)."""
    engine._pipelined = False
    return engine


def _eos_case(family, cfg, reference, seed):
    """Prompts over 4 slots with more requests than slots, the first of
    which stops on EOS with budget left: a token its round-by-round stream
    (on ``reference``) picks first at its third to sixth place."""
    rng = np.random.default_rng(seed)
    shapes = [(20, 9), (9, 12), (40, 5), (33, 8), (12, 6), (5, 6)]
    prompts = [rng.integers(0, 512, size=n).tolist() for n, _ in shapes]
    stream = reference.generate(prompts[0], max_new_tokens=9)
    reference.finished.clear()
    eos = next(t for k, t in enumerate(stream)
               if 2 <= k <= 5 and t not in stream[:k])
    return [(p, new, eos if k == 0 else None)
            for k, (p, (_, new)) in enumerate(zip(prompts, shapes))]


def _serve_counting(engine, case):
    """Serve ``case``; the requests, the counters, and whether the state
    was swapped on the device while a decode's picks were unread."""
    swaps = []
    swap_fn = engine._swap_state_fn
    engine._swap_state_fn = lambda c, i, j: (
        swaps.append(engine._picks is not None), swap_fn(c, i, j))[1]
    reg = metrics.get_registry()
    reg.reset()
    metrics.enable()
    try:
        reqs = [engine.submit(p, max_new_tokens=new, eos_id=eos)
                for p, new, eos in case]
        engine.run_until_idle()
        counters = dict(reg.snapshot()["counters"])
    finally:
        metrics.disable()
        reg.reset()
    return reqs, counters, swaps


def test_the_pipelined_round_serves_what_the_round_by_round_order_serves(
        family, cfg):
    """The hybrid plan's state swaps run on the device after the decode in
    flight. The request that reads EOS was decoded once more than needed:
    that pick is dropped, and every request after it in its slot serves
    the full forward's logits, so the state its extra decode left was not
    carried over."""
    reference = _round_by_round(_engine(family, cfg))
    case = _eos_case(family, cfg, reference, seed=12)
    want, theirs, _ = _serve_counting(reference, case)
    engine = _engine(family, cfg)
    rows = _record_logits(engine)
    got, ours, swaps = _serve_counting(engine, case)
    assert engine._pipelined and any(swaps)
    assert got[0].finish_reason == want[0].finish_reason == "eos"
    assert [r.generated for r in got] == [r.generated for r in want]
    assert ours["serve.tokens.generated"] == theirs["serve.tokens.generated"]
    assert ours["serve.decode.rows_discarded"] == 1
    assert ours["serve.decode.overlapped"] == ours["serve.decode.steps"] - 1
    assert "serve.decode.overlapped" not in theirs
    assert not engine._paging.state_live.any()
    params = family.make_params(family.seed_key(11), cfg)
    forward = jax.jit(functools.partial(family.forward, cfg=cfg))
    worst = 0.0
    for r in got:
        seq = r.prompt + r.generated
        x = np.zeros((1, 128), np.int32)
        x[0, :len(seq)] = seq
        ref = np.asarray(forward(params, jnp.asarray(x))[0])
        ref = ref[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.generated)]
        worst = max(worst, np.abs(np.stack(rows[r.rid]) - ref).max())
    assert worst < ENGINE_TOL, worst


def test_the_engine_serves_the_weights_it_is_handed(family, cfg):
    """bfloat16 matrices stay bfloat16 and are not copied: the tree the
    model's init made IS the engine's."""
    set_policy("mixed_bfloat16")
    model = family.build_program(cfg, 11)
    made = []
    init = model.init
    model.init = lambda *a, **k: (made.append(init(*a, **k)), made[-1])[1]
    engine = ServeEngine(model, max_batch=2, max_len=64, paged=True,
                         kv_dtype="bf16", num_pages=8)
    theirs = jax.tree_util.tree_leaves(made[0]["params"])
    ours = jax.tree_util.tree_leaves(engine.params)
    assert all(a is b for a, b in zip(theirs, ours))
    kernel = engine.params["block_2"]["residual_1"]["main"]["routedexperts"]
    assert kernel["wg"].dtype == jnp.bfloat16
    assert kernel["router"].dtype == jnp.float32
    assert engine.cache["state"].dtype == jnp.float32
    assert engine.cache["latent"].dtype == jnp.bfloat16


def test_the_contiguous_engine_refuses_latent_and_state_layers(family, cfg):
    with pytest.raises(ValueError, match="paged=True"):
        ServeEngine(family.build_program(cfg, 1), max_batch=2, max_len=64)
    with pytest.raises(ValueError, match="max_len"):
        ServeEngine(family.build_program(cfg, 1), max_batch=2, paged=True)


def test_plan_gives_each_attention_layer_a_cache_kind(family, cfg):
    plan = kv_cache.build_plan(family.build_program(cfg, 1))
    kinds = [op[0] for op in plan.ops if op[0] in ("attn", "latent", "state")]
    # layer_group_size 3 at rehearsal: latent attention closes each period.
    assert kinds == ["state", "state", "latent"] * 2 + ["state", "state"]
    assert (plan.latent_layers, plan.state_layers, plan.moe_layers) == (2, 6, 6)
    assert plan.latent_width == 40 and plan.recurrent and plan.num_layers == 0
    pool = jax.eval_shape(lambda: kv_cache.init_page_pool(
        plan, num_pages=8, page_size=16, dtype=jnp.bfloat16, slots=3))
    assert pool["latent"].shape == (2, 9, 16, 40)
    assert pool["state"].shape == (6, 3, 4, 16, 16)
    assert pool["conv"].shape == (6, 3, 3, 3 * 4 * 16)
    assert kv_cache.page_nbytes(plan, page_size=16,
                                dtype=jnp.bfloat16) == 2 * 16 * 40 * 2
    assert kv_cache.state_nbytes_per_slot(plan) == 6 * 4 * (
        4 * 16 * 16 + 3 * 192)


def test_a_hybrid_model_is_saved_and_loaded_layer_for_layer(family, cfg):
    """``save_model`` writes layers by class name: the new layers (and the
    expert layer beside them) resolve on the way back."""
    from tpu_dist.models import serialize

    def flat(layers):
        for layer in layers:
            yield layer
            for name in ("layers", "main"):
                yield from flat(getattr(layer, name, ()))

    model = family.build_program(cfg, 1)
    seen = set()
    for layer in flat(model.layers):
        again = serialize.layer_from_config(serialize.layer_config(layer))
        assert again == layer
        seen.add(type(layer).__name__)
    assert {"StreamEmbedding", "RMSNorm", "GatedMLP", "LatentAttention",
            "DeltaAttention", "RoutedExperts"} <= seen


# -- the programs the serve cells run -----------------------------------------

#: sha256 (first 16 hex digits) of the lowered text of the decode and the
#: prefill program the serve cells run, at a small size, as the PARENT
#: commit lowers them. ``int8`` and ``bfloat16``: the GPT-2 plan over such
#: a pool (taken at PR 27: the hybrid family's cache kinds and the one
#: positions helper left them byte for byte). ``hybrid``: the hybrid plan
#: at its rehearsal widths over a bfloat16 pool (prefill taken at PR 31's
#: parent; decode renewed at PR 34, which meant to change it: the state
#: update is a loop over slot blocks up to the highest decoding slot. Off
#: the TPU the grouped product lowers as it did; the TPU's kernel is
#: pinned in ``test_tpu_compile.py``).
#: A program that lowers to the same text has the same key in the compile
#: cache and loads the same executable. To renew after a deliberate
#: change: run ``_digest`` on the commit before it.
PARENT_DIGESTS = {
    ("int8", "decode"): "e8f1b89dcbc4d35b",
    ("int8", "prefill"): "2b1131b1fd2308b7",
    ("bfloat16", "decode"): "b7e9c46cc4e32c1a",
    ("bfloat16", "prefill"): "23f7f9cff5af7765",
    ("hybrid", "decode"): "fff8a6b7e8b07b8c",
    ("hybrid", "prefill"): "e0af96c75ae1e56c",
}


def _digest(model, dtype, program, **pool_kw):
    plan = kv_cache.build_plan(model)
    params = jax.eval_shape(lambda: model.init(0))["params"]
    pool = jax.eval_shape(lambda: kv_cache.init_page_pool(
        plan, num_pages=24, page_size=16, dtype=dtype, **pool_kw))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if program == "decode":
        lowered = jax.jit(functools.partial(
            kv_cache.paged_decode_ragged, plan, walk=False)).lower(
                params, pool, i32(4, 8), i32(4), i32(4),
                jax.ShapeDtypeStruct((4,), jnp.bool_))
    else:
        slot = (i32(),) if plan.recurrent else ()
        lowered = jax.jit(functools.partial(kv_cache.paged_prefill, plan)).lower(
            params, pool, i32(8), i32(32), i32(), i32(), *slot)
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.mark.parametrize("pinned, program", list(PARENT_DIGESTS),
                         ids=["-".join(k) for k in PARENT_DIGESTS])
def test_serve_programs_lower_as_the_parent_lowered_them(
        pinned, program, family, cfg):
    from tpu_dist.models.transformer import build_transformer_lm

    set_policy("mixed_bfloat16")
    with jax.default_matmul_precision("default"):
        if pinned == "hybrid":
            got = _digest(family.build_program(cfg, 1), jnp.bfloat16,
                          program, slots=4)
        else:
            model = build_transformer_lm(512, 128, d_model=64, depth=2,
                                         num_heads=4, ff_dim=256)
            got = _digest(model, jnp.dtype(pinned), program)
    assert got == PARENT_DIGESTS[pinned, program]
