"""tpu_dist.serve tests: KV-cache numerical equivalence with the full
forward pass (dense AND flash-interpret prefill), scheduler invariants
(FIFO admission, bucket selection, cohort semantics, deadline eviction,
no starvation), engine end-to-end correctness under continuous batching
with slot compaction, the no-retrace compiled-program contract, the
Trainer.predict single-program fix, and the CLI/bench entrypoints.

Timing-free on purpose: deadlines run on an injected fake clock, and
correctness asserts token streams against full-forward greedy
references, never wall-clock values.
"""

import functools
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.ops.flash_attention import flash_attention
from tpu_dist.serve import kv_cache
from tpu_dist.serve.engine import ServeEngine
from tpu_dist.serve.scheduler import Request, Scheduler, default_buckets

VOCAB = 32


def _lm(seq_len=32, d_model=16, depth=2, num_heads=2):
    model = build_transformer_lm(VOCAB, seq_len, d_model=d_model,
                                 depth=depth, num_heads=num_heads)
    variables = model.init(0)
    return model, variables


def _full_logits(model, variables, tokens):
    """Training-path forward: [L] ids -> [L, vocab] fp32 logits."""
    out, _ = model.apply(variables["params"], variables["state"],
                         jnp.asarray(np.asarray(tokens, np.int32))[None])
    return np.asarray(out[0], np.float32)


def _greedy_reference(model, variables, prompt, n):
    """n greedy tokens via the full-sequence forward each step."""
    toks = list(prompt)
    logits = []
    for _ in range(n):
        lg = _full_logits(model, variables, toks)[len(toks) - 1]
        logits.append(lg)
        toks.append(int(np.argmax(lg)))
    return toks[len(prompt):], logits


class TestKVCacheEquivalence:
    # Tier-1 duration audit: ~23s of greedy full-forward reference decodes.
    # The same cache-vs-full-forward contract stays in tier-1 one level up
    # (TestServeEngine::test_continuous_batching_matches_full_forward) and
    # check.sh's serve-bench gates token-identical streams on every push.
    @pytest.mark.slow
    def test_incremental_decode_matches_full_forward(self):
        model, variables = _lm()
        plan = kv_cache.build_plan(model)
        params = variables["params"]
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, VOCAB, size=5).tolist()
        n = 8
        ref_tokens, ref_logits = _greedy_reference(model, variables,
                                                   prompt, n)

        cache = kv_cache.init_cache(plan, max_batch=4, max_len=32)
        padded = np.zeros(8, np.int32)
        padded[:5] = prompt
        slot = 2  # not slot 0: the slot index must not leak into the math
        cache, lg = kv_cache.prefill(plan, params, cache,
                                     jnp.asarray(padded), jnp.int32(5),
                                     jnp.int32(slot))
        tokens = np.zeros(4, np.int32)
        lengths = np.zeros(4, np.int32)
        got_tokens, got_logits = [], [np.asarray(lg, np.float32)]
        tokens[slot] = got = int(np.argmax(got_logits[0]))
        got_tokens.append(got)
        lengths[slot] = len(prompt)
        for _ in range(n - 1):
            cache, lg = kv_cache.decode_step(
                plan, params, cache, jnp.asarray(tokens),
                jnp.asarray(lengths), bucket=3)
            got_logits.append(np.asarray(lg[slot], np.float32))
            lengths[slot] += 1
            tokens[slot] = got = int(np.argmax(got_logits[-1]))
            got_tokens.append(got)

        assert got_tokens == ref_tokens
        for i, (a, b) in enumerate(zip(got_logits, ref_logits)):
            np.testing.assert_allclose(a, b, atol=1e-5, err_msg=f"step {i}")

    # Tier-1 duration audit: ~16s (128-pos interpret-mode flash compile).
    # Kernel-vs-dense parity stays in tier-1 in test_flash_attention.py and
    # prefill-vs-full-forward logits parity through the cache plumbing in
    # test_serve_paging.py::test_suffix_prefill_matches_full_prefill_logits.
    @pytest.mark.slow
    def test_flash_attention_prefill_matches(self):
        # interpret-mode flash needs L to be a whole tile (128): a 128-pos
        # model, prompt padded to 128. Decode then runs off the
        # flash-written cache — the TPU serving shape, on CPU.
        model, variables = _lm(seq_len=128)
        plan = kv_cache.build_plan(model)
        params = variables["params"]
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, VOCAB, size=37).tolist()
        ref_tokens, ref_logits = _greedy_reference(model, variables,
                                                   prompt, 4)

        cache = kv_cache.init_cache(plan, max_batch=2, max_len=128)
        padded = np.zeros(128, np.int32)
        padded[:len(prompt)] = prompt
        cache, lg = kv_cache.prefill(
            plan, params, cache, jnp.asarray(padded),
            jnp.int32(len(prompt)), jnp.int32(0),
            attention_fn=functools.partial(flash_attention, interpret=True))
        got_logits = [np.asarray(lg, np.float32)]
        tokens = np.zeros(2, np.int32)
        lengths = np.zeros(2, np.int32)
        tokens[0] = int(np.argmax(got_logits[0]))
        lengths[0] = len(prompt)
        got_tokens = [int(tokens[0])]
        for _ in range(3):
            cache, lg = kv_cache.decode_step(
                plan, params, cache, jnp.asarray(tokens),
                jnp.asarray(lengths), bucket=1)
            got_logits.append(np.asarray(lg[0], np.float32))
            lengths[0] += 1
            tokens[0] = int(np.argmax(got_logits[-1]))
            got_tokens.append(int(tokens[0]))
        assert got_tokens == ref_tokens
        for a, b in zip(got_logits, ref_logits):
            np.testing.assert_allclose(a, b, atol=2e-4)

    def test_swap_slots_exchanges_rows(self):
        model, _ = _lm()
        plan = kv_cache.build_plan(model)
        cache = kv_cache.init_cache(plan, max_batch=3, max_len=8)
        cache["k"] = cache["k"].at[:, 0].set(1.0).at[:, 2].set(3.0)
        out = kv_cache.swap_slots(cache, jnp.int32(0), jnp.int32(2))
        assert float(out["k"][0, 0, 0, 0, 0]) == 3.0
        assert float(out["k"][0, 2, 0, 0, 0]) == 1.0
        assert float(out["k"][0, 1, 0, 0, 0]) == 0.0

    def test_unservable_models_rejected(self):
        from tpu_dist.models.layers import Conv2D, Dense
        from tpu_dist.models.model import Sequential

        with pytest.raises(TypeError, match="no attention"):
            kv_cache.build_plan(Sequential([Dense(4)], input_shape=(4,)))
        with pytest.raises(TypeError, match="not servable"):
            kv_cache.build_plan(Sequential(
                [Conv2D(4, 3)], input_shape=(8, 8, 1)))
        moe = build_transformer_lm(VOCAB, 16, d_model=16, depth=1,
                                   num_heads=2, moe_experts=2)
        with pytest.raises(TypeError, match="not servable"):
            kv_cache.build_plan(moe)


class TestScheduler:
    def _req(self, n=1, **kw):
        return Request(prompt=[1] * n, **kw)

    def test_fifo_admission_and_bucket_selection(self):
        s = Scheduler(8)
        assert s.buckets == (1, 2, 4, 8)
        for i in range(3):
            s.submit(self._req(), now=float(i))
        admitted = s.admit()
        assert [r.rid for r in admitted] == [0, 1, 2]
        assert [r.slot for r in admitted] == [0, 1, 2]
        assert s.bucket() == 4
        s.submit(self._req(), now=3.0)
        assert s.admit()[0].slot == 3
        assert s.bucket() == 4
        s.submit(self._req(), now=4.0)
        s.admit()
        assert s.bucket() == 8

    def test_default_buckets(self):
        assert default_buckets(1) == (1,)
        assert default_buckets(6) == (1, 2, 4, 6)
        assert default_buckets(8) == (1, 2, 4, 8)

    def test_finish_compacts_with_swap(self):
        s = Scheduler(4)
        for i in range(3):
            s.submit(self._req(), now=0.0)
        r0, r1, r2 = s.admit()
        swap = s.finish(r0, now=1.0)
        assert swap == (0, 2)  # last active slot moved into the hole
        assert r2.slot == 0 and s.num_active == 2
        assert s.finish(r2, now=2.0) == (0, 1)
        assert r1.slot == 0
        assert s.finish(r1, now=3.0) is None

    def test_static_cohort_holds_bucket_and_blocks_admission(self):
        s = Scheduler(4, policy="static")
        for i in range(6):
            s.submit(self._req(), now=0.0)
        cohort = s.admit()
        assert len(cohort) == 4
        assert s.admit() == []  # no mid-cohort admission
        s.finish(cohort[0], now=1.0)
        s.finish(cohort[1], now=1.0)
        # Drained slots keep paying padded compute: bucket stays 4.
        assert s.num_active == 2 and s.bucket() == 4
        assert s.admit() == []
        for r in list(s.active()):
            s.finish(r, now=2.0)
        assert len(s.admit()) == 2  # next cohort only after full drain
        assert s.bucket() == 2

    def test_deadline_eviction_active_and_queued(self):
        s = Scheduler(2)
        a = s.submit(self._req(deadline_s=1.0), now=0.0)
        b = s.submit(self._req(deadline_s=10.0), now=0.0)
        c = s.submit(self._req(deadline_s=0.5), now=0.0)  # starves queued
        s.admit()
        assert c.status == "queued"
        evicted = s.evict_deadline(now=2.0)
        assert {r.rid for r, _ in evicted} == {a.rid, c.rid}
        assert a.status == "evicted" and a.finish_reason == "deadline"
        assert c.status == "evicted"
        assert b.status == "active" and s.num_active == 1

    def test_no_starvation_under_full_batch(self):
        # A full batch of long requests must not starve a queued short
        # one: admission is arrival-ordered and every active request
        # makes progress each round, so the queued request enters as soon
        # as ANY active one completes — and completions are bounded by
        # max_new_tokens.
        s = Scheduler(2)
        long_a = s.submit(self._req(max_new_tokens=4), now=0.0)
        long_b = s.submit(self._req(max_new_tokens=4), now=0.0)
        late = s.submit(self._req(max_new_tokens=1), now=0.1)
        s.admit()
        rounds = 0
        while late.status == "queued":
            rounds += 1
            assert rounds <= 4, "queued request starved"
            done = [r for r in s.active()
                    if s.record_token(r, 7, now=float(rounds))]
            for r in sorted(done, key=lambda r: r.slot, reverse=True):
                s.finish(r, now=float(rounds))
            s.admit()
        assert rounds == 4  # exactly when the first long request ends

    def test_record_token_eos_and_length(self):
        s = Scheduler(1)
        r = s.submit(self._req(max_new_tokens=3, eos_id=9), now=0.0)
        s.admit()
        assert not s.record_token(r, 4, now=1.0)
        assert s.record_token(r, 9, now=2.0)
        assert r.finish_reason == "eos"
        r2 = Request(prompt=[1], max_new_tokens=1)
        s.finish(r, now=2.0)
        s.submit(r2, now=3.0)
        s.admit()
        assert s.record_token(r2, 4, now=4.0)
        assert r2.finish_reason == "length"

    # -- edge cases the journal replay leans on ------------------------------

    def test_slot_reuse_immediately_after_deadline_eviction(self):
        # Replay re-admits recovered requests right after recovery evicts
        # stale ones; the freed slot must be reusable the same round.
        s = Scheduler(2)
        doomed = s.submit(self._req(deadline_s=1.0), now=0.0)
        keeper = s.submit(self._req(deadline_s=None), now=0.0)
        s.admit()
        assert doomed.slot == 0 and keeper.slot == 1
        (evict,) = s.evict_deadline(now=5.0)
        assert evict[0] is doomed and evict[1] == (0, 1)  # keeper moved down
        assert keeper.slot == 0 and s.num_active == 1
        fresh = s.submit(self._req(), now=5.0)
        (admitted,) = s.admit()
        assert admitted is fresh and fresh.slot == 1  # the freed slot
        assert s.slots[0] is keeper and s.slots[1] is fresh

    def test_queued_deadline_expiry_races_admission(self):
        # A queued request whose deadline has already passed must expire,
        # never occupy a slot — even when a slot frees in the same round.
        s = Scheduler(1)
        hog = s.submit(self._req(deadline_s=None, max_new_tokens=1),
                       now=0.0)
        stale = s.submit(self._req(deadline_s=1.0), now=0.0)
        live = s.submit(self._req(deadline_s=50.0), now=0.0)
        s.admit()
        s.record_token(hog, 7, now=2.0)
        s.finish(hog, now=2.0)  # slot frees at now=2.0 — stale is expired
        evicted = s.evict_deadline(now=2.0)
        assert [(r, sw) for r, sw in evicted] == [(stale, None)]
        assert stale.status == "evicted"
        assert stale.finish_reason == "deadline" and stale.slot == -1
        (admitted,) = s.admit()
        assert admitted is live  # FIFO skips the expired one entirely

    def test_multi_free_compaction_applies_swaps_in_slot_order(self):
        # Several slots freeing in one round: releases run highest slot
        # first, so each swap moves a slot the remaining releases no
        # longer reference. The survivor set must come out compact.
        s = Scheduler(4)
        reqs = [s.submit(self._req(), now=0.0) for _ in range(4)]
        s.admit()
        done = [reqs[0], reqs[2]]  # free slots 0 and 2 together
        swaps = [s.finish(r, now=1.0)
                 for r in sorted(done, key=lambda r: r.slot, reverse=True)]
        # Slot 2 freed first: last slot (3) moves into it; then slot 0
        # freed: new last slot (2, now holding reqs[3]) moves down.
        assert swaps == [(2, 3), (0, 2)]
        assert s.num_active == 2
        assert s.slots[0] is reqs[3] and s.slots[1] is reqs[1]
        assert {r.slot for r in s.active()} == {0, 1}
        assert reqs[0].slot == -1 and reqs[2].slot == -1

    def test_bounded_queue_and_rid_pinning(self):
        s = Scheduler(1, max_queue=1)
        s.submit(self._req(), now=0.0)
        assert s.full()
        with pytest.raises(RuntimeError):
            s.submit(self._req(), now=0.0)
        # Journal-recovered requests pin their original rid; the counter
        # jumps past it so fresh submissions never collide.
        s2 = Scheduler(2)
        pinned = s2.submit(self._req(), now=0.0, rid=7)
        fresh = s2.submit(self._req(), now=0.0)
        assert pinned.rid == 7 and fresh.rid == 8
        assert s2.reserve_rid() == 9


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestServeEngine:
    def test_continuous_batching_matches_full_forward(self):
        # More requests than slots, ragged prompts, varied budgets: every
        # request's stream must equal its full-forward greedy reference
        # even as slots compact/swap around it mid-flight.
        model, variables = _lm()
        engine = ServeEngine(model, max_batch=3, max_len=32)
        rng = np.random.default_rng(3)
        specs = [(rng.integers(0, VOCAB, size=int(rng.integers(2, 7)))
                  .tolist(), int(rng.integers(2, 9))) for _ in range(7)]
        reqs = [engine.submit(p, max_new_tokens=n) for p, n in specs]
        engine.run_until_idle()
        for req, (prompt, n) in zip(reqs, specs):
            ref, _ = _greedy_reference(model, variables, prompt, n)
            assert req.generated == ref, f"request {req.rid}"
            assert req.status == "done"

    @pytest.mark.parametrize("trained_first", [False, True])
    def test_fit_beside_an_engine_leaves_it_servable(self, trained_first):
        """The trainer donates its variables to every step. An engine made
        before the first ``fit()`` owns weights of its own making; one
        made after it serves a snapshot: either way a later ``fit()``
        deletes nothing the engine holds, and the engine goes on serving
        the weights it was made with."""
        import tpu_dist as td

        model = build_transformer_lm(VOCAB, 16, d_model=16, depth=1,
                                     num_heads=2)
        model.compile(
            loss=td.ops.SparseCategoricalCrossentropy(from_logits=True),
            optimizer="sgd")
        x = (np.arange(8 * 16).reshape(8, 16) % VOCAB).astype(np.int32)
        ds = td.data.Dataset.from_tensor_slices((x, x)).batch(8)
        if trained_first:
            model.fit(ds, epochs=1, steps_per_epoch=1, verbose=0)
        engine = ServeEngine(model, max_batch=2, max_len=16)
        if trained_first:
            # This backend donates nothing, so say it outright: no leaf
            # of the engine's is one the trainer will hand to its step.
            theirs = {id(leaf) for leaf in jax.tree_util.tree_leaves(
                model.variables["params"])}
            assert not any(id(leaf) in theirs for leaf in
                           jax.tree_util.tree_leaves(engine.params))
        first = engine.submit([1, 2, 3], max_new_tokens=4)
        engine.run_until_idle()
        model.fit(ds, epochs=1, steps_per_epoch=2, verbose=0)
        again = engine.submit([1, 2, 3], max_new_tokens=4)
        engine.run_until_idle()
        assert again.status == "done"
        assert again.generated == first.generated
        assert not any(leaf.is_deleted() for leaf in
                       jax.tree_util.tree_leaves(engine.params))

    def test_steady_state_never_retraces(self):
        model, _ = _lm()
        engine = ServeEngine(model, max_batch=4, max_len=32)
        rng = np.random.default_rng(4)

        def burst():
            for _ in range(6):
                engine.submit(rng.integers(0, VOCAB, size=4).tolist(),
                              max_new_tokens=5)
            engine.run_until_idle()

        burst()
        first = engine.compiled_programs()
        cache_sizes = {b: fn._cache_size()
                       for b, fn in engine._decode_fns.items()}
        burst()  # same shapes — nothing new may compile
        assert engine.compiled_programs() == first
        for b, fn in engine._decode_fns.items():
            assert fn._cache_size() == cache_sizes[b] == 1, f"bucket {b}"

    def test_eos_stops_generation(self):
        model, variables = _lm()
        prompt = [3, 1, 4]
        ref, _ = _greedy_reference(model, variables, prompt, 8)
        eos = ref[2]  # generation must stop at eos's FIRST occurrence
        expect = ref[:ref.index(eos) + 1]
        engine = ServeEngine(model, max_batch=2, max_len=32)
        out = engine.generate(prompt, max_new_tokens=8, eos_id=eos)
        assert out == expect and out[-1] == eos
        assert engine.finished[0].finish_reason == "eos"

    def test_deadline_eviction_frees_slot(self):
        clock = _FakeClock()
        model, _ = _lm()
        engine = ServeEngine(model, max_batch=1, max_len=32, clock=clock)
        stuck = engine.submit([1, 2], max_new_tokens=30, deadline_s=5.0)
        quick = engine.submit([3, 4], max_new_tokens=2)
        engine.step()  # admits `stuck` only (single slot)
        assert stuck.status == "active" and quick.status == "queued"
        clock.t = 6.0  # blow the deadline
        engine.run_until_idle()
        assert stuck.status == "evicted"
        assert stuck.finish_reason == "deadline"
        assert quick.status == "done" and len(quick.generated) == 2

    def test_ttft_stamped_after_first_token_readback(self):
        # The PR 12 wart: ttft_s was stamped before the async dispatch
        # resolved, so a slow device->host readback was invisible to the
        # internal metric while every client saw it. Simulate the
        # readback cost by advancing the clock inside _pick and require
        # the internal p50 to track the client-observed p50 (the time
        # the token first becomes visible after step() returns).
        clock = _FakeClock()
        model, _ = _lm()
        engine = ServeEngine(model, max_batch=2, max_len=32, clock=clock)
        orig_pick = engine._pick

        def slow_pick(logits):
            clock.t += 1.0  # device->host readback cost
            return orig_pick(logits)

        engine._pick = slow_pick
        reqs = [engine.submit([1, 2, 3], max_new_tokens=2)
                for _ in range(4)]
        client = {}
        while not engine.scheduler.idle():
            engine.step()
            for i, r in enumerate(reqs):
                if i not in client and r.generated:
                    client[i] = clock.t - r.submit_s
        internal = sorted(r.ttft_s for r in reqs)
        observed = sorted(client.values())
        internal_p50 = internal[len(internal) // 2]
        observed_p50 = observed[len(observed) // 2]
        # Internal stamps right at readback; the client can only be
        # later (other slots' readbacks in the same step), never earlier,
        # and each extra readback costs 1.0 fake second.
        assert internal_p50 <= observed_p50
        assert observed_p50 - internal_p50 <= len(reqs) * 1.0
        for r in reqs:
            assert r.ttft_s >= 1.0  # the readback itself is included

    def test_serve_metrics_recorded(self):
        from tpu_dist.observe import metrics

        model, _ = _lm()
        metrics.get_registry().reset()
        metrics.enable()
        try:
            engine = ServeEngine(model, max_batch=2, max_len=32)
            for _ in range(3):
                engine.submit([1, 2, 3], max_new_tokens=3)
            engine.run_until_idle()
            snap = metrics.get_registry().snapshot()
        finally:
            metrics.disable()
        c = snap["counters"]
        assert c["serve.requests.submitted"] == 3
        assert c["serve.requests.completed"] == 3
        assert c["serve.tokens.generated"] == 9
        assert c["serve.decode.steps"] >= 2
        d = snap["distributions"]
        assert d["serve.request.latency_s"]["count"] == 3
        assert d["serve.request.ttft_s"]["count"] == 3
        assert d["serve.batch.occupancy"]["count"] >= 2
        for k in ("p50", "p95", "p99"):
            assert k in d["serve.request.latency_s"]

    def test_saved_model_roundtrip_serves(self, tmp_path):
        from tpu_dist.models import serialize

        model, variables = _lm()
        prompt = [5, 6, 7]
        ref, _ = _greedy_reference(model, variables, prompt, 4)
        serialize.save_model(_materialized(model, variables),
                             str(tmp_path / "m"))
        engine = ServeEngine.from_saved(str(tmp_path / "m"), max_batch=2)
        assert engine.generate(prompt, max_new_tokens=4) == ref

    def test_prompt_too_long_rejected(self):
        model, _ = _lm()
        engine = ServeEngine(model, max_batch=1, max_len=8)
        with pytest.raises(ValueError, match="does not fit"):
            engine.submit(list(range(8)), max_new_tokens=1)


def _materialized(model, variables):
    """Give a freshly init()'d model a trainer holding ``variables`` so
    save_model can serialize real weights."""
    from tpu_dist.training.trainer import Trainer

    model.compile(optimizer="sgd", loss="mse")
    t = Trainer(model)
    t.ensure_variables()
    t.variables["params"] = variables["params"]
    model._trainer = t
    return model


class TestPredictSingleProgram:
    def test_ragged_batches_one_compiled_program(self):
        from tpu_dist.data import Dataset
        from tpu_dist.models import Dense, Sequential

        m = Sequential([Dense(4)], input_shape=(6,))
        m.compile(optimizer="sgd", loss="mse")
        rng = np.random.default_rng(5)
        x = rng.normal(size=(26, 6)).astype(np.float32)  # 26 = 8+8+8+2
        ds = Dataset.from_tensor_slices(
            (x, np.zeros((26, 4), np.float32))).batch(8)
        out = m.predict(ds)
        assert out.shape == (26, 4)
        # The ragged final batch (2 rows) must reuse the 8-row program.
        assert m._trainer._predict_fn._cache_size() == 1
        np.testing.assert_allclose(out, m.predict(x[:26]), atol=1e-6)


class TestServeCLI:
    def test_bench_closed_loop(self, capsys):
        from tpu_dist.serve.cli import main

        rc = main(["--bench", "--requests", "5", "--max-batch", "2",
                   "--max-len", "32", "--d-model", "16", "--depth", "1",
                   "--num-heads", "2", "--vocab", "32", "--max-new", "6",
                   "--seed", "1"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["ok"] and report["completed"] == 5
        assert report["mode"] == "closed-loop"
        assert report["throughput_tok_s"] > 0
        assert report["latency_s"]["p99"] is not None
        assert report["ttft_s"]["p50"] is not None

    def test_bench_open_loop_exports_observe(self, tmp_path, monkeypatch,
                                             capsys):
        from tpu_dist.observe.exporters import read_series
        from tpu_dist.observe.telemetry import OBSERVE_DIR_ENV
        from tpu_dist.serve.cli import main

        monkeypatch.setenv(OBSERVE_DIR_ENV, str(tmp_path))
        rc = main(["--bench", "--requests", "4", "--max-batch", "2",
                   "--max-len", "32", "--d-model", "16", "--depth", "1",
                   "--num-heads", "2", "--vocab", "32", "--max-new", "4",
                   "--arrival-rate", "200", "--seed", "2"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["mode"] == "open-loop" and report["ok"]
        series = read_series(tmp_path / "serve.jsonl")
        assert series and series[0]["kind"] == "serve_bench"
        counters = series[0]["metrics"]["counters"]
        assert counters["serve.requests.completed"] == 4
        prom = (tmp_path / "serve.prom").read_text()
        assert 'tpu_dist_serve_request_latency_s{quantile="0.99"}' in prom

    def test_demo_runs(self, capsys):
        from tpu_dist.serve.cli import main

        rc = main(["--requests", "2", "--max-batch", "2", "--max-len",
                   "32", "--d-model", "16", "--depth", "1", "--num-heads",
                   "2", "--vocab", "32", "--seed", "0"])
        assert rc == 0
        assert "req 0" in capsys.readouterr().out


class TestServeShardcheck:
    def test_entry_points_trace_clean_with_baseline(self):
        import pathlib

        from tpu_dist.analysis import baseline, jaxpr_checks

        traced, findings = jaxpr_checks.trace_entry_points(
            ["serve.prefill_step", "serve.decode_step"])
        assert not findings, [f.message for f in findings]
        assert set(traced) == {"serve.prefill_step", "serve.decode_step"}
        path = pathlib.Path(__file__).parent.parent / "ANALYSIS_BASELINE.json"
        base = baseline.load(str(path))
        for name in traced:
            assert name in base["entries"], f"{name} missing from baseline"
            # Decode/prefill must stay collective-free on the default
            # strategy: request-level parallelism only.
            assert base["entries"][name]["total_comm_bytes"] == 0
