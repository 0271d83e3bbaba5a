"""Paged KV-cache subsystem (serve/paging.py + kv_cache paged kernels):
allocator free-list/refcount/reservation invariants, loud exhaustion and
budget errors, paged-vs-contiguous numerical equivalence (allclose logits
AND bit-identical greedy streams), prefix-cache hits with copy-on-write
divergence, free-page-headroom admission (FIFO deferral instead of
deadlock), host pointer-swap compaction, observe metrics, the
shardcheck baseline pins for the paged entry points, int8 quantized
pools (sizing ratio, stream parity, COW scale rows, quant-error
metric), and ragged single-program decode (parity with the bucketed
engine, one compiled program, no steady-state retrace).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.observe import metrics
from tpu_dist.serve import kv_cache, paging
from tpu_dist.serve.engine import ServeEngine
from tpu_dist.serve.paging import (PageAllocator, PageExhaustedError,
                                   PagedKVState, PrefixCache)

VOCAB = 32


def _lm(seq_len=64, d_model=16, depth=2, num_heads=2):
    model = build_transformer_lm(VOCAB, seq_len, d_model=d_model,
                                 depth=depth, num_heads=num_heads)
    model.init(0)
    return model


def _workload(n, *, seed=3, lo=2, hi=14, max_new=10):
    rng = np.random.default_rng(seed)
    return [{"prompt": rng.integers(1, VOCAB,
                                    size=int(rng.integers(lo, hi))).tolist(),
             "max_new_tokens": int(rng.integers(3, max_new + 1))}
            for _ in range(n)]


def _drive(engine, workload):
    reqs = [engine.submit(w["prompt"], max_new_tokens=w["max_new_tokens"])
            for w in workload]
    engine.run_until_idle()
    return {r.rid: list(r.generated) for r in reqs}


class TestPageAllocator:
    def _alloc(self, num_pages=8, slots=4, max_pages=4, page_size=4):
        return PageAllocator(num_pages=num_pages, page_size=page_size,
                             slots=slots, max_pages=max_pages)

    def test_alloc_release_roundtrip(self):
        a = self._alloc()
        a.reserve_pending(3)
        a.bind_reservation(0, 3)
        pages = [a.alloc(0) for _ in range(3)]
        assert len(set(pages)) == 3
        assert a.pages_in_use == 3 and a.free_pages == 5
        assert list(a.table[0, :3]) == pages
        assert all(a.writable(p) for p in pages)
        a.release_slot(0)
        assert a.pages_in_use == 0 and a.free_pages == 8
        assert np.all(a.table == a.scratch)
        a.check()

    def test_shared_page_not_writable_until_sole_owner(self):
        a = self._alloc()
        a.bind_reservation(0, 2)
        pg = a.alloc(0)
        a.attach(1, [pg], full=True)  # second owner
        assert not a.writable(pg)
        a.release_slot(1)
        assert a.writable(pg)

    def test_cow_clones_and_releases_shared(self):
        a = self._alloc()
        a.bind_reservation(0, 1)
        pg = a.alloc(0)
        a.retain(pg)  # the prefix cache's reference
        a.attach(1, [pg], full=False)
        a.reserved[1] = 1
        src, dst = a.cow(1, 0)
        assert src == pg and dst != pg
        assert a.table[1, 0] == dst and a.writable(dst)
        assert a.refcount[pg] == 2  # slot 0 + cache; slot 1 let go
        a.check()

    def test_reservation_headroom_blocks_overcommit(self):
        a = self._alloc(num_pages=4)
        a.reserve_pending(3)
        assert a.headroom() == 1
        with pytest.raises(PageExhaustedError, match="reserved"):
            a.reserve_pending(2)

    def test_exhaustion_error_is_actionable(self):
        a = self._alloc(num_pages=2, max_pages=8)
        a.bind_reservation(0, 8)
        a.alloc(0)
        a.alloc(0)
        with pytest.raises(PageExhaustedError) as e:
            a.alloc(0)
        msg = str(e.value)
        assert "2/2 pages in use" in msg and "num_pages" in msg

    def test_swap_slots_is_pointer_swap(self):
        a = self._alloc()
        a.bind_reservation(0, 2)
        p0 = [a.alloc(0), a.alloc(0)]
        a.bind_reservation(1, 1)
        p1 = [a.alloc(1)]
        a.swap_slots(0, 1)
        assert list(a.table[1, :2]) == p0 and a.count[1] == 2
        assert list(a.table[0, :1]) == p1 and a.count[0] == 1
        a.check()


class TestBudgetGuards:
    def test_contiguous_budget_names_fitting_slots(self):
        model = _lm()
        plan = kv_cache.build_plan(model)
        per_slot = kv_cache.cache_nbytes(plan, max_batch=1, max_len=64)
        with pytest.raises(ValueError, match="fits 2 slot"):
            kv_cache.init_cache(plan, max_batch=4, max_len=64,
                                budget_bytes=per_slot * 2)
        # Within budget: allocates normally.
        c = kv_cache.init_cache(plan, max_batch=2, max_len=64,
                                budget_bytes=per_slot * 2)
        assert c["k"].shape[1] == 2

    def test_pool_budget_names_fitting_pages(self):
        model = _lm()
        plan = kv_cache.build_plan(model)
        per_page = kv_cache.page_nbytes(plan, page_size=8)
        with pytest.raises(ValueError, match="fits 3 page"):
            kv_cache.init_page_pool(plan, num_pages=8, page_size=8,
                                    budget_bytes=per_page * 4)
        pool = kv_cache.init_page_pool(plan, num_pages=3, page_size=8,
                                       budget_bytes=per_page * 4)
        assert pool["k"].shape[1] == 4  # 3 + scratch

    def test_engine_budget_paths(self):
        model = _lm()
        plan = kv_cache.build_plan(model)
        budget = kv_cache.cache_nbytes(plan, max_batch=2, max_len=64)
        with pytest.raises(ValueError, match="budget_bytes"):
            ServeEngine(model, max_batch=4, max_len=64,
                        budget_bytes=budget)
        # Paged mode sizes the pool to the same budget instead of dying.
        e = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                        page_size=8, budget_bytes=budget)
        assert e.num_pages == kv_cache.pages_for_budget(
            plan, page_size=8, budget_bytes=budget)
        # Two contiguous slots' worth of tokens, minus the scratch row
        # the pool spends on absorbing padded writes.
        assert e.num_pages == 2 * (64 // 8) - 1


class TestPagedKernelEquivalence:
    """Device-math pins: the paged kernels against the contiguous ones,
    same weights, same prompt — allclose logits, identical argmax."""

    def _reference(self, model, prompt, n):
        engine = ServeEngine(model, max_batch=4, max_len=64)
        req = engine.submit(list(prompt), max_new_tokens=n)
        engine.run_until_idle()
        return list(req.generated)

    def test_cold_paged_stream_matches_contiguous(self):
        model = _lm()
        rng = np.random.default_rng(11)
        for trial in range(3):
            prompt = rng.integers(1, VOCAB,
                                  size=int(rng.integers(3, 20))).tolist()
            want = self._reference(model, prompt, 8)
            paged = ServeEngine(model, max_batch=4, max_len=64,
                                paged=True, page_size=8)
            assert paged.generate(prompt, max_new_tokens=8) == want, trial

    def test_suffix_prefill_matches_full_prefill_logits(self):
        model = _lm()
        plan = kv_cache.build_plan(model)
        params = model.init(0)["params"]
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, VOCAB, size=11).astype(np.int32)
        padded = np.zeros(16, np.int32)
        padded[:11] = prompt

        cache = kv_cache.init_cache(plan, max_batch=1, max_len=64)
        _, want = kv_cache.prefill(plan, params, cache,
                                   jnp.asarray(padded), jnp.int32(11),
                                   jnp.int32(0))

        ps, max_pages = 4, 16
        pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=ps)
        row = np.full(max_pages, 8, np.int32)
        row[:4] = [5, 2, 7, 0]  # page ids must not leak into the math
        # Cold-fill the first 8 positions, then suffix-prefill the rest:
        # the warm pass must reproduce the full prefill's last logits.
        pool, _ = kv_cache.paged_prefill(plan, params, pool,
                                         jnp.asarray(row),
                                         jnp.asarray(padded),
                                         jnp.int32(8), jnp.int32(0))
        sfx = np.zeros(8, np.int32)
        sfx[:3] = prompt[8:11]
        pool, got = kv_cache.paged_prefill(plan, params, pool,
                                           jnp.asarray(row),
                                           jnp.asarray(sfx),
                                           jnp.int32(11), jnp.int32(8))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_copy_page_copies_all_layers(self):
        model = _lm()
        plan = kv_cache.build_plan(model)
        pool = kv_cache.init_page_pool(plan, num_pages=4, page_size=4)
        pool = {k: v + np.arange(5)[None, :, None, None]
                for k, v in pool.items()}
        out = kv_cache.copy_page(pool, jnp.int32(3), jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(out["k"][:, 1]),
                                      np.asarray(pool["k"][:, 3]))
        np.testing.assert_array_equal(np.asarray(out["v"][:, 1]),
                                      np.asarray(pool["v"][:, 3]))


class TestPrefixCache:
    def _state(self, num_pages=16, page_size=4, slots=4):
        return PagedKVState(num_pages=num_pages, page_size=page_size,
                            slots=slots, max_pages=16 // page_size + 2,
                            bytes_per_token=8)

    def test_full_chunk_hit_after_register(self):
        st = self._state()
        prompt = list(range(1, 10))  # 9 tokens: 2 full pages + tail of 1
        st.allocator.reserve_pending(3)
        st.begin(0, prompt, 10)
        st.register_prefill(0, prompt)
        pages, matched, partial = st.prefix.lookup(prompt)
        assert matched == 8 and len(pages) == 2 and not partial
        # A different prompt sharing one page-aligned chunk hits less.
        pages, matched, _ = st.prefix.lookup(prompt[:4] + [30, 30])
        assert matched == 4 and len(pages) == 1
        assert st.prefix.lookup([30] * 6)[1] == 0

    def test_partial_tail_registered_at_finish(self):
        st = self._state()
        prompt = list(range(1, 8))  # 7 tokens: 1 full page + tail of 3
        st.allocator.reserve_pending(3)
        st.begin(0, prompt, 9)
        st.register_prefill(0, prompt)
        assert st.prefix.lookup(prompt)[1] == 4  # tail not cached yet
        st.finish(0, prompt)
        pages, matched, partial = st.prefix.lookup(prompt + [29, 28])
        assert matched == 7 and partial and len(pages) == 2
        st.allocator.check()

    def test_eviction_is_leaf_first_and_frees_pages(self):
        st = self._state(num_pages=8)
        prompt = list(range(1, 9))  # 2 full pages -> chain of 2 nodes
        st.allocator.reserve_pending(2)
        st.begin(0, prompt, 8)
        st.register_prefill(0, prompt)
        st.finish(0, prompt)
        assert st.allocator.pages_in_use == 2  # cache holds both
        freed = st.prefix.evict(1)
        assert freed == 1
        # The leaf (second chunk) went first: the root chunk still hits.
        assert st.prefix.lookup(prompt)[1] == 4
        st.prefix.evict(1)
        assert st.allocator.pages_in_use == 0

    def test_engine_prefix_hit_streams_match_cold(self):
        """COW divergence: two prompts sharing a long prefix must emit
        exactly what a prefix-cache-free paged engine emits."""
        model = _lm()
        pre = np.random.default_rng(2).integers(
            1, VOCAB, size=21).tolist()  # 2 full pages + partial tail
        suffixes = ([7, 9], [7, 3], [2])  # tail-sharing + divergence
        warm = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                           page_size=8)
        cold = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                           page_size=8, prefix_caching=False)
        for sfx in suffixes:
            got = warm.generate(pre + sfx, max_new_tokens=6)
            want = cold.generate(pre + sfx, max_new_tokens=6)
            assert got == want, sfx
        assert warm._paging.prefix.hits >= 2
        warm._paging.allocator.check()

    def test_identical_prompt_reuses_whole_prefix(self):
        model = _lm()
        prompt = list(range(1, 18))
        engine = ServeEngine(model, max_batch=2, max_len=64, paged=True,
                             page_size=8)
        first = engine.generate(prompt, max_new_tokens=5)
        second = engine.generate(prompt, max_new_tokens=5)
        assert first == second
        assert engine._paging.prefix.hits == 1
        # The warm prefill padded to the minimum bucket, not the cold one.
        assert min(engine.compiled_programs()["paged_prefill"]) == 8


class TestPagedEngine:
    def test_backlog_parity_with_contiguous(self):
        model = _lm()
        workload = _workload(12)
        want = _drive(ServeEngine(model, max_batch=4, max_len=64),
                      workload)
        got = _drive(ServeEngine(model, max_batch=4, max_len=64,
                                 paged=True, page_size=8), workload)
        assert got == want

    def test_default_is_contiguous_and_unchanged(self):
        model = _lm()
        engine = ServeEngine(model, max_batch=2, max_len=64)
        assert engine.paged is False and engine._paging is None
        assert set(engine.compiled_programs()) == {"decode", "prefill"}
        assert engine.cache["k"].shape == (2, 2, 2, 64, 8)

    def test_steady_state_never_retraces(self):
        model = _lm()
        engine = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                             page_size=8)
        rng = np.random.default_rng(4)

        def burst():
            for _ in range(6):
                engine.submit(rng.integers(1, VOCAB, size=4).tolist(),
                              max_new_tokens=5)
            engine.run_until_idle()

        burst()
        first = engine.compiled_programs()
        burst()  # same shapes — nothing new may compile
        assert engine.compiled_programs() == first
        for b, fn in engine._paged_decode_fns.items():
            assert fn._cache_size() == 1, f"bucket {b}"
        for p, fn in engine._paged_prefill_fns.items():
            assert fn._cache_size() == 1, f"pad {p}"

    def test_small_pool_defers_admission_fifo(self):
        """The headroom gate: a pool far below slot capacity serves the
        whole backlog by deferring admissions, never deadlocking and
        never reordering."""
        model = _lm()
        engine = ServeEngine(model, max_batch=8, max_len=64, paged=True,
                             page_size=8, num_pages=6,
                             prefix_caching=False)
        workload = _workload(8, lo=6, hi=14, max_new=8)
        reqs = [engine.submit(w["prompt"],
                              max_new_tokens=w["max_new_tokens"])
                for w in workload]
        # 6 pages can hold at most 2-3 of these requests at once.
        engine.step()
        assert engine.scheduler.num_active < len(reqs)
        engine.run_until_idle()
        # Nobody starves and nobody deadlocks: every request runs to its
        # full token budget despite the deferrals.
        assert {r.rid for r in engine.finished
                if r.status == "done"} == {r.rid for r in reqs}
        for r in reqs:
            assert len(r.generated) == r.max_new_tokens
        engine._paging.allocator.check()
        assert engine._paging.allocator.pages_in_use == 0

    def test_submit_rejects_impossible_request_loudly(self):
        model = _lm()
        engine = ServeEngine(model, max_batch=2, max_len=64, paged=True,
                             page_size=8, num_pages=3)
        with pytest.raises(ValueError, match="pages"):
            engine.submit(list(range(1, 30)), max_new_tokens=20)

    def test_compaction_swap_is_host_only(self):
        """finish-in-the-middle triggers the scheduler's slot swap; the
        paged engine mirrors it as a page-table pointer swap and the
        survivor's stream stays correct."""
        model = _lm()
        want = ServeEngine(model, max_batch=3, max_len=64).generate(
            [5, 4, 3, 2, 1], max_new_tokens=9)
        engine = ServeEngine(model, max_batch=3, max_len=64, paged=True,
                             page_size=8)
        short = [engine.submit([i + 1, i + 2], max_new_tokens=2)
                 for i in range(2)]
        survivor = engine.submit([5, 4, 3, 2, 1], max_new_tokens=9)
        engine.run_until_idle()
        assert all(len(r.generated) == 2 for r in short)
        assert survivor.generated == want
        engine._paging.allocator.check()

    def test_page_metrics_exported(self):
        model = _lm()
        registry = metrics.get_registry()
        registry.reset()
        metrics.enable()
        try:
            engine = ServeEngine(model, max_batch=2, max_len=64,
                                 paged=True, page_size=8)
            prompt = list(range(1, 15))
            engine.generate(prompt, max_new_tokens=4)
            engine.generate(prompt, max_new_tokens=4)
            snap = registry.snapshot()
        finally:
            metrics.disable()
        assert snap["counters"]["serve.prefix.hits"] == 1
        assert snap["counters"]["serve.prefix.misses"] == 1
        assert snap["counters"]["serve.prefix.bytes_saved"] > 0
        assert "serve.pages.in_use" in snap["gauges"]
        assert "serve.pages.free" in snap["gauges"]
        skipped = snap["distributions"]["serve.prefill.skipped_tokens"]
        assert skipped["count"] == 2 and skipped["max"] > 0


class TestPagedShardcheck:
    def test_paged_entry_points_trace_clean_with_baseline(self):
        import pathlib

        from tpu_dist.analysis import baseline, jaxpr_checks

        names = ["serve.paged_prefill", "serve.paged_decode_step"]
        traced, findings = jaxpr_checks.trace_entry_points(names)
        assert not findings, [f.message for f in findings]
        assert set(traced) == set(names)
        path = (pathlib.Path(__file__).parent.parent
                / "ANALYSIS_BASELINE.json")
        base = baseline.load(str(path))
        for name in names:
            assert name in base["entries"], f"{name} missing from baseline"
            # Paged serving must stay collective-free on the default
            # strategy, exactly like the contiguous path it replaces.
            assert base["entries"][name]["total_comm_bytes"] == 0
            assert base["entries"][name]["peak_hbm_bytes"] > 0


class TestInt8KV:
    def _plan64(self):
        # key_dim 64: the fp32 scale rows amortize over the head dim and
        # the int8 page lands at ~1.89x bf16 density (the bench's gate).
        model = build_transformer_lm(VOCAB, 16, d_model=128, depth=1,
                                     num_heads=2)
        model.init(0)
        return kv_cache.build_plan(model)

    def test_page_sizing_counts_scale_rows(self):
        plan = self._plan64()
        i8 = kv_cache.page_nbytes(plan, page_size=8, dtype=jnp.int8)
        bf = kv_cache.page_nbytes(plan, page_size=8, dtype=jnp.bfloat16)
        payload = 2 * plan.num_layers * plan.num_heads * 8 * plan.key_dim
        scales = 2 * plan.num_layers * plan.num_heads * 8 * 4
        assert i8 == payload + scales
        assert bf / i8 >= 1.8  # the capacity claim, statically
        budget = 64 * bf
        # pages_for_budget spends one row of the budget on the scratch
        # page, same contract as the float pools.
        assert (kv_cache.pages_for_budget(plan, page_size=8,
                                          budget_bytes=budget,
                                          dtype=jnp.int8)
                == budget // i8 - 1)

    def test_contiguous_cache_rejects_int8(self):
        plan = self._plan64()
        with pytest.raises(ValueError, match="int8"):
            kv_cache.init_cache(plan, max_batch=2, max_len=16,
                                dtype=jnp.int8)

    def test_engine_rejects_kv_dtype_without_paged(self):
        model = _lm()
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(model, max_batch=2, max_len=64, kv_dtype="int8")

    def test_engine_rejects_unknown_kv_dtype(self):
        model = _lm()
        with pytest.raises(ValueError, match="int8"):
            ServeEngine(model, max_batch=2, max_len=64, paged=True,
                        page_size=8, kv_dtype="int4")

    def test_int8_pool_has_scale_planes_sized_like_pages(self):
        plan = self._plan64()
        pool = kv_cache.init_page_pool(plan, num_pages=4, page_size=8,
                                       dtype=jnp.int8)
        assert pool["k"].dtype == jnp.int8
        assert pool["k_scale"].dtype == jnp.float32
        # One fp32 scale per (layer, page, head, position); the payload
        # keeps a position's heads side by side in one row.
        layers, pages, ps, width = pool["k"].shape
        assert width == plan.num_heads * plan.key_dim
        assert pool["k_scale"].shape == (layers, pages, ps, plan.num_heads)
        assert pool["v_scale"].shape == pool["k_scale"].shape

    def test_int8_streams_match_fp32_paged(self):
        model = _lm()
        workload = _workload(12)
        want = _drive(ServeEngine(model, max_batch=4, max_len=64,
                                  paged=True, page_size=8), workload)
        got = _drive(ServeEngine(model, max_batch=4, max_len=64,
                                 paged=True, page_size=8,
                                 kv_dtype="int8"), workload)
        assert got == want

    def test_copy_page_carries_scale_rows(self):
        plan = self._plan64()
        pool = kv_cache.init_page_pool(plan, num_pages=4, page_size=8,
                                       dtype=jnp.int8)
        pool = dict(pool)
        for name in pool:
            marked = np.array(pool[name])
            marked[:, 0] = 7
            pool[name] = jnp.asarray(marked)
        pool = kv_cache.copy_page(pool, src=0, dst=2)
        for name in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(pool[name][:, 2]),
                                          np.asarray(pool[name][:, 0]))

    def test_prefix_hit_cow_streams_match_cold_int8(self):
        """The int8 COW path must copy payload AND scale rows: a warm
        prefix-cache engine has to emit exactly what a cache-free int8
        engine emits across tail-sharing divergent suffixes."""
        model = _lm()
        pre = np.random.default_rng(2).integers(
            1, VOCAB, size=21).tolist()  # 2 full pages + partial tail
        warm = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                           page_size=8, kv_dtype="int8")
        cold = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                           page_size=8, prefix_caching=False,
                           kv_dtype="int8")
        for sfx in ([7, 9], [7, 3], [2]):
            assert (warm.generate(pre + sfx, max_new_tokens=6)
                    == cold.generate(pre + sfx, max_new_tokens=6)), sfx
        assert warm._paging.prefix.hits >= 2
        warm._paging.allocator.check()

    def test_quant_error_metric_recorded(self):
        model = _lm()
        registry = metrics.get_registry()
        registry.reset()
        metrics.enable()
        try:
            engine = ServeEngine(model, max_batch=2, max_len=64,
                                 paged=True, page_size=8,
                                 kv_dtype="int8")
            engine.generate(list(range(1, 15)), max_new_tokens=4)
            dist = registry.distribution("serve.kv.quant_error")
            gauge = registry.gauge("serve.pages.bytes_per_slot")
            assert dist.count >= 1
            # Per-position amax scaling keeps the dequant error tiny
            # relative to these O(1) activations.
            assert 0 <= dist.max < 0.5
            assert gauge.value > 0
        finally:
            metrics.disable()


class TestRaggedDecode:
    def test_ragged_requires_paged(self):
        model = _lm()
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(model, max_batch=2, max_len=64, ragged=True)

    def test_ragged_streams_match_bucketed(self):
        model = _lm()
        workload = _workload(12)
        want = _drive(ServeEngine(model, max_batch=4, max_len=64,
                                  paged=True, page_size=8), workload)
        got = _drive(ServeEngine(model, max_batch=4, max_len=64,
                                 paged=True, page_size=8, ragged=True),
                     workload)
        assert got == want

    def test_ragged_int8_streams_match_bucketed_int8(self):
        model = _lm()
        workload = _workload(10)
        want = _drive(ServeEngine(model, max_batch=4, max_len=64,
                                  paged=True, page_size=8,
                                  kv_dtype="int8"), workload)
        got = _drive(ServeEngine(model, max_batch=4, max_len=64,
                                 paged=True, page_size=8, ragged=True,
                                 kv_dtype="int8"), workload)
        assert got == want

    def test_single_program_no_steady_state_retrace(self):
        """The pow2-retrace kill shot: ONE decode program at full
        capacity, and its jit cache must sit at exactly one entry even
        after a second backlog churns through every occupancy level."""
        model = _lm()
        engine = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                             page_size=8, ragged=True)
        _drive(engine, _workload(12))
        assert engine.compiled_programs()["paged_decode"] == [4]
        fn = engine._paged_decode_fns[4]
        assert fn._cache_size() == 1
        _drive(engine, _workload(8, seed=11))
        assert engine.compiled_programs()["paged_decode"] == [4]
        assert fn._cache_size() == 1


# -- the page-walking decode kernel (ops/paged_attention.py) -------------------

WALK_PS, WALK_MAX_LEN, WALK_SLOTS = 8, 64, 4
WALK_MAX_PAGES = WALK_MAX_LEN // WALK_PS


def _walk_state(kv_dtype, lengths, *, shared_pages=0, retired=(), seed=0):
    """A pool whose every page (scratch included) holds random K/V, page
    tables of distinct pages per slot (the first ``shared_pages`` columns
    of slots 0 and 1 the SAME pages: a shared prefix; a ``retired``
    slot's row all scratch, as the allocator leaves it), and one token
    per slot to decode at ``lengths``."""
    model = _lm(seq_len=WALK_MAX_LEN)
    plan = kv_cache.build_plan(model)
    params = model.init(0)["params"]
    rng = np.random.default_rng(seed)
    num_pages = WALK_SLOTS * WALK_MAX_PAGES
    pool = kv_cache.init_page_pool(plan, num_pages=num_pages,
                                   page_size=WALK_PS, dtype=kv_dtype)
    filled = {}
    for name, a in pool.items():
        if a.dtype == jnp.int8:
            filled[name] = rng.integers(-127, 128, a.shape).astype(np.int8)
        elif name.endswith("_scale"):
            filled[name] = rng.uniform(0.002, 0.02, a.shape).astype(
                np.float32)
        else:
            filled[name] = rng.normal(size=a.shape).astype(np.float32)
    tables = rng.permutation(num_pages).reshape(
        WALK_SLOTS, WALK_MAX_PAGES).astype(np.int32)
    tables[1, :shared_pages] = tables[0, :shared_pages]
    tables[list(retired)] = num_pages
    tokens = rng.integers(1, VOCAB, WALK_SLOTS).astype(np.int32)
    return (plan, params, {k: jnp.asarray(v) for k, v in filled.items()},
            tables, tokens, np.asarray(lengths, np.int32))


def _pages_equal(before, after, pages):
    return all(np.array_equal(np.asarray(before[n])[:, pages],
                              np.asarray(after[n])[:, pages])
               for n in before)


WALK_CASES = {
    # lengths of the four slots; the first names the case.
    "first_decode_of_an_empty_slot": dict(lengths=[0, 5, 17, 30]),
    "at_a_page_boundary": dict(lengths=[WALK_PS, 3 * WALK_PS, 1, 9]),
    "one_short_of_a_page_boundary": dict(
        lengths=[WALK_PS - 1, 4 * WALK_PS - 1, 20, 2]),
    "last_position": dict(lengths=[WALK_MAX_LEN - 1, WALK_MAX_LEN - 1, 0, 33]),
    "shared_prefix_pages": dict(lengths=[2 * WALK_PS + 3, 2 * WALK_PS + 5,
                                         7, 12], shared_pages=2),
    "inactive_and_mid_prefill_slots": dict(
        lengths=[19, 11, 26, 0], active=[True, False, True, False]),
    "bucketed": dict(lengths=[13, WALK_PS, 40, 3], bucket=2),
    # A request that ran to ``max_len`` leaves its length behind when it
    # retires; a swap can park that slot inside the bucket, its table
    # row all scratch. One key more than the row addresses.
    "bucketed_with_a_retired_slot_at_max_len": dict(
        lengths=[21, WALK_MAX_LEN, WALK_MAX_LEN, 6], bucket=4,
        retired=(1, 2)),
}


class TestPageWalkingKernel:
    @pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.float32],
                             ids=["int8", "float32"])
    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_kernel_under_the_interpreter_matches_the_xla_body(
            self, case, kv_dtype):
        spec = dict(WALK_CASES[case])
        active = spec.pop("active", None)
        bucket = spec.pop("bucket", None)
        plan, params, pool, tables, tokens, lengths = _walk_state(
            kv_dtype, **spec)
        scratch = pool["k"].shape[1] - 1
        if bucket is not None:
            def decode(walk):
                return kv_cache.paged_decode_step(
                    plan, params, dict(pool), jnp.asarray(tables),
                    jnp.asarray(tokens), jnp.asarray(lengths),
                    bucket=bucket, walk=walk)
            live = ((np.arange(WALK_SLOTS) < bucket)
                    & (tables[:, 0] != scratch))
        else:
            live = np.asarray(active if active is not None
                              else [True] * WALK_SLOTS)

            def decode(walk):
                return kv_cache.paged_decode_ragged(
                    plan, params, dict(pool), jnp.asarray(tables),
                    jnp.asarray(tokens), jnp.asarray(lengths),
                    jnp.asarray(live), walk=walk)
        # Off the TPU the kernel runs under the Pallas interpreter.
        want_pool, want = decode(False)
        got_pool, got = decode(True)
        # fp32 round-off: the kernel sums in blocks and scales the
        # scores, the XLA body scales the keys.
        rows = live[:got.shape[0]]
        np.testing.assert_allclose(np.asarray(got)[rows],
                                   np.asarray(want)[rows],
                                   rtol=2e-5, atol=2e-5)
        assert np.all(np.isfinite(np.asarray(got)))
        for name in pool:
            assert np.all(np.isfinite(np.asarray(got_pool[name])))
            # The scratch page absorbs the inactive slots' writes, which
            # the two bodies make from different garbage.
            a = np.asarray(got_pool[name])[:, :scratch]
            b = np.asarray(want_pool[name])[:, :scratch]
            if a.dtype == np.int8:
                # A value that sat on a rounding boundary may land one
                # step apart once layer 0's output differs by round-off.
                assert np.abs(a.astype(np.int32) - b).max() <= 1
            else:
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        # Only the live slots' tail pages (and scratch) were written:
        # an inactive or mid-prefill slot's real pages, and every page
        # that is not a tail, keep their bytes.
        tails = {int(tables[s, lengths[s] // WALK_PS])
                 for s in range(WALK_SLOTS) if live[s]}
        untouched = [p for p in range(scratch) if p not in tails]
        assert _pages_equal(pool, got_pool, untouched)
        for s in np.flatnonzero(~live):
            assert _pages_equal(pool, got_pool,
                                tables[s][tables[s] != scratch])

    @pytest.mark.parametrize("kv_dtype,ragged", [
        ("int8", True), (None, True), ("int8", False)],
        ids=["int8-ragged", "float32-ragged", "int8-bucketed"])
    def test_greedy_streams_are_token_identical_at_rehearsal_sizes(
            self, kv_dtype, ragged):
        """The chat cell's engine at its rehearsal sizes, decoding once
        through the XLA body and once through the interpreted kernel."""
        model = build_transformer_lm(512, 128, d_model=64, depth=2,
                                     num_heads=4, ff_dim=256)
        model.init(0)
        rng = np.random.default_rng(7)
        workload = [{"prompt": rng.integers(1, 512, int(n)).tolist(),
                     "max_new_tokens": int(m)}
                    for n, m in zip(rng.integers(8, 90, 8),
                                    rng.integers(2, 16, 8))]

        def engine():
            return ServeEngine(model, max_batch=4, paged=True, ragged=ragged,
                               kv_dtype=kv_dtype, page_size=16,
                               prefill_chunk=32)

        want = _drive(engine(), workload)
        walked = engine()
        if ragged:
            walked._paged_decode_fns[4] = walked._jit(
                "paged_decode_ragged", kv_cache.paged_decode_ragged,
                walk=True)
        else:
            for bucket in (1, 2, 4):
                walked._paged_decode_fns[bucket] = walked._jit(
                    "paged_decode", kv_cache.paged_decode_step,
                    bucket=bucket, walk=True)
        assert _drive(walked, workload) == want

    def test_the_kernel_stops_at_the_row_and_masks_the_scale_rows(self):
        """A retired slot's stale length counts one key more than its
        table row addresses: the kernel stops at the row's capacity (no
        table entry, page or scale row past it is read). And a masked
        key's scale is never multiplied in: the scratch page's scale
        rows may hold anything, and 0 * NaN is not 0."""
        from tpu_dist.ops import paged_attention as pa

        plan, _, pool, tables, _, _ = _walk_state(jnp.int8, [0] * WALK_SLOTS)
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(
            WALK_SLOTS, plan.num_heads, plan.key_dim)).astype(np.float32))
        held = np.array([WALK_MAX_LEN, 11, WALK_MAX_LEN, 1], np.int32)

        def attend(n_keys, poison):
            scales = []
            for name in ("k", "v"):
                sc = np.array(kv_cache._gather_scales(
                    pool, name, 0, jnp.asarray(tables)))     # [b, H, S]
                if poison:
                    masked = (np.arange(WALK_MAX_LEN)[None, None, :]
                              >= held[:, None, None])
                    sc[np.broadcast_to(masked, sc.shape)] = np.nan
                scales.append(jnp.asarray(sc))
            return np.asarray(pa.paged_attention(
                q, pool["k"], pool["v"], 0, jnp.asarray(tables),
                jnp.asarray(n_keys), scales=tuple(scales)))

        want = attend(held, poison=False)
        past = held + (held == WALK_MAX_LEN)        # max_len + 1 keys
        np.testing.assert_array_equal(attend(past, poison=False), want)
        np.testing.assert_array_equal(attend(held, poison=True), want)

    def test_the_compiled_kernel_takes_whole_tiles_only(self):
        from tpu_dist.ops import paged_attention as pa

        cell = jnp.zeros((1, 2, 16, 20 * 64), jnp.int8)   # the chat cell's
        assert pa.pages_per_block(64, 16) == 8
        assert pa.supported(cell, max_pages=64)
        # A row narrower than a lane tile, a page shorter than a sublane
        # tile, a table too short for a 128-key block: the XLA body.
        assert not pa.supported(jnp.zeros((1, 2, 16, 32), jnp.int8), 64)
        assert not pa.supported(jnp.zeros((1, 2, 4, 1280), jnp.int8), 64)
        assert not pa.supported(cell, max_pages=4)
        assert "key blocks of 64" in pa.decline_reason(cell, max_pages=4)
        # Off the TPU the engine's programs never hold it.
        assert not kv_cache.walks_pages({"k": cell}, 64)

    def test_a_kernel_declined_on_the_tpu_is_said_once(self, monkeypatch,
                                                       caplog):
        """The XLA body reads the cache by capacity, sixty times slower
        at the chat cell's sizes: a TPU run that lands on it says so,
        once per pool and reason (the flash kernel's idiom)."""
        import jax

        from tpu_dist.ops import paged_attention as pa

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        pa.log_declined.cache_clear()
        cell = {"k": jnp.zeros((1, 2, 16, 20 * 64), jnp.int8)}
        narrow = {"k": jnp.zeros((1, 2, 16, 32), jnp.int8)}
        with caplog.at_level("WARNING", logger=pa.logger.name):
            assert kv_cache.walks_pages(cell, 64)
            assert not caplog.records
            for _ in range(3):
                assert not kv_cache.walks_pages(narrow, 64)
            assert not kv_cache.walks_pages(cell, 64, devices=4)
        said = [r.getMessage() for r in caplog.records]
        assert len(said) == 2
        assert "(1, 2, 16, 32)" in said[0] and "multiple of 128" in said[0]
        assert "4 devices" in said[1]
        pa.log_declined.cache_clear()
