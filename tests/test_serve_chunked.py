"""Chunked-prefill tests: the interleaved-prefill contract end to end.

The tentpole guarantee is *token parity* — splitting a prompt into
chunks interleaved with decode steps must stream bit-identically to the
whole-prompt prefill, because every chunk attends over all prior cached
positions under the same absolute-position mask. These tests pin that at
the kernel level (chunk-by-chunk logits vs one-shot prefill, contiguous
and paged), at the engine level (greedy streams across ragged backlogs,
cold and prefix-warm), and for every host-side invariant the cursor
introduces: mid-prefill slots excluded from decode, arrival-ordered
chunk draining, journal replay through the same chunked path, deadline
eviction of a half-prefilled request releasing exactly its written
pages, and the no-retrace compiled-program surface.

Timing-free like test_serve.py: deadlines use the injected fake clock,
parity is asserted on token streams, never wall-clock values.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.serve import journal as journal_lib
from tpu_dist.serve import kv_cache
from tpu_dist.serve.engine import ServeEngine

VOCAB = 32


def _lm(seq_len=48, d_model=16, depth=1, num_heads=2):
    model = build_transformer_lm(VOCAB, seq_len, d_model=d_model,
                                 depth=depth, num_heads=num_heads)
    model.init(0)
    return model


def _workload(n, *, seed=11, lo=3, hi=36, max_new=6):
    """Ragged prompts long enough that chunk=8 actually chunks."""
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


@pytest.fixture(scope="module")
def model():
    return _lm()


@pytest.fixture(scope="module")
def plain_streams(model):
    """The unchunked reference streams every parity test compares to —
    computed once; chunking must never change a single token."""
    return _drive(ServeEngine(model, max_batch=2, max_len=48),
                  _workload(6))


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestChunkKernelParity:
    def _probe(self, max_len=32):
        model = _lm(seq_len=max_len)
        variables = model.init(0)
        plan = kv_cache.build_plan(model)
        params = variables["params"]
        return plan, params, max_len

    def test_chunked_equals_whole_prefill(self):
        plan, params, max_len = self._probe()
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, VOCAB, size=20).tolist()
        chunk = 8

        cache = kv_cache.init_cache(plan, max_batch=2, max_len=max_len)
        whole = np.asarray(prompt + [0] * (max_len - len(prompt)), np.int32)
        cache, ref_logits = kv_cache.prefill(
            plan, params, cache, jnp.asarray(whole),
            jnp.int32(len(prompt)), jnp.int32(1))
        ref_k = [np.asarray(k) for k in cache["k"]]

        cache2 = kv_cache.init_cache(plan, max_batch=2, max_len=max_len)
        for start in range(0, len(prompt), chunk):
            end = min(start + chunk, len(prompt))
            toks = prompt[start:end] + [0] * (chunk - (end - start))
            cache2, logits = kv_cache.prefill_chunk_step(
                plan, params, cache2, jnp.asarray(np.asarray(toks, np.int32)),
                jnp.int32(end), jnp.int32(1), jnp.int32(start))
        np.testing.assert_array_equal(np.asarray(ref_logits),
                                      np.asarray(logits))
        for want, k in zip(ref_k, cache2["k"]):
            # Written positions bit-identical; garbage past the prompt is
            # masked out of every later attention, so it may differ.
            np.testing.assert_array_equal(
                want[1, :, :len(prompt)],
                np.asarray(k)[1, :, :len(prompt)])

    def test_paged_chunked_equals_whole_paged_prefill(self):
        plan, params, _ = self._probe()
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, VOCAB, size=20).tolist()
        chunk, page_size = 8, 4
        row = jnp.arange(8, dtype=jnp.int32)  # pages 0..7 for slot's seq

        pool = kv_cache.init_page_pool(plan, num_pages=8,
                                       page_size=page_size)
        pad = 24
        whole = np.asarray(prompt + [0] * (pad - len(prompt)), np.int32)
        pool, ref_logits = kv_cache.paged_prefill(
            plan, params, pool, row, jnp.asarray(whole),
            jnp.int32(len(prompt)), jnp.int32(0))

        pool2 = kv_cache.init_page_pool(plan, num_pages=8,
                                        page_size=page_size)
        for start in range(0, len(prompt), chunk):
            end = min(start + chunk, len(prompt))
            toks = prompt[start:end] + [0] * (chunk - (end - start))
            pool2, logits = kv_cache.paged_prefill(
                plan, params, pool2, row,
                jnp.asarray(np.asarray(toks, np.int32)),
                jnp.int32(end), jnp.int32(start))
        np.testing.assert_array_equal(np.asarray(ref_logits),
                                      np.asarray(logits))


class TestChunkedEngineParity:
    def test_contiguous_streams_match_unchunked(self, model,
                                                plain_streams):
        chunked = _drive(
            ServeEngine(model, max_batch=2, max_len=48, prefill_chunk=8),
            _workload(6))
        assert chunked == plain_streams

    def test_paged_streams_match_unchunked(self, model, plain_streams):
        paged = _drive(
            ServeEngine(model, max_batch=2, max_len=48, paged=True,
                        page_size=8, prefill_chunk=8),
            _workload(6))
        assert paged == plain_streams

    def test_prefix_warm_chunked_matches_cold(self, model):
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, VOCAB, size=30).tolist()
        cold = ServeEngine(model, max_batch=2, max_len=48).generate(
            prompt, max_new_tokens=6)
        engine = ServeEngine(model, max_batch=2, max_len=48, paged=True,
                             page_size=8, prefill_chunk=8)
        first = engine.generate(prompt, max_new_tokens=6)
        hits_before = engine._paging.prefix.hits
        again = engine.generate(prompt, max_new_tokens=6)
        assert first == cold and again == cold
        # The warm pass actually took the prefix-hit path: cached chunks
        # were skipped, not re-prefilled.
        assert engine._paging.prefix.hits > hits_before

    def test_interleave_width_preserves_parity(self, model,
                                               plain_streams):
        wide = _drive(
            ServeEngine(model, max_batch=2, max_len=48, prefill_chunk=8,
                        prefill_interleave=3),
            _workload(6))
        assert wide == plain_streams

    def test_chunk_zero_default_has_no_chunk_programs(self, model):
        engine = ServeEngine(model, max_batch=2, max_len=48)
        engine.generate([1, 2, 3], max_new_tokens=3)
        assert "prefill_chunk" not in engine.compiled_programs()

    @pytest.mark.parametrize("kwargs", [
        dict(prefill_chunk=12),           # not a power of two
        dict(prefill_chunk=4),            # below the minimum pad
        dict(prefill_chunk=-8),
        dict(max_len=40, prefill_chunk=16),  # doesn't divide max_len
        dict(prefill_chunk=8, prefill_interleave=0),
    ])
    def test_knob_validation(self, model, kwargs):
        kwargs.setdefault("max_len", 48)
        with pytest.raises(ValueError):
            ServeEngine(model, max_batch=2, **kwargs)

    def test_paged_chunk_need_not_divide_max_len(self, model):
        # The divisibility constraint guards the contiguous
        # dynamic_update_slice window; the paged scatter has no such
        # edge, so the same knob is legal there.
        engine = ServeEngine(model, max_batch=2, max_len=40, paged=True,
                             page_size=8, prefill_chunk=16)
        assert engine.prefill_chunk == 16


class TestChunkCursorInvariants:
    def test_mid_prefill_slot_excluded_from_decode(self, model):
        engine = ServeEngine(model, max_batch=2, max_len=48,
                             prefill_chunk=8)
        rng = np.random.default_rng(6)
        short = engine.submit([3, 1, 4], max_new_tokens=12)
        engine.step()  # short is fully prefilled and decoding
        assert short.generated and short.prefill_pos == len(short.prompt)
        long = engine.submit(rng.integers(1, VOCAB, size=30).tolist(),
                             max_new_tokens=4)
        seen_mid_prefill = False
        short_tokens_while_long_prefilled = 0
        for _ in range(40):
            before = len(short.generated)
            engine.step()
            if engine.scheduler.is_prefilling(long):
                seen_mid_prefill = True
                # Cursor trails the prompt; the slot length mirrors it
                # and decode never touches the slot.
                assert long.generated == []
                assert long.prefill_pos < len(long.prompt)
                assert engine._lengths[long.slot] == long.prefill_pos
                assert long not in engine.scheduler.ready()
                short_tokens_while_long_prefilled += (
                    len(short.generated) - before)
            if engine.scheduler.idle():
                break
        assert seen_mid_prefill
        # Interleaving is the point: the short request kept streaming
        # while the long prompt was still being chunked in.
        assert short_tokens_while_long_prefilled > 0
        assert long.status == "done" and short.status == "done"
        assert long.prefill_pos == len(long.prompt)

    def test_chunk_queue_drains_arrival_ordered(self, model):
        engine = ServeEngine(model, max_batch=2, max_len=48,
                             prefill_chunk=8)
        rng = np.random.default_rng(7)
        a = engine.submit(rng.integers(1, VOCAB, size=28).tolist(),
                          max_new_tokens=3)
        b = engine.submit(rng.integers(1, VOCAB, size=28).tolist(),
                          max_new_tokens=3)
        engine.step()  # admits both, advances only the queue head
        assert engine.scheduler.peek_prefill() is a
        while engine.scheduler.is_prefilling(a):
            # Starvation-free FIFO: b never receives a chunk before a's
            # prefill completes.
            assert b.prefill_pos == 0
            engine.step()
        engine.run_until_idle()
        assert a.status == "done" and b.status == "done"


class TestChunkedRecovery:
    def test_mid_chunk_crash_replay_parity(self, tmp_path, model):
        workload = _workload(5, seed=21, lo=20, hi=36, max_new=6)
        baseline = _drive(ServeEngine(model, max_batch=2, max_len=48),
                          workload)

        first = ServeEngine(model, max_batch=2, max_len=48,
                            prefill_chunk=8, journal=tmp_path / "j")
        for w in workload:
            first.submit(w["prompt"], max_new_tokens=w["max_new_tokens"])
        for _ in range(3):
            first.step()
        # With 20-40 token prompts and chunk=8, three rounds leave at
        # least one admitted request mid-prefill at the crash point.
        assert any(r.prefill_pos < len(r.prompt)
                   for r in first.scheduler.active())
        first.journal._buf.clear()  # the torn unflushed tail
        del first

        second = ServeEngine(model, max_batch=2, max_len=48,
                             prefill_chunk=8, journal=tmp_path / "j")
        assert second.last_replay is not None
        second.run_until_idle()
        second.close()

        state = journal_lib.load(tmp_path / "j" / journal_lib.JOURNAL_NAME)
        for rid, want in baseline.items():
            jr = state.requests[rid]
            assert jr.finished, f"request {rid} never finished after replay"
            assert jr.tokens == want, (
                f"request {rid} diverged after chunked recovery: "
                f"{jr.tokens} != {want}")


class TestChunkedDeadline:
    def test_deadline_expiry_mid_prefill_releases_pages(self, model):
        clock = _FakeClock()
        engine = ServeEngine(model, max_batch=1, max_len=48, paged=True,
                             page_size=8, prefill_chunk=8, clock=clock)
        rng = np.random.default_rng(9)
        prompt = rng.integers(1, VOCAB, size=30).tolist()
        stuck = engine.submit(prompt, max_new_tokens=4, deadline_s=5.0)
        engine.step()  # admit + first chunk only
        assert engine.scheduler.is_prefilling(stuck)
        assert 0 < stuck.prefill_pos < len(prompt)
        clock.t = 6.0  # blow the deadline mid-prefill
        engine.run_until_idle()
        assert stuck.status == "evicted"
        assert stuck.finish_reason == "deadline"
        alloc = engine._paging.allocator
        # Every page not retained by the prefix cache went back on the
        # free list — a half-prefilled eviction leaks nothing.
        assert alloc.pages_in_use == engine._paging.prefix.pages_held
        assert alloc.count.sum() == 0

        # And nothing garbage was registered: only pages actually written
        # (<= the cursor) may have entered the prefix cache, so an
        # identical fresh request must still stream exactly like a cold
        # engine.
        cold = ServeEngine(model, max_batch=1, max_len=48).generate(
            prompt, max_new_tokens=4)
        again = engine.generate(prompt, max_new_tokens=4)
        assert again == cold

    def test_eviction_leaves_a_chunk_in_flight_its_table_row(self, model):
        """Dispatch is asynchronous and nothing waits for a mid-prompt
        chunk: the row its program was handed must not be the allocator's
        own memory, which the eviction resets to scratch. (On the CPU
        ``jnp.asarray`` aliases a 64-byte-aligned numpy buffer; the table
        is aligned here by construction, elsewhere by chance.)"""
        clock = _FakeClock()
        engine = ServeEngine(model, max_batch=1, max_len=48, paged=True,
                             page_size=8, prefill_chunk=8, clock=clock)
        alloc = engine._paging.allocator
        raw = np.zeros(alloc.table.nbytes + 64, np.uint8)
        at = -raw.ctypes.data % 64
        aligned = raw[at:at + alloc.table.nbytes].view(
            alloc.table.dtype).reshape(alloc.table.shape)
        aligned[...] = alloc.table
        alloc.table = aligned
        handed = []
        upload = engine._upload
        engine._upload = lambda *a: handed.append(upload(*a)) or handed[-1]
        engine.submit(list(range(1, 31)), max_new_tokens=4, deadline_s=5.0)
        engine.step()  # admit + first chunk only
        row = alloc.table[0].copy()
        assert row[0] != alloc.scratch
        clock.t = 6.0
        engine.step()  # evicts: the allocator's row is all scratch now
        assert np.all(alloc.table[0] == alloc.scratch)
        np.testing.assert_array_equal(np.asarray(handed[0][0]), row)


class TestChunkedNoRetrace:
    def test_contiguous_steady_state_never_retraces(self, model):
        engine = ServeEngine(model, max_batch=2, max_len=48,
                             prefill_chunk=8)
        rng = np.random.default_rng(4)

        def burst():
            for _ in range(5):
                engine.submit(
                    rng.integers(1, VOCAB,
                                 size=int(rng.integers(3, 30))).tolist(),
                    max_new_tokens=4)
            engine.run_until_idle()

        burst()
        first = engine.compiled_programs()
        assert first["prefill_chunk"], "chunk programs never compiled"
        burst()  # same shape universe — nothing new may compile
        assert engine.compiled_programs() == first
        for pad, fn in engine._chunk_fns.items():
            assert fn._cache_size() == 1, f"chunk pad {pad}"

    def test_paged_chunking_adds_no_programs(self, model):
        # The paged path chunks through the existing paged_prefill
        # traced-start seam: no separate chunk program family at all.
        engine = ServeEngine(model, max_batch=2, max_len=48, paged=True,
                             page_size=8, prefill_chunk=8)
        rng = np.random.default_rng(8)

        def burst():
            for _ in range(5):
                engine.submit(
                    rng.integers(1, VOCAB,
                                 size=int(rng.integers(3, 30))).tolist(),
                    max_new_tokens=4)
            engine.run_until_idle()

        burst()
        first = engine.compiled_programs()
        assert "prefill_chunk" not in first
        burst()
        assert engine.compiled_programs() == first
        for p, fn in engine._paged_prefill_fns.items():
            assert fn._cache_size() == 1, f"pad {p}"
