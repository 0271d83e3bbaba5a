"""The pipelined serving round (``ServeEngine.step``): a greedy paged ragged
engine dispatches round n's decode before it reads round n - 1's picks,
makes that decode's input tokens on the device, and reads, records and
retires while the device works.

Each case runs one script of submissions and steps twice: on the engine as
it is, and on the same engine stepped ROUND BY ROUND (:func:`round_by_round`,
the reference: every program's picks are read in the round that made them,
the order a sampling, bucketed or contiguous engine keeps). The two are held
to token-identical streams and exact counters. The GPT-2 plan over an int8
pool here; the hybrid plan (its state swapped on the device) in
``test_hybrid_lm.py``, the window plan in ``test_exaone_lm.py``.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.observe import metrics
from tpu_dist.serve import engine as engine_lib
from tpu_dist.serve import journal as journal_lib
from tpu_dist.serve.engine import ServeEngine

VOCAB = 64
ENGINE = dict(max_batch=4, max_len=64, paged=True, page_size=8, ragged=True,
              kv_dtype="int8", prefill_chunk=8)


@pytest.fixture(scope="module")
def model():
    model = build_transformer_lm(VOCAB, 64, d_model=16, depth=2, num_heads=2)
    model.init(0)
    return model


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def round_by_round(engine):
    """The reference order on the engine's own programs: each step reads a
    last chunk's first token before the decode and the decode's picks right
    after it, and a request retires in the round its last token was read."""
    engine._pipelined = False
    return engine


class Trial:
    """One script on one engine: its requests, the counters it left, the
    rows of each decode it dispatched, and for each dispatch whether a
    decode was still unread then."""

    def __init__(self, model, script, *, pipelined, clock=None, **kw):
        self.engine = ServeEngine(model, **{**ENGINE, **kw},
                                  clock=clock or time.monotonic)
        if not pipelined:
            round_by_round(self.engine)
        self.rows, self.behind, self.swaps_in_flight = [], [], []
        engine = self.engine
        dispatch, swap = engine._dispatch_decode, engine._apply_swap

        def dispatching(fn, args, decoding):
            self.rows.append(len(decoding))
            self.behind.append(any(u.dispatched_s is not None
                                   for u in engine._unread))
            return dispatch(fn, args, decoding)

        def swapping(pair):
            if pair is not None:
                self.swaps_in_flight.append(engine._picks is not None)
            return swap(pair)

        engine._dispatch_decode, engine._apply_swap = dispatching, swapping
        reg = metrics.get_registry()
        reg.reset()
        metrics.enable()
        try:
            self.reqs = script(engine)
            self.counters = dict(reg.snapshot()["counters"])
        finally:
            metrics.disable()
            reg.reset()
        assert engine.scheduler.idle() and not engine._unread


def _prompt(rng, n):
    return rng.integers(1, VOCAB, size=n).tolist()


def _burst(shapes, *, seed=0, eos_id=None):
    def script(engine):
        rng = np.random.default_rng(seed)
        reqs = [engine.submit(_prompt(rng, n), max_new_tokens=new,
                              eos_id=eos_id) for n, new in shapes]
        engine.run_until_idle()
        return reqs
    return script


def _compare(model, script, *, restarts, discarded=0, extra_rows=0,
             evicted=(), fake_clock=False, **kw):
    """Run ``script`` both ways and hold the pipelined run to the
    reference: ``restarts`` decodes dispatched with none in flight,
    ``discarded`` picks thrown away, ``extra_rows`` rows decoded that the
    reference never decoded, and the requests ``evicted`` with a pick of
    theirs in flight."""
    ref, got = (Trial(model, script, pipelined=pipelined,
                      clock=_FakeClock() if fake_clock else None, **kw)
                for pipelined in (False, True))
    assert got.engine._pipelined and not ref.engine._pipelined
    for mine, want in zip(got.reqs, ref.reqs):
        assert (mine.status, mine.finish_reason) == (want.status,
                                                     want.finish_reason)
        if mine.rid in evicted:
            # Its pick in flight at the eviction is thrown away.
            assert mine.generated == want.generated[:-1]
        else:
            assert mine.generated == want.generated, mine.rid
    c, r = got.counters, ref.counters
    assert c["serve.tokens.generated"] + len(evicted) == (
        r["serve.tokens.generated"])
    for name in ("serve.requests.completed", "serve.requests.evicted"):
        assert c.get(name, 0) == r.get(name, 0), name
    assert c["serve.decode.steps"] == len(got.rows)
    assert c.get("serve.decode.overlapped", 0) == sum(got.behind) == (
        len(got.rows) - restarts)
    assert c.get("serve.decode.rows_discarded", 0) == discarded
    # A request finished by its length sits the round out: no row the
    # reference did not decode, unless EOS came with a decode in flight.
    assert sum(got.rows) == sum(ref.rows) + extra_rows
    assert not any(ref.behind) and not any(ref.swaps_in_flight)
    assert "serve.decode.overlapped" not in r
    assert "serve.decode.rows_discarded" not in r
    return got, ref


def _stream(model, prompt, new):
    engine = round_by_round(ServeEngine(model, **ENGINE))
    return engine.generate(prompt, max_new_tokens=new)


def test_a_slot_finished_by_length_is_swapped_while_a_round_is_in_flight(
        model):
    """Slot 1 ends first while slots 2 and 3 decode on: its swap lands
    after the next decode was dispatched, whose picks are then taken in
    the order the swap left; queued requests reuse the freed slots."""
    got, _ = _compare(model, _burst([(5, 9), (3, 2), (7, 6), (6, 4),
                                     (4, 7), (5, 3)]), restarts=1)
    assert any(got.swaps_in_flight)


def test_a_request_of_one_token_never_decodes(model):
    got, ref = _compare(model, _burst([(5, 1), (6, 5), (3, 1), (7, 3),
                                       (4, 1)], seed=1), restarts=1)
    assert [len(r.generated) for r in got.reqs] == [1, 5, 1, 3, 1]


def test_a_last_chunk_feeds_the_decode_on_the_device(model, monkeypatch):
    """A prompt of four chunks lands while another request decodes: its
    first token goes into the next decode's input where it was picked,
    and the host reads it with the decode before it."""
    put = []
    feed = engine_lib._next_inputs
    monkeypatch.setattr(engine_lib, "_next_inputs", lambda t, o, s, k: (
        put.append(int(s)), feed(t, o, s, k))[1])

    def script(engine):
        rng = np.random.default_rng(2)
        short = engine.submit(_prompt(rng, 3), max_new_tokens=12)
        engine.step()
        long = engine.submit(_prompt(rng, 30), max_new_tokens=5)
        engine.run_until_idle()
        return [short, long]

    got, _ = _compare(model, script, restarts=1)
    fed = [s for s in put if s < ENGINE["max_batch"]]
    # Each first token once, in the pipelined run only: the short one's
    # into a decode nothing fed yet, the long one's beside the short one.
    assert len(fed) == 2
    assert got.reqs[1].generated


def test_eos_with_a_decode_in_flight_discards_its_row(model):
    """The request that reads EOS was decoded once more in the round in
    flight; that pick is dropped, and the next request in its one slot
    serves what the reference serves."""
    rng = np.random.default_rng(3)
    prompt = _prompt(rng, 6)
    stream = _stream(model, prompt, 8)
    eos = next(t for k, t in enumerate(stream)
               if 2 <= k <= 5 and t not in stream[:k])

    def script(engine):
        a = engine.submit(prompt, max_new_tokens=8, eos_id=eos)
        b = engine.submit(_prompt(np.random.default_rng(4), 5),
                          max_new_tokens=6)
        engine.run_until_idle()
        return [a, b]

    # The one slot empties with the extra decode in flight: nobody reads
    # it, and the next request's first decode starts with none in flight.
    got, _ = _compare(model, script, restarts=2, discarded=1,
                      extra_rows=1, max_batch=1)
    assert got.reqs[0].finish_reason == "eos"
    assert got.reqs[0].generated[-1] == eos


def test_a_deadline_eviction_with_a_round_in_flight(model):
    def script(engine):
        stuck = engine.submit([1, 2, 3], max_new_tokens=30, deadline_s=5.0)
        quick = engine.submit([4, 5], max_new_tokens=4)
        for _ in range(4):
            engine.step()
        engine.clock.t = 6.0
        engine.run_until_idle()
        return [stuck, quick]

    got, ref = _compare(model, script, restarts=1, discarded=1,
                        evicted=(0,), fake_clock=True, max_batch=2)
    assert got.reqs[0].status == "evicted" and got.reqs[1].status == "done"
    assert got.engine._paging.allocator.pages_in_use == (
        got.engine._paging.prefix.pages_held)


def test_the_engine_goes_idle_and_resumes(model):
    def script(engine):
        first = _burst([(5, 4), (7, 3)], seed=5)(engine)
        assert not engine._unread and engine._picks is None
        return first + _burst([(6, 5), (3, 2), (8, 4)], seed=6)(engine)

    got, _ = _compare(model, script, restarts=2)
    # Below 100 % only for the first round after the engine went idle.
    assert got.behind.count(False) == 2


def test_journal_recovery_of_a_pipelined_engine_is_token_identical(
        model, tmp_path):
    shapes = [(20, 5), (9, 6), (27, 4), (5, 3), (14, 6)]
    want = Trial(model, _burst(shapes, seed=7), pipelined=False)
    first = ServeEngine(model, **ENGINE, journal=tmp_path / "j")
    rng = np.random.default_rng(7)
    for n, new in shapes:
        first.submit(_prompt(rng, n), max_new_tokens=new)
    for _ in range(4):
        first.step()
    assert first._unread      # a decode in flight at the crash
    first.journal._buf.clear()  # the torn unflushed tail
    del first
    second = ServeEngine(model, **ENGINE, journal=tmp_path / "j")
    assert second._pipelined and second.last_replay is not None
    second.run_until_idle()
    second._paging.allocator.check()
    second.close()
    state = journal_lib.load(tmp_path / "j" / journal_lib.JOURNAL_NAME)
    for req in want.reqs:
        assert state.requests[req.rid].tokens == req.generated


def test_the_stall_watchdog_wraps_the_dispatch_and_the_read(model):
    class Stall:
        naps = [0.5]

        def on_decode(self):
            if self.naps:
                time.sleep(self.naps.pop(0))

        def on_step_end(self, done_count):
            pass

    tripped = []
    got = Trial(model, _burst([(5, 4), (3, 3)], seed=8), pipelined=True,
                stall_timeout_s=0.15, stall_action=tripped.append,
                fault_injector=Stall())
    ref = Trial(model, _burst([(5, 4), (3, 3)], seed=8), pipelined=False)
    assert [r.generated for r in got.reqs] == [r.generated for r in ref.reqs]
    assert len(tripped) == 1 and tripped[0]["bucket"] == ENGINE["max_batch"]


def test_a_sampling_engine_keeps_the_round_by_round_order(model):
    got = Trial(model, _burst([(5, 4), (3, 3), (6, 5)], seed=9),
                pipelined=True, temperature=0.8, seed=3)
    assert not got.engine._pipelined
    assert got.counters["serve.decode.steps"] == len(got.rows) > 0
    assert "serve.decode.overlapped" not in got.counters
    assert not any(got.behind)


def test_uploads_are_copies_of_what_the_host_changes_after_dispatch(model):
    """On the CPU ``jnp.asarray`` aliases a 64-byte-aligned numpy buffer:
    an array handed to a program the host has not waited for would change
    under it when the host swaps or extends its rows."""
    raw = np.zeros(16 * 4 + 64, np.uint8)
    at = -raw.ctypes.data % 64
    host = raw[at:at + 64].view(np.int32)
    host[:] = 7
    aliased = jnp.asarray(host)
    host[0] = 99
    assert int(aliased[0]) == 99
    engine = ServeEngine(model, **ENGINE)
    host[0] = 7
    (copy,) = engine._upload(host)
    host[0] = 99
    assert int(copy[0]) == 7


def test_the_input_feed_is_traced_once_a_capacity(model):
    """One shape a capacity and one placement, whether the feed starts
    from the previous decode's picks or from nothing: no compile after
    warm-up, and the decode program compiles once."""
    before = engine_lib._next_inputs._cache_size()
    got = Trial(model, _burst([(5, 6), (3, 2), (20, 5), (6, 4), (4, 7),
                               (9, 1), (5, 3)], seed=10),
                pipelined=True, max_batch=5)
    assert engine_lib._next_inputs._cache_size() == before + 1
    (decode,) = got.engine._paged_decode_fns.values()
    assert decode._cache_size() == 1
