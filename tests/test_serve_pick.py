"""The next token is picked inside the engine's programs (``serve/engine.py``:
``_picking``): a step hands the host its slots' token ids and leaves the
logits on the device, which cross when somebody reads a row and only then.

Every case runs over the six program families the engine builds: the
contiguous cache (whole-prompt and chunked prefill), the paged pool
(bucketed, ragged fp32, ragged int8) and the hybrid plan (latent pages,
recurrent state, routed experts) at the size ``tests/test_hybrid_lm.py``
uses.
"""

import functools
import importlib.util
import json
import pathlib

import numpy as np
import pytest

from tpu_dist.models import build_transformer_lm
from tpu_dist.models.policy import policy, set_policy
from tpu_dist.observe import metrics
from tpu_dist.serve.engine import ServeEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
VOCAB, SLOTS = 32, 4

GPT = dict(max_batch=SLOTS, max_len=64)
FAMILIES = {
    "contiguous": GPT,
    "contiguous-chunked": {**GPT, "prefill_chunk": 8},
    "paged-bucketed": {**GPT, "paged": True, "page_size": 8},
    "paged-ragged-fp32": {**GPT, "paged": True, "page_size": 8,
                          "ragged": True},
    "paged-ragged-int8": {**GPT, "paged": True, "page_size": 8,
                          "ragged": True, "kv_dtype": "int8"},
    "hybrid": dict(max_batch=SLOTS, max_len=128, paged=True, ragged=True,
                   kv_dtype="fp32", page_size=16, num_pages=40,
                   prefill_chunk=32),
}

#: What ``compiled_programs()`` reported for these workloads at the parent
#: commit (501c3fc), family by family: the pick adds no program.
PROGRAMS = {
    "contiguous": {"decode": [1, 2, 4], "prefill": [8, 16]},
    "contiguous-chunked": {"decode": [1, 2, 4], "prefill": [],
                           "prefill_chunk": [8]},
    "paged-bucketed": {"decode": [], "prefill": [],
                       "paged_decode": [1, 2, 4], "paged_prefill": [8, 16]},
    "paged-ragged-fp32": {"decode": [], "prefill": [],
                          "paged_decode": [4], "paged_prefill": [8, 16]},
    "paged-ragged-int8": {"decode": [], "prefill": [],
                          "paged_decode": [4], "paged_prefill": [8, 16]},
    "hybrid": {"decode": [], "prefill": [],
               "paged_decode": [4], "paged_prefill": [8, 16, 32]},
}

#: The families whose greedy rounds are pipelined: the decode of round n is
#: dispatched before round n - 1's picks are read.
PIPELINED = {"paged-ragged-fp32", "paged-ragged-int8", "hybrid"}

pytestmark = pytest.mark.parametrize("family", list(FAMILIES))


@pytest.fixture(autouse=True)
def float32():
    before = policy()
    set_policy("float32")
    yield
    set_policy(before)


@functools.cache
def _hybrid():
    spec = importlib.util.spec_from_file_location(
        "ling_hybrid_for_pick_tests",
        ROOT / "tpubench/reference/ling_hybrid.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    full = json.loads(
        (ROOT / "tpubench/configs/ling-3.0-flash.json").read_text())
    return module, {**full, **full["rehearsal"]}


def _engine(family, **kw):
    if family == "hybrid":
        module, cfg = _hybrid()
        model = module.build_program(cfg, 11)
    else:
        model = build_transformer_lm(VOCAB, 64, d_model=16, depth=2,
                                     num_heads=2)
        model.init(0)
    return ServeEngine(model, **FAMILIES[family], **kw)


def _vocab(family):
    return _hybrid()[1]["vocab_size"] if family == "hybrid" else VOCAB


def _serve(engine, family):
    """More requests than slots, mixed lengths: every bucket, slot swaps,
    chunks of every pad."""
    rng = np.random.default_rng(5)
    shapes = ([(40, 6), (9, 9), (20, 4), (12, 7), (5, 5), (30, 3)]
              if family == "hybrid" else
              [(3, 6), (11, 9), (7, 4), (13, 7), (5, 5), (9, 3), (2, 8)])
    reqs = [engine.submit(rng.integers(1, _vocab(family), size=n).tolist(),
                          max_new_tokens=new) for n, new in shapes]
    engine.run_until_idle()
    assert all(r.status == "done" for r in reqs)
    return reqs


def _spy(engine, read):
    """One event a picked token, in order: the row ``_pick`` was handed
    (``read(row)`` of it, taken inside the pick), the token it returned,
    the request, slot and round it was for, and the row's index in its
    program's result (a decode's slot as the step was dispatched)."""
    events = []
    pick, record = engine._pick, engine.scheduler.record_token

    def spying(row):
        spying.last = (read(row), pick(row), row.index)
        return spying.last[1]

    def recording(req, token, *, now):
        seen, picked, index = spying.last
        events.append({"row": seen, "token": picked, "rid": req.rid,
                       "slot": req.slot, "index": index,
                       "round": engine._round, "first": not req.generated})
        return record(req, token, now=now)

    engine._pick, engine.scheduler.record_token = spying, recording
    return events


@pytest.fixture()
def recording():
    reg = metrics.get_registry()
    reg.reset()
    metrics.enable()
    try:
        yield lambda: dict(reg.snapshot()["counters"])
    finally:
        metrics.disable()
        reg.reset()


def test_every_served_token_is_the_first_maximum_of_its_row(family):
    """The spy of ``tests/test_hybrid_lm.py``: ``np.array`` of every row."""
    engine = _engine(family)
    events = _spy(engine, np.array)
    reqs = _serve(engine, family)
    assert len(events) == sum(len(r.generated) for r in reqs)
    for e in events:
        assert e["row"].dtype == np.float32
        assert e["row"].shape == (_vocab(family),)
        assert e["token"] == int(np.argmax(e["row"]))
    for r in reqs:
        assert r.generated == [e["token"] for e in events
                               if e["rid"] == r.rid]


def _slot_rows(engine):
    """Slots a decode program ran over, summed over the steps so far: the
    bucket of each step (a ragged engine's is its capacity, every step)."""
    buckets = []
    bucket = engine.scheduler.bucket
    engine.scheduler.bucket = lambda: (buckets.append(bucket()),
                                       buckets[-1])[1]

    def total(steps):
        if engine.paged and engine.ragged:
            assert not buckets
            return engine.max_batch * steps
        assert len(buckets) == steps
        return sum(buckets)
    return total


def test_a_greedy_step_hands_the_host_token_ids_only(family, recording):
    engine = _engine(family)
    slot_rows = _slot_rows(engine)
    reqs = _serve(engine, family)
    got = recording()
    rows = slot_rows(got["serve.decode.steps"]) + len(reqs)
    assert got["serve.logits.bytes"] == 4 * rows
    generated = sum(len(r.generated) for r in reqs)
    assert got["serve.tokens.generated"] == generated
    assert got["serve.pick.device"] == generated
    assert "serve.pick.host_rows" not in got


def test_a_row_is_the_programs_and_a_step_crosses_once(family, recording):
    engine = _engine(family)
    outputs = {}
    acquire = engine._acquire_program

    def keeping(kind, key, builder):
        fn = acquire(kind, key, builder)

        def run(*args):
            out = fn(*args)
            outputs[engine._round, out[1].ndim] = out[1]
            return out
        return run

    engine._acquire_program = keeping
    events = _spy(engine, lambda row: row)
    _serve(engine, family)
    before = recording()["serve.logits.bytes"]
    decode = [e for e in events if not e["first"]]
    crowded = max({e["round"] for e in decode},
                  key=lambda rnd: sum(e["round"] == rnd for e in decode))
    a, b = [e for e in decode if e["round"] == crowded][:2]
    # A pipelined round reads the picks of the decode the round before it
    # dispatched; round by round a decode is read in its own round.
    program = np.asarray(outputs[crowded - engine._pipelined, 2])
    assert engine._pipelined == (family in PIPELINED)
    assert len(a["row"]) == len(b["row"]) == _vocab(family) == program.shape[1]
    assert np.array_equal(np.asarray(a["row"]), program[a["index"]])
    assert recording()["serve.logits.bytes"] == before + program.nbytes
    assert np.array_equal(np.asarray(b["row"]), program[b["index"]])
    assert np.array_equal(np.asarray(a["row"]), program[a["index"]])
    assert recording()["serve.logits.bytes"] == before + program.nbytes
    # A copy asked for is a copy: the step's rows stay what they were.
    np.array(a["row"])[:] = 0.0
    assert np.array_equal(np.asarray(a["row"]), program[a["index"]])
    # A first token's row is the prefill program's whole result (the last
    # prefill of its round: one round may admit several requests).
    first = [e for e in events if e["first"]][-1]
    assert np.array_equal(np.asarray(first["row"]),
                          np.asarray(outputs[first["round"], 1]))
    assert (recording()["serve.logits.bytes"]
            == before + program.nbytes + 4 * _vocab(family))
    assert "serve.pick.host_rows" not in recording()


def _parent_pick(rng, temperature, logits):
    """``ServeEngine._pick`` as commit 501c3fc had it, ``temperature > 0``."""
    z = logits.astype(np.float64) / temperature
    z -= z.max()
    p = np.exp(z)
    return int(rng.choice(logits.shape[-1], p=p / p.sum()))


def test_sampling_draws_the_parents_stream_from_the_same_logits(
        family, recording):
    engine = _engine(family, temperature=0.8, seed=17)
    slot_rows = _slot_rows(engine)
    events = _spy(engine, np.array)
    reqs = _serve(engine, family)
    rng = np.random.default_rng(17)
    assert [e["token"] for e in events] == [
        _parent_pick(rng, 0.8, e["row"]) for e in events]
    assert any(e["token"] != int(np.argmax(e["row"])) for e in events)
    got = recording()
    generated = sum(len(r.generated) for r in reqs)
    assert got["serve.pick.host_rows"] == generated == len(events)
    assert "serve.pick.device" not in got
    # Today's cost: every step's logits cross, once, beside its token ids.
    rows = slot_rows(got["serve.decode.steps"]) + len(reqs)
    assert got["serve.logits.bytes"] == (4 + 4 * _vocab(family)) * rows


def test_the_pick_adds_no_program(family):
    engine = _engine(family)
    _serve(engine, family)
    assert engine.compiled_programs() == PROGRAMS[family]
    fns = (engine._decode_fns, engine._prefill_fns, engine._chunk_fns,
           engine._paged_decode_fns, engine._paged_prefill_fns)
    assert all(fn._cache_size() == 1 for d in fns for fn in d.values())
