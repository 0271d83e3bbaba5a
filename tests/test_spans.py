"""The program's one span primitive and what stands on it: free when off,
nested and identified when on, the phases of a serving round, a request's
three records, the compile listener, a Telemetry that adds no wait, and
the eight per-layer metrics the benchmark reads from them."""

import json
import pathlib
import shutil

import jax
import numpy as np
import pytest

import tpu_dist as td
from tpu_dist.observe import exporters, metrics
from tpu_dist.observe.telemetry import Telemetry
from tpu_dist.utils import compile_cache, profiler

ROOT = pathlib.Path(__file__).resolve().parents[1]

ROUND_CHILDREN = {
    "serve.step.admit", "serve.step.prefill_chunk", "serve.step.decode_prep",
    "serve.step.decode_dispatch", "serve.step.decode_wait",
    "serve.step.pick", "serve.step.journal_flush"}


@pytest.fixture()
def recording():
    """The default registry, clean and enabled for one test."""
    reg = metrics.get_registry()
    reg.reset()
    metrics.enable()
    try:
        yield reg
    finally:
        metrics.disable()
        reg.reset()


def _spans(reg, prefix=""):
    return [s for s in reg.snapshot()["spans"]
            if s["name"].startswith(prefix)]


# -- the primitive ------------------------------------------------------------

def test_off_span_is_one_shared_null_object_and_records_nothing():
    reg = metrics.get_registry()
    reg.reset()
    assert not metrics.enabled() and not profiler.is_active()
    a, b = profiler.span("serve.step", 1), profiler.span("x.y")
    assert a is b is profiler.NULL_SPAN
    with a as entered:
        assert entered is profiler.NULL_SPAN and entered.seconds == 0.0
    assert profiler.current_span() is None
    snap = reg.snapshot()
    assert snap["spans"] == [] and snap["distributions"] == {}
    assert snap["counters"] == {}


def test_on_span_observes_and_rings_with_parent_and_ident(recording):
    with profiler.span("outer", 7) as outer:
        assert profiler.current_span() is outer
        with profiler.span("outer.inner", 7) as inner:
            pass
    assert profiler.current_span() is None
    snap = recording.snapshot()
    by_name = {s["name"]: s for s in snap["spans"]}
    assert set(by_name) == {"outer", "outer.inner"}
    assert by_name["outer"]["parent"] is None
    assert by_name["outer.inner"]["parent"] == by_name["outer"]["id"]
    assert {s["ident"] for s in snap["spans"]} == {7}
    assert by_name["outer"]["start"] <= by_name["outer.inner"]["start"]
    assert by_name["outer.inner"]["end"] <= by_name["outer"]["end"]
    assert 0.0 <= inner.seconds <= outer.seconds
    assert snap["distributions"]["span.outer.s"]["count"] == 1
    assert snap["distributions"]["span.outer.s"]["sum"] == pytest.approx(
        outer.seconds)


def test_a_span_that_raises_still_closes(recording):
    with pytest.raises(ValueError):
        with profiler.span("boom"):
            raise ValueError("inside")
    assert profiler.current_span() is None
    assert [s["name"] for s in _spans(recording)] == ["boom"]


def test_the_ring_is_bounded_and_drops_the_oldest(recording):
    n = metrics.SPAN_RING_SIZE + 10
    for i in range(n):
        recording.record_span("r", float(i), float(i) + 0.5, ident=i)
    spans = recording.snapshot()["spans"]
    assert len(spans) == metrics.SPAN_RING_SIZE
    assert spans[0]["ident"] == 10 and spans[-1]["ident"] == n - 1
    assert set(spans[0]) == set(metrics.SPAN_FIELDS)


def test_jsonl_series_carries_the_ring_on_its_final_record_only(
        recording, tmp_path):
    with profiler.span("once"):
        pass
    with exporters.JsonlExporter(tmp_path / "m.jsonl") as out:
        out.write(recording.snapshot(), kind="epoch", epoch=0)
        out.write(recording.snapshot(), kind="final")
    epoch, final = exporters.read_series(tmp_path / "m.jsonl")
    assert "spans" not in epoch["metrics"]
    assert [s["name"] for s in final["metrics"]["spans"]] == ["once"]


def test_compile_listener_leaves_a_record_under_the_open_span(recording):
    _, _, before_s = compile_cache.meter().read()
    with profiler.span("holder", 386) as holder:
        jax.jit(lambda x: x * 3.0 + 1.25)(np.arange(7.0)).block_until_ready()
    assert compile_cache.meter().read()[2] > before_s
    snap = recording.snapshot()
    compiles = [s for s in snap["spans"] if s["name"] == "compile"]
    assert compiles and all(c["parent"] == holder.id for c in compiles)
    assert all(c["end"] >= c["start"] for c in compiles)
    for key in ("trace_s", "lower_s", "backend_s"):
        assert snap["distributions"][f"compile.{key}"]["count"] >= 1


# -- a serving round ----------------------------------------------------------

@pytest.fixture(scope="module")
def cells_policy():
    """The cells set the process's precision policy (``mixed_bfloat16``);
    the tests that come after this file in the worker get the old one."""
    from tpu_dist.models.policy import policy, set_policy

    before = policy()
    yield
    set_policy(before)


@pytest.fixture(scope="module")
def chat_engine(tmp_path_factory, cells_policy):
    """The chat cell's engine at its rehearsal sizes, with a journal."""
    from tpubench.harness import cells, serve_cell

    cell = cells.Cell("serve.gpt2-large.chat").at_rehearsal_sizes()
    run = serve_cell.ServeRun(cell, seed=5)
    run.engine_args["journal"] = str(tmp_path_factory.mktemp("journal"))
    run.build(seconds=1.0)
    engine = run.engine
    # Every program the rounds below need, before anything records.
    for prompt in ([3, 4, 5, 6], [9, 8, 7]):
        engine.submit(prompt, max_new_tokens=3)
    engine.run_until_idle()
    yield engine
    engine.close()


def _full_round(engine, reg):
    """One round with every phase in it: a request already decoding, and
    a second one admitted whose prompt is a single chunk."""
    first = engine.submit([3, 4, 5, 6], max_new_tokens=8)
    engine.step()
    second = engine.submit([9, 8, 7], max_new_tokens=8)
    reg.reset()
    engine.step()
    snap, rnd = reg.snapshot(), engine._round
    engine.run_until_idle()
    assert first.status == second.status == "done"
    return snap, rnd


def test_one_round_leaves_exactly_the_phases_nested_under_the_step(
        chat_engine, recording):
    snap, rnd = _full_round(chat_engine, recording)
    spans = [s for s in snap["spans"] if s["name"].startswith("serve.step")]
    (step,) = [s for s in spans if s["name"] == "serve.step"]
    children = [s for s in spans if s["parent"] == step["id"]]
    assert sorted(s["name"] for s in children) == sorted(ROUND_CHILDREN)
    (chunk,) = [s for s in children
                if s["name"] == "serve.step.prefill_chunk"]
    # The cell's engine is pipelined: the last chunk's first token is not
    # waited for inside the chunk; it rides the decode's read-back, which
    # reads the previous round's decode after this round's was dispatched.
    rest = [s for s in spans if s is not step and s not in children]
    assert rest == []
    assert chunk["end"] <= [s for s in children if s["name"]
                            == "serve.step.decode_wait"][0]["start"]
    assert snap["counters"]["serve.decode.steps"] == 1
    assert snap["counters"]["serve.decode.overlapped"] == 1
    # Children tile the parent without overlap, so they sum to at most it.
    assert sum(s["end"] - s["start"] for s in children) <= (
        step["end"] - step["start"])
    assert all(step["start"] <= s["start"] and s["end"] <= step["end"]
               for s in children)
    # One ident a round: the round's number.
    assert {s["ident"] for s in spans} == {rnd}
    # The round's own counters and its host share.
    assert snap["counters"]["serve.step.rounds"] == 1
    assert snap["counters"]["serve.upload.bytes"] > 0
    assert snap["counters"]["serve.logits.bytes"] > 0
    host = snap["distributions"]["serve.step.host_s"]
    whole = snap["distributions"]["span.serve.step.s"]
    assert host["count"] == whole["count"] == 1
    assert 0.0 < host["sum"] <= whole["sum"]


def test_a_requests_three_records_share_its_rid_and_sum_to_its_ttft(
        chat_engine, recording):
    recording.reset()
    reqs = [chat_engine.submit([5, 6, 7, 8, 9], max_new_tokens=4),
            chat_engine.submit([2, 3], max_new_tokens=4)]
    chat_engine.run_until_idle()
    snap = recording.snapshot()
    for req in reqs:
        mine = {s["name"]: s for s in snap["spans"]
                if s["name"].startswith("serve.request.")
                and s["ident"] == req.rid}
        assert set(mine) == {"serve.request.queued",
                             "serve.request.prefill",
                             "serve.request.decode"}
        queued, prefill = mine["serve.request.queued"], mine[
            "serve.request.prefill"]
        assert queued["end"] == prefill["start"] == req.admit_s
        assert (queued["end"] - queued["start"]
                + prefill["end"] - prefill["start"]) == pytest.approx(
                    req.ttft_s, abs=1e-9)
        assert mine["serve.request.decode"]["end"] == req.finish_s
    d = snap["distributions"]
    assert d["serve.request.queue_wait_s"]["count"] == 2
    assert d["serve.request.prefill_s"]["count"] == 2
    assert (d["serve.request.queue_wait_s"]["sum"]
            + d["serve.request.prefill_s"]["sum"]) == pytest.approx(
                sum(r.ttft_s for r in reqs), abs=1e-9)
    # Every token after a request's first has a gap to its predecessor.
    assert d["serve.token.gap_s"]["count"] == sum(
        len(r.generated) - 1 for r in reqs)


def test_programs_built_rises_on_a_new_bucket_and_not_on_a_repeat(
        chat_engine, recording):
    chat_engine.generate([4, 4, 4], max_new_tokens=2)   # pad 8: warmed
    assert "serve.programs.built" not in recording.snapshot()["counters"]
    fresh = sorted(set((8, 16, 32, 64))
                   - set(chat_engine.compiled_programs()["paged_prefill"]))
    assert fresh, "the fixture warmed every prefill pad"
    prompt = list(range(1, fresh[0]))   # pads up to the unseen bucket
    chat_engine.generate(prompt, max_new_tokens=2)
    snap = recording.snapshot()
    assert snap["counters"]["serve.programs.built"] == 1
    (built,) = [s for s in snap["spans"]
                if s["name"] == "serve.program.build"]
    assert built["ident"] == f"paged_prefill:{fresh[0]}"
    chat_engine.generate(prompt, max_new_tokens=2)
    assert recording.snapshot()["counters"]["serve.programs.built"] == 1


def test_int8_prefill_error_is_read_only_while_recording(chat_engine):
    reg = metrics.get_registry()
    reg.reset()
    assert chat_engine._kv_quant and not metrics.enabled()
    chat_engine.generate([6, 5, 4, 3], max_new_tokens=2)
    assert chat_engine._pending_qerr == []
    assert "serve.kv.quant_error" not in reg.snapshot()["distributions"]
    metrics.enable()
    try:
        chat_engine.generate([6, 5, 4, 2], max_new_tokens=2)
        snap = reg.snapshot()
    finally:
        metrics.disable()
        reg.reset()
    assert chat_engine._pending_qerr == []
    assert (snap["distributions"]["serve.kv.quant_error"]["count"]
            == snap["counters"]["serve.prefill.chunks"])


@pytest.mark.parametrize("walks", [False, True], ids=["xla-body", "kernel"])
def test_decode_page_counters_count_what_the_lengths_say(
        chat_engine, monkeypatch, walks):
    """``serve.decode.pages_read`` over ``.pages_addressed``: the pages a
    decode step's attention reads against those its table rows address.
    Which body runs the engine decided when it made its pool (the
    platform); here the test answers for it once the programs are built,
    so the compiled program stays what it was."""
    reg = metrics.get_registry()
    reg.reset()
    prompt, new = list(range(1, 15)), 6     # decodes at lengths 14 .. 18
    assert chat_engine._walks_pages is False        # off the TPU
    chat_engine.generate(prompt, max_new_tokens=new)
    monkeypatch.setattr(chat_engine, "_walks_pages", walks)
    assert not any(k.startswith("serve.decode.pages")
                   for k in reg.snapshot()["counters"])
    metrics.enable()
    try:
        chat_engine.generate([p + 1 for p in prompt], max_new_tokens=new)
        counters = reg.snapshot()["counters"]
    finally:
        metrics.disable()
        reg.reset()
    steps = new - 1                         # the prefill gives the first
    page, slots = chat_engine.page_size, chat_engine.max_batch
    max_pages = chat_engine._paging.allocator.table.shape[1]
    addressed = steps * slots * max_pages
    assert counters["serve.decode.steps"] == steps
    assert counters["serve.decode.pages_addressed"] == addressed
    read = sum((len(prompt) + i) // page + 1 for i in range(steps))
    assert read == 1 + 1 + 2 + 2 + 2        # the tail crosses into page 2
    assert counters["serve.decode.pages_read"] == (
        read if walks else addressed)


# -- the trainer --------------------------------------------------------------

def _fit(callbacks):
    model = td.models.Sequential(
        [td.models.Dense(8, activation="relu"), td.models.Dense(4)],
        input_shape=(8,))
    model.compile(loss="sparse_categorical_crossentropy", optimizer="sgd")
    rng = np.random.default_rng(0)
    ds = td.Dataset.from_tensor_slices(
        (rng.normal(size=(64, 8)).astype(np.float32),
         rng.integers(0, 4, 64).astype(np.int64))).batch(16)
    model.fit(ds, epochs=2, verbose=0, callbacks=callbacks)


def test_fit_under_telemetry_blocks_no_more_than_a_fit_without(
        eight_devices, monkeypatch):
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (calls.append(1), real(x))[1])
    _fit([])
    without = len(calls)
    del calls[:]
    reg = metrics.MetricsRegistry(enabled=False)
    _fit([Telemetry(registry=reg)])
    assert len(calls) <= without
    snap = reg.snapshot()
    d = snap["distributions"]
    # The step's phases come from the two spans, one pair an execution.
    assert snap["counters"]["step.count"] == 8
    assert d["step.data_wait_s"]["count"] == 8
    assert d["step.data_wait_s"]["sum"] == pytest.approx(
        d["span.train.exec.fetch.s"]["sum"])
    assert d["step.dispatch_s"]["sum"] == pytest.approx(
        d["span.train.exec.dispatch.s"]["sum"])
    # One wall-time reading and one end-of-epoch span an epoch.
    assert d["step.total_s"]["count"] == 2
    assert d["span.train.epoch.end.s"]["count"] == 2
    fetches = [s for s in snap["spans"] if s["name"] == "train.exec.fetch"]
    assert sorted(s["ident"] for s in fetches) == list(range(8))


def test_telemetry_with_its_own_registry_takes_the_spans_and_gives_back(
        eight_devices):
    default = metrics.get_registry()
    default.reset()
    reg = metrics.MetricsRegistry(enabled=False)
    _fit([Telemetry(registry=reg)])
    assert metrics.get_registry() is default and not default.enabled
    assert default.snapshot()["spans"] == []
    assert reg.snapshot()["distributions"][
        "span.train.exec.dispatch.s"]["count"] == 8


# -- the benchmark's readers --------------------------------------------------

NEW_METRICS = {
    "serve.gpt2-large.chat": (
        "serve_step_host_share", "serve_decode_prep_ms", "serve_pick_ms",
        "serve_token_gap_mean_ms", "serve_queue_wait_mean_ms",
        "serve_prefill_mean_ms", "decode_pages_read_share"),
    "train.gpt2-medium.dp1": ("trainer_fetch_ms", "trainer_dispatch_ms"),
}


@pytest.fixture(scope="module")
def rehearsed_layers(tmp_path_factory, cells_policy):
    """A temporary checkout holding BENCHMARK.json and tpubench/ alone,
    and each cell's per-layer metrics read there at rehearsal sizes."""
    from tpubench import run as bench_run

    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "tpubench", root / "tpubench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    layers = {}
    for workload in NEW_METRICS:
        args = bench_run.parse([
            "--workload", workload, "--seed", str(2 ** 31 + 25),
            "--seconds", "3", "--trace", "1", "--rehearse", "1",
            "--root", str(root)])
        cell = bench_run.load_cell(args)
        result = bench_run.run_cell(cell, args)
        assert result["checks_ok"], result["rows"]
        layers[workload] = result["per_layer"]
    return layers


@pytest.mark.parametrize(
    "workload,name",
    [(w, n) for w, names in NEW_METRICS.items() for n in names])
def test_the_new_metric_reads_from_files_and_entries_alone(
        rehearsed_layers, workload, name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert workload in entry["workloads"]
    assert entry["source"] in ("program_span", "program_counter")
    spec = json.loads(
        (ROOT / "tpubench/layer_metrics" / f"{name}.json").read_text())
    assert spec["reader"] in ("counter_ratio", "distribution_mean")
    value = rehearsed_layers[workload][name]["value"]
    assert value > 0.0
    if name == "serve_step_host_share":
        assert value <= 100.0
    if name == "decode_pages_read_share":
        assert value == 100.0   # off the TPU the XLA body reads every row
