"""The reduction from a trace to numbers, on a hand-made trace whose
answers can be worked out on paper, and on a small trace recorded on the
chip (``data/recorded_trace.json``, cut from a real run of the cell)."""

import json
import pathlib

import pytest

from tpubench.harness import readers
from tpubench.harness import trace as T

DATA = pathlib.Path(__file__).parent / "data"
US = 1000  # ns


def hand_made():
    """Two devices, a window of 100 us. Device 0: compute 0-40, an
    all-reduce 30-60 (30-40 hidden behind compute, 40-60 exposed), compute
    70-90. Device 1: compute 0-50, all-reduce 50-60 (all exposed)."""
    return {
        "/device:TPU:0": {"XLA Ops": [
            ["fusion.1", 0, 40 * US], ["all-reduce.7", 30 * US, 30 * US],
            ["flash_fwd.3", 70 * US, 20 * US]],
            "XLA Modules": [["jit_step(1)", 0, 90 * US]]},
        "/device:TPU:1": {"XLA Ops": [
            ["fusion.1", 0, 50 * US], ["all-reduce.7", 50 * US, 10 * US]]},
        "/host:CPU": {"python": [
            ["tpubench.window", 0, 100 * US],
            ["tpubench.engine_step", 0, 65 * US],
            ["tpubench.wait_for_request", 65 * US, 35 * US]]},
    }


def with_program_spans():
    """The same, with the program's own spans inside the harness's: a
    round 2-64 us that holds a decode_prep 58-61 us and a decode_wait
    61-64 us, and a span that is no one's and is dropped on loading."""
    t = hand_made()
    t["/host:CPU"]["python"] += [
        ["tpu_dist.serve.step", 2 * US, 62 * US],
        ["tpu_dist.serve.step.decode_prep", 58 * US, 3 * US],
        ["tpu_dist.serve.step.decode_wait", 61 * US, 3 * US]]
    return t


def test_intervals():
    assert T.union([(5, 7), (0, 3), (2, 4)]) == [(0, 4), (5, 7)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]


def test_busy_idle_and_window_by_hand():
    t = hand_made()
    assert T.device_planes(t) == ["/device:TPU:0", "/device:TPU:1"]
    assert T.window_seconds(t) == pytest.approx(100e-6)
    # Device 0 busy 0-60 and 70-90 = 80 us; device 1 busy 0-60 = 60 us.
    assert T.busy_seconds(t) == pytest.approx(70e-6)
    share = readers.device_idle_share({"trace": t}, {})
    assert share == pytest.approx(30.0)


def test_exposed_collective_time_by_hand():
    t = hand_made()
    # Device 0: 20 us exposed; device 1: 10 us: 15 us on average.
    assert T.exposed_collective_seconds(t) == pytest.approx(15e-6)
    ctx = {"trace": t}
    assert readers.exposed_collective_share(ctx, {}) == pytest.approx(15.0)


def test_kernel_sums_and_roofline_by_hand():
    t = hand_made()
    seconds, count = T.matching_seconds(t, ["flash.*fwd"])
    assert (seconds, count) == (pytest.approx(10e-6), 0.5)
    peaks = {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12}
    # 1e9 FLOPs need 10 us at this peak; the kernel's events took 10 us
    # a device: 100 % of the roofline, compute bound.
    ctx = {"trace": t, "peaks": peaks, "work": {"k": (1e9, 1e3)}}
    spec = {"work": "k", "patterns": ["flash.*fwd"]}
    assert readers.kernel_roofline(ctx, spec) == pytest.approx(100.0)
    ctx["work"] = {"step": 4.5e9}
    spec = {"work": "step", "patterns": ["^jit_step"], "line": "XLA Modules"}
    # 4.5e9 FLOPs over 45 us a device (90 us on one of two) at 100 TFLOP/s.
    assert readers.trace_flops_share(ctx, spec) == pytest.approx(100.0)


def test_idle_gaps_are_charged_to_what_the_host_was_doing():
    gaps = dict(T.idle_gaps_by_span(hand_made()))
    # Device 0 idle 60-70 (5 us under engine_step, then 5 under wait:
    # each part of a gap to the span open at that instant) and 90-100
    # (wait_for_request).
    assert gaps == {"tpubench.wait_for_request": pytest.approx(15e-6),
                    "tpubench.engine_step": pytest.approx(5e-6)}


def test_each_part_of_a_gap_goes_to_the_innermost_open_span():
    gaps = T.idle_gaps_by_span(with_program_spans())
    # Idle 60-70: 60-61 decode_prep, 61-64 decode_wait (both inside the
    # round, inside engine_step: the shortest wins), 64-65 engine_step
    # alone, 65-70 and 90-100 wait_for_request.
    assert dict(gaps) == {
        "tpubench.wait_for_request": pytest.approx(15e-6),
        "tpu_dist.serve.step.decode_wait": pytest.approx(3e-6),
        "tpu_dist.serve.step.decode_prep": pytest.approx(1e-6),
        "tpubench.engine_step": pytest.approx(1e-6)}
    assert [name for name, _ in gaps][0] == "tpubench.wait_for_request"
    assert sum(s for _, s in gaps) == pytest.approx(20e-6)
    # Where no span is open the time is named as such, not dropped.
    bare = with_program_spans()
    bare["/host:CPU"]["python"] = [
        e for e in bare["/host:CPU"]["python"]
        if not e[0].startswith("tpubench.") or e[0] == T.WINDOW_SPAN]
    assert dict(T.idle_gaps_by_span(bare)) == {
        "unattributed": pytest.approx(16e-6),
        "tpu_dist.serve.step.decode_wait": pytest.approx(3e-6),
        "tpu_dist.serve.step.decode_prep": pytest.approx(1e-6)}
    assert dict(T.idle_gaps_by_span(bare, n=1)) == {
        "unattributed": pytest.approx(16e-6)}
    ops = dict(T.top_device_ops(hand_made()))
    assert ops["fusion"] == pytest.approx(40e-6)
    assert ops["all-reduce"] == pytest.approx(30e-6)


def test_a_reader_that_finds_nothing_returns_nothing():
    empty = {"trace": {}, "counters": {}, "distributions": {}, "host": {},
             "work": {}, "peaks": None}
    for name, spec in [
            ("device_idle_share", {}),
            ("exposed_collective_share", {}),
            ("trace_flops_share", {"work": "x", "patterns": ["y"]}),
            ("trace_bytes_share", {"work": "x", "patterns": ["y"]}),
            ("kernel_roofline", {"work": "x", "patterns": ["y"]}),
            ("counter_ratio", {"numerator": "a", "denominator": "b"}),
            ("distribution_mean", {"distribution": "d"}),
            ("host_value", {"key": "k"})]:
        assert readers.READERS[name](empty, spec) is None
    # A trace with devices but no event of the kernel: silent, not 0.
    ctx = {"trace": hand_made(), "peaks": {"bf16_flops": 1.0},
           "work": {"k": (1.0, 1.0)}}
    assert readers.kernel_roofline(
        ctx, {"work": "k", "patterns": ["no_such_kernel"]}) is None


def test_counter_readers():
    ctx = {"counters": {"serve.decode.steps": 50},
           "distributions": {
               "serve.prefill.skipped_tokens": {"count": 4, "sum": 512.0},
               "serve.batch.occupancy": {"count": 4, "sum": 3.0}},
           "host": {"prompt_tokens_sent": 2048}}
    spec = {"numerator": "serve.prefill.skipped_tokens",
            "denominator": "host:prompt_tokens_sent", "scale": 100.0}
    assert readers.counter_ratio(ctx, spec) == pytest.approx(25.0)
    assert readers.distribution_mean(
        ctx, {"distribution": "serve.batch.occupancy", "scale": 100.0}
    ) == pytest.approx(75.0)


@pytest.mark.skipif(not (DATA / "recorded_trace.json").is_file(),
                    reason="no recorded trace in this checkout")
def test_the_recorded_trace_reduces_to_what_was_read_by_hand():
    recorded = json.loads((DATA / "recorded_trace.json").read_text())
    t, expect = recorded["trace"], recorded["by_hand"]
    assert T.window_seconds(t) == pytest.approx(expect["window_s"])
    assert T.busy_seconds(t) == pytest.approx(expect["busy_s"])
    for pattern, (seconds, count) in expect["matching"].items():
        got = T.matching_seconds(t, [pattern])
        assert got == (pytest.approx(seconds), count)


@pytest.mark.skipif(not (DATA / "recorded_serve_trace.json").is_file(),
                    reason="no recorded serving trace in this checkout")
def test_the_recorded_serving_trace_charges_its_gaps_to_the_programs_spans():
    """Some rounds of the backlog cell on the chip (``tools/cut_trace.py``):
    the sweep over span edges gives what painting nanoseconds gave, and
    the device's idle time lies in the program's own phases."""
    recorded = json.loads((DATA / "recorded_serve_trace.json").read_text())
    t, expect = recorded["trace"], recorded["by_hand"]
    assert T.window_seconds(t) == pytest.approx(expect["window_s"])
    assert T.busy_seconds(t) == pytest.approx(expect["busy_s"])
    got = T.idle_gaps_by_span(t, n=100)
    assert dict(got) == {k: pytest.approx(v, abs=1e-9)
                         for k, v in expect["idle_by_span_s"].items()}
    assert sum(s for _, s in got) == pytest.approx(
        expect["window_s"] - expect["busy_s"])
    assert [s for _, s in got] == sorted((s for _, s in got), reverse=True)
    in_program = sum(s for name, s in got if name.startswith("tpu_dist."))
    assert in_program > 0.5 * sum(s for _, s in got)
    assert T.idle_gaps_by_span(t) == got[:10]
