"""One traced run of a cell, read for the program's own spans.

    python3 tpubench/tools/program_spans.py --workload <cell> --seed 1 \
        --seconds 40 --out chiprun_out/spans.<cell>.json

Runs the cell exactly as ``run.py --trace 1`` does and prints the same
line; beside it, it writes what the result line has no room for: every
``tpu_dist.*`` and ``tpubench.*`` host span of the trace with its count
and seconds, how many program spans lie inside a harness span, the
device's idle time by innermost span in full (``harness/trace.py``; the
line's ``breakdown`` keeps the first ten), the registry's span
distributions as a per-phase split, and the longest serving round of the
span ring with its children and any compile record inside it. A builder's
tool: the benchmark's numbers do not pass through it.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tpubench import run as bench_run  # noqa: E402
from tpubench.harness import trace as trace_lib  # noqa: E402

PROGRAM_PREFIX = trace_lib.PROGRAM_PREFIX


def read_trace(trace: dict) -> dict:
    spans = trace_lib.host_spans(trace)
    program = [s for s in spans if s[0].startswith(PROGRAM_PREFIX)]
    harness = [s for s in spans if s[0].startswith(trace_lib.SPAN_PREFIX)
               and s[0] != trace_lib.WINDOW_SPAN]
    inside = sum(any(h[1] <= s[1] and s[1] + s[2] <= h[1] + h[2]
                     for h in harness) for s in program)
    totals: dict = {}
    for name, _, dur in spans:
        n, t = totals.get(name, (0, 0))
        totals[name] = (n + 1, t + dur)
    device = [(a, b) for p in trace_lib.device_planes(trace)
              for _, a, b in trace_lib.ops_in_window(trace, p)]
    return {
        "host_lines": sorted(
            f"{plane}/{line}" for plane, lines in trace.items()
            if not trace_lib.DEVICE_PLANE.match(plane)
            for line, events in lines.items()
            if any(e[0].startswith(PROGRAM_PREFIX) for e in events)),
        "program_spans": len(program),
        "program_spans_inside_a_harness_span": inside,
        "spans": {k: {"count": n, "seconds": t / 1e9}
                  for k, (n, t) in sorted(totals.items())},
        "program_span_extent_ns": ([min(s[1] for s in program),
                                    max(s[1] + s[2] for s in program)]
                                   if program else None),
        "device_op_extent_ns": ([min(a for a, _ in device),
                                 max(b for _, b in device)]
                                if device else None),
        "idle_by_innermost_span": trace_lib.idle_gaps_by_span(trace, n=100)}


def longest_round(ring: list) -> dict:
    rounds = [s for s in ring if s["name"] == "serve.step"]
    if not rounds:
        return {}
    top = max(rounds, key=lambda s: s["end"] - s["start"])
    inside = [s for s in ring if s is not top
              and top["start"] <= s["start"] and s["end"] <= top["end"]
              and (s["name"].startswith("serve.step.")
                   or s["name"] in ("compile", "serve.program.build"))]
    return {"ident": top["ident"], "ms": 1e3 * (top["end"] - top["start"]),
            "inside": [[s["name"], 1e3 * (s["end"] - s["start"]),
                        s["ident"]] for s in inside]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", required=True)
    a = p.parse_args(argv)
    args = bench_run.parse([
        "--workload", a.workload, "--seed", str(a.seed), "--seconds",
        str(a.seconds), "--trace", "1", "--rehearse", str(a.rehearse)])
    report: dict = {}
    load = trace_lib.load_xplane

    def load_and_read(trace_dir):
        trace = load(trace_dir)
        report["trace"] = read_trace(trace)
        return trace

    trace_lib.load_xplane = load_and_read
    try:
        cell = bench_run.load_cell(args)
        result = bench_run.run_cell(cell, args)
    finally:
        trace_lib.load_xplane = load
    print(json.dumps(bench_run.result_line(cell, args, result)), flush=True)

    from tpu_dist.observe import metrics

    host = result["host"]
    report["phases_ms"] = {
        k: {"count": d["count"], "mean": 1e3 * d["sum"] / d["count"],
            "p50": 1e3 * d["p50"], "max": 1e3 * d["max"]}
        for k, d in host.get("distributions", {}).items()
        if d.get("count") and (k.startswith(("span.", "step.", "compile."))
                               or k.endswith("_s"))}
    report["counters"] = host.get("counters", {})
    report["longest_round"] = longest_round(
        metrics.get_registry().snapshot()["spans"])
    report["engine_step_max_ms"] = host.get("engine_step_max_ms")
    out = pathlib.Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
