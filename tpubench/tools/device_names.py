"""One traced run of a cell, read for the device time of each program and
of each layer scope.

    python3 tpubench/tools/device_names.py --workload <cell> --seed 1 \
        --seconds 15 --out names.<cell>.json

Runs the cell exactly as ``run.py --trace 1`` does and prints the same
line; beside it, it writes the readings of ``harness/scopes.py`` that the
line does not carry: the metrics of :data:`METRICS` for the cell's kind,
each program's executions and device milliseconds by its module name,
device seconds by layer scope, the operation families of the busy time
under no scope, and what reading the scopes cost. A diagnostic: the
benchmark's numbers do not pass through it.
"""

import argparse
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tpubench import run as bench_run  # noqa: E402
from tpubench.harness import scopes  # noqa: E402
from tpubench.harness import trace as trace_lib  # noqa: E402

#: Per-layer metrics of program and scope device time, as a metric file of
#: ``layer_metrics/`` would give them, by the kind of cell they read.
METRICS = {
    "serve": {
        "serve_decode_device_ms": {
            "reader": "trace_program_ms",
            "patterns": ["^jit_serve_paged_decode"]},
        "serve_chunk_device_ms": {
            "reader": "trace_program_ms",
            "patterns": ["^jit_serve_paged_prefill"]},
        "serve_device_share.attention": {
            "reader": "trace_scope_share",
            "patterns": [r"^tpu_dist\.(attn|mla|gqa)"]},
        "serve_device_share.moe": {
            "reader": "trace_scope_share", "patterns": [r"^tpu_dist\.moe$"]},
        "serve_device_share.kda": {
            "reader": "trace_scope_share", "patterns": [r"^tpu_dist\.kda$"]},
        "serve_device_share.dense": {
            "reader": "trace_scope_share",
            "patterns": [r"^tpu_dist\.dense$"]},
        "serve_device_share.head": {
            "reader": "trace_scope_share",
            "patterns": [r"^tpu_dist\.head$"]},
        "serve_device_share.embed": {
            "reader": "trace_scope_share",
            "patterns": [r"^tpu_dist\.embed$"]},
        "serve_device_share.unscoped": {
            "reader": "trace_scope_share", "complement": True},
    },
    "train": {
        "train_device_share.optimizer": {
            "reader": "trace_scope_share",
            "patterns": [r"^tpu_dist\.optimizer$"]},
        "train_device_share.unscoped": {
            "reader": "trace_scope_share", "complement": True},
    },
}


def programs(trace: dict) -> dict:
    """{module name: {count, device ms a run}} on the first device plane
    inside the window, the name without its fingerprint."""
    planes = trace_lib.device_planes(trace)
    out: dict = {}
    for name, a, b in (trace_lib.ops_in_window(
            trace, planes[0], trace_lib.MODULES_LINE) if planes else ()):
        key = re.sub(r"\(\d+\)$", "", name)
        n, t = out.get(key, (0, 0))
        out[key] = (n + 1, t + b - a)
    return {k: {"count": n, "ms": t / n / 1e6}
            for k, (n, t) in sorted(out.items(), key=lambda kv: -kv[1][1])}


def read(trace: dict, kind: str, sizes: dict) -> dict:
    ctx = {"trace": trace, "sizes": sizes}
    metrics = {}
    for name, spec in METRICS[kind].items():
        value = scopes.READERS[spec["reader"]](ctx, spec)
        if value is not None:
            metrics[name] = value
    return {"metrics": metrics, "programs": programs(trace),
            "scope_seconds": scopes.scope_seconds(trace),
            "unscoped_ops": scopes.unscoped_ops(trace)}


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
    cell = bench_run.load_cell(args)

    def load_and_read(trace_dir):
        t0 = time.perf_counter()
        trace = load(trace_dir)
        t1 = time.perf_counter()
        scopes.with_op_scopes(trace, trace_dir)
        t2 = time.perf_counter()
        report.update(read(trace, cell.kind, cell.sizes))
        report["load_xplane_s"] = t1 - t0
        report["load_op_scopes_s"] = t2 - t1
        report["read_s"] = time.perf_counter() - t2
        report["scope_events"] = sum(
            len(lines.get(scopes.SCOPES_LINE, ())) for lines in trace.values())
        report["op_events"] = sum(
            len(lines.get(trace_lib.OPS_LINE, ())) for lines in trace.values())
        return trace

    trace_lib.load_xplane = load_and_read
    try:
        result = bench_run.run_cell(cell, args)
    finally:
        trace_lib.load_xplane = load
    print(json.dumps(bench_run.result_line(cell, args, result)), flush=True)
    out = pathlib.Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    print(json.dumps({"device_names": report.get("metrics", {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
