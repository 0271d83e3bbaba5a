"""Run one cell of the benchmark once.

    python3 tpubench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it finds the chip (or fails: never the CPU), builds the cell
named in ``BENCHMARK.json`` from its files, warms up every shape, measures
for ``--seconds``, reads the device's memory, frees the program, holds what
the timed path produced against the plain reference, and prints one JSON
object as the last line of standard output. With ``--trace 0`` the line
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics. ``--rehearse 1`` lets the same code run at the configuration's
``rehearsal`` sizes on any backend: it prints ``"correct": false`` and no
metric, and exists for the benchmark's own tests.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tpubench.harness import cells, checks, device, readers  # noqa: E402
from tpubench.harness import trace as trace_lib  # noqa: E402

EXIT_NO_CHIP = 3
OUT_DIR = ".tpubench_out"


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=str(ROOT),
                   help="checkout to read BENCHMARK.json from (tests)")
    return p.parse_args(argv)


def load_cell(args):
    cell = cells.Cell(args.workload, pathlib.Path(args.root))
    return cell.at_rehearsal_sizes() if args.rehearse else cell


def run_cell(cell, args, *, sabotage=None, t_start=None) -> dict:
    """Everything of a run after the arguments are read; the benchmark's
    tests call this with ``sabotage`` to break the timed path."""
    root = pathlib.Path(args.root)
    device.configure_compile_cache(root)
    info = device.require_chips(cell.chips, rehearse=bool(args.rehearse))
    meter = device.CompileMeter()
    trace_dir = None
    if args.trace:
        trace_dir = root / OUT_DIR / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    ctx = {"t_start": t_start if t_start is not None else T_START,
           "meter": meter, "trace_dir": trace_dir, "sabotage": sabotage,
           "memory_peak": lambda: device.memory_peak_bytes(cell.chips)}
    if cell.kind == "train":
        from tpubench.harness import train_cell as runner
    elif cell.kind == "serve":
        from tpubench.harness import serve_cell as runner
    else:
        raise ValueError(f"mix kind {cell.kind!r} has no runner")
    try:
        result = runner.run_cell(cell, args, ctx)
        result["device"] = {**info,
                            "memory_peak_bytes": result["memory_peak_bytes"]}
        rows = checks.judge(result["numbers"], cell.mix["limits"])
        rows.append({"name": "compiles_in_window",
                     "value": result["host"]["compiles_in_window"],
                     "limit": 0,
                     "ok": result["host"]["compiles_in_window"] == 0})
        rows.append({"name": "failed", "value": result["failed"], "limit": 0,
                     "ok": result["failed"] == 0})
        result["rows"] = rows
        result["checks_ok"] = all(r["ok"] for r in rows)
        if trace_dir is not None:
            read_layers(cell, result, trace_dir, info)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def read_layers(cell, result, trace_dir, info):
    """The per-layer metrics of a traced run, each by its own reader."""
    from tpubench.harness import peaks

    try:
        trace = trace_lib.load_xplane(trace_dir)
    except FileNotFoundError:
        trace = {}
    host = result["host"]
    ctx = {"counters": host.get("counters", {}),
           "distributions": host.get("distributions", {}),
           "host": host, "trace": trace, "work": result["work"],
           "chips": cell.chips,
           "sizes": {**cell.sizes,
                     **{k: v for k, v in cell.mix.get("engine", {}).items()
                        if isinstance(v, int)}},
           "peaks": (peaks.peaks_for(info["kind"])
                     if info["platform"] == "tpu" else None)}
    layers = {}
    for metric in cell.per_layer:
        spec = cell.metric_spec(metric["name"])
        value = readers.read_metric(ctx, spec)
        if value is not None:
            layers[metric["name"]] = {"value": value, "unit": metric["unit"]}
    result["per_layer"] = layers
    if trace_lib.device_planes(trace):
        result["device"]["busy_s"] = trace_lib.busy_seconds(trace)
        result["device"]["window_s"] = trace_lib.window_seconds(trace)
        result["breakdown"] = {
            "device_ops": trace_lib.top_device_ops(trace),
            "idle_gaps": trace_lib.idle_gaps_by_span(trace)}
    # For a person's one look at a real trace, and for cutting the small
    # recorded trace the tests pin the reduction on.
    for var, make in (("TPUBENCH_DESCRIBE_TRACE", trace_lib.describe),
                      ("TPUBENCH_KEEP_TRACE", lambda t: t)):
        if os.environ.get(var):
            out = pathlib.Path(os.environ[var])
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(make(trace)))


def result_line(cell, args, result) -> dict:
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if args.rehearse:
        metrics, correct = {}, False
    elif args.trace:
        metrics, correct = result.get("per_layer", {}), result["checks_ok"]
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in result["end_to_end"].items() if k in units}
        correct = result["checks_ok"]
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": result["device"]}
    if args.trace and "breakdown" in result and not args.rehearse:
        line["breakdown"] = result["breakdown"]
    if args.rehearse:
        line["rehearsal"] = {"checks_ok": result["checks_ok"],
                             "end_to_end": result["end_to_end"],
                             "per_layer": result.get("per_layer", {})}
    line["host"] = {k: v for k, v in result["host"].items()
                    if isinstance(v, (int, float))}
    line["compared"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in result["rows"]}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = load_cell(args)
        result = run_cell(cell, args)
    except device.NoChip as exc:
        print(f"tpubench: {exc}", file=sys.stderr)
        return EXIT_NO_CHIP
    line = result_line(cell, args, result)
    print(json.dumps(line), flush=True)
    for r in result["rows"]:
        print(f"tpubench compared {r['name']}: {r['value']!r} "
              f"(limit {r['limit']!r}) {'ok' if r['ok'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
