"""What the program's tracing costs when it is on, measured on the chip.

    python3 tpubench/tools/tracing_cost.py --workload <cell> --seed 1 \
        --seconds 40 --modes off,on,on,off

One process, one line of JSON a window. ``off`` is how the driver
measures (registry disabled, every span a null object), ``on`` enables
the observe registry (spans, counters, the ring) and nothing else,
``telemetry`` (training cells) runs the window under the program's
``Telemetry`` callback. A training cell builds its program once and runs
the windows one after another; a serving cell builds a fresh engine from
the same seed for every window, so that each meets the same traffic with
the same empty prefix cache. The profiler is never started. A builder's
tool: it prints no benchmark result.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tpubench.harness import cells, device, stats  # noqa: E402


def train_windows(cell, args, modes):
    import jax

    from tpu_dist.observe import metrics
    from tpu_dist.observe.telemetry import Telemetry
    from tpubench.harness import train_cell

    run = train_cell.TrainRun(cell, args.seed, chips=cell.chips)
    run.build()
    run.first_steps()
    run.warm_up()
    for mode in modes:
        callbacks = [Telemetry()] if mode == "telemetry" else []
        if mode == "on":
            metrics.get_registry().reset()
            metrics.enable()
        jax.block_until_ready(run.model.variables["params"])
        t0 = time.perf_counter()
        stamper = train_cell._Stamper(deadline=t0 + args.seconds)
        try:
            run._fit(run.epochs_done, 100000, run.steps_per_epoch,
                     callbacks + [stamper])
            jax.block_until_ready(run.model.variables["params"])
        finally:
            if mode == "on":
                metrics.disable()
        window_s = time.perf_counter() - t0
        steps = len(stamper.stamps) * run.steps_per_epoch
        yield {"mode": mode, "window_s": window_s, "steps": steps,
               "train_tokens_per_s":
                   steps * run.global_batch * run.seq / window_s}


def serve_windows(cell, args, modes):
    from tpu_dist.observe import metrics
    from tpubench.harness import serve_cell

    for mode in modes:
        run = serve_cell.ServeRun(cell, args.seed)
        run.build(args.seconds)
        run.warm_up()
        if mode == "on":
            metrics.get_registry().reset()
            metrics.enable()
        try:
            host = run.window(args.seconds)
        finally:
            metrics.disable()
        run.free_program()
        yield {"mode": mode, "window_s": host["window_s"],
               "sent": host["sent"], "failed": host["failed"],
               "serve_tokens_per_s":
                   host["tokens_generated"] / host["window_s"],
               "ttft_p95_ms": stats.percentile(host["ttft_ms"], 95),
               "itl_p95_ms": stats.percentile(host["itl_ms"], 95),
               "itl_p50_ms": stats.percentile(host["itl_ms"], 50),
               "engine_steps": host["engine_steps"],
               "engine_step_max_ms": host["engine_step_max_ms"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--modes", default="off,on,on,off")
    p.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.at_rehearsal_sizes()
    device.configure_compile_cache(ROOT)
    info = device.require_chips(cell.chips, rehearse=bool(args.rehearse))
    modes = args.modes.split(",")
    windows = train_windows if cell.kind == "train" else serve_windows
    for line in windows(cell, args, modes):
        print(json.dumps({"workload": cell.name, "seed": args.seed,
                          "device": info, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
