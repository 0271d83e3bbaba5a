"""Find the knee of a serving cell once, on the chip: the same engine,
warmed once, under the mix at several offered rates.

    python3 tpubench/tools/sweep.py --workload <cell> --rates 3,4,5,6,7,8 \
        --seconds 25 --seed 1

The knee is the highest rate at which completed tokens/s still follows
the offered tokens/s and the backlog at the close does not grow with the
window. The cell's file then states 0.8 of it as ``rate_rps``: the
benchmark never searches for a rate.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tpubench.harness import cells, device, stats, traffic  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=25.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse", type=int, default=0)
    args = p.parse_args(argv)
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.at_rehearsal_sizes()
    device.configure_compile_cache(ROOT)
    device.require_chips(cell.chips, rehearse=bool(args.rehearse))
    from tpubench.harness.serve_cell import ServeRun

    run = ServeRun(cell, args.seed)
    run.build(args.seconds)
    run.warm_up()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        run.mix = {**cell.mix, "rate_rps": rate}
        run.plan = traffic.plan_requests(
            run.mix, args.seconds, args.seed + i, run.vocab,
            run.engine.max_len)
        host = run.window(args.seconds)
        offered = sum(r.max_new_tokens for r in run.plan) / args.seconds
        late = [r for r in run.reqs if r is not None and (
            r.finish_s is None or r.finish_s > run.closed_s)]
        print(json.dumps({
            "rate_rps": rate, "requests": len(run.plan),
            "offered_tokens_per_s": offered,
            "generated_tokens_per_s":
                host["tokens_generated"] / host["window_s"],
            "completed_tokens_per_s":
                host["tokens_completed"] / host["window_s"],
            "unfinished_at_close": len(late),
            "ttft_p50_ms": stats.percentile(host["ttft_ms"], 50),
            "ttft_p95_ms": stats.percentile(host["ttft_ms"], 95),
            "itl_p50_ms": stats.percentile(host["itl_ms"], 50),
            "itl_p95_ms": stats.percentile(host["itl_ms"], 95),
            "lateness_p95_ms": stats.percentile(host["lateness_ms"], 95),
            "failed": host["failed"], "steps": host["engine_steps"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
