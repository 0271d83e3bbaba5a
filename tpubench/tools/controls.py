"""Read the controls and planted faults of a cell on the chip, several
seeds in one process. The benchmark's own runs never run this; ``PERF.md``
records what it read, and the limits in the mix files stand between its
readings and the program's.

    python3 tpubench/tools/controls.py --workload <cell> --seeds 1,2,3 \
        [--program 1] [--seconds 15]

Training: the reference (float32, ``highest``) stands in the program's
place in each variant and is held against the clean reference:
``fp8`` (every matmul operand rounded to e4m3: the precision below the
bf16 the cell states), ``half_batch`` (half of every batch left out, the
mean over the rest), ``one_shard`` (four-chip cells: one chip's rows
only, what a replica computes when the exchange is left out) and
``frozen`` (a step that returns its state unchanged). With ``--program 1``
the program's own first steps are read too, for the lower readings.

Serving: the program serves a short window at the cell's own load; the
sample is judged as a run judges it, and the ``fp8`` control is read at
the same positions.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tpubench.harness import cells, checks, device  # noqa: E402


def _public(numbers):
    return {k: v for k, v in numbers.items() if not k.startswith("_")}


def train_readings(cell, seed, with_program):
    from tpubench.harness.train_cell import TrainRun

    run = TrainRun(cell, seed, chips=cell.chips)
    out = {}
    if with_program:
        run.build()
        run.first_steps()
        run.free_program()
    else:
        from tpubench.harness import traffic

        rows = run.global_batch * 3
        run.x, run.y = traffic.token_rows(seed, run.vocab, rows, run.seq)
    ref = run.reference_numbers()
    if with_program:
        out["program"] = checks.train_numbers(run.prog, ref)
    variants = {"fp8": dict(quant="fp8"), "half_batch": dict(keep_rows=0.5),
                "frozen": dict(freeze=True)}
    if cell.chips > 1:
        variants["one_shard"] = dict(keep_rows=1.0 / cell.chips)
    for name, kw in variants.items():
        out[name] = _public(checks.train_numbers(
            run.reference_numbers(**kw), ref))
    return out


def serve_readings(cell, seed, seconds):
    from tpubench.harness.serve_cell import ServeRun

    run = ServeRun(cell, seed)
    run.build(seconds)
    run.warm_up()
    host = run.window(seconds)
    run.free_program()
    return {"program": _public(run.reference_numbers()),
            "fp8": _public(run.reference_numbers(quant="fp8")),
            "served": len(run.served), "failed": host["failed"]}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--program", type=int, default=0)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--rehearse", type=int, default=0)
    args = p.parse_args(argv)
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.at_rehearsal_sizes()
    device.configure_compile_cache(ROOT)
    # Without --program a training cell's variants are all the reference
    # standing in the program's place: one chip holds them.
    needs = cell.chips if (args.program or cell.kind == "serve") else 1
    device.require_chips(needs, rehearse=bool(args.rehearse))
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if cell.kind == "train":
            out = train_readings(cell, seed, bool(args.program))
        else:
            out = serve_readings(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "seconds": round(time.perf_counter() - t, 1),
                          **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
