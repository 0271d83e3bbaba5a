"""Step-execution benchmark: overlap-aware schedules vs the fused default.

What it measures
----------------
The two step-time knobs this repo's overlap work added, each against its
default-off baseline on the same data and seed:

* **Bucketed gradient all-reduce** (``compile(gradient_bucket_bytes=N)``):
  the explicit shard_map schedule that reduces gradients in size-bounded
  buckets (reverse-topological flush order) instead of one fused
  end-of-step all-reduce. Numerics contract: final losses match the fused
  schedule to allclose (observed bit-identical on this workload — the
  concat/split packing never reassociates the per-leaf reduction).
* **Double-buffered host->device input** (``compile(prefetch_to_device=K)``):
  a background thread device_puts batch k+1 while step k runs. Measured on
  a deliberately slow host pipeline (per-batch ``time.sleep``) via the
  telemetry registry's ``step.data_wait_s`` series — the warm run must cut
  the cold run's data wait by at least ``--data-wait-cut``.

Gates (non-vacuous by construction; exit 1 on failure)
------------------------------------------------------
* loss parity: |fused - bucketed| final loss <= 1e-5 (and per-epoch);
* the bucketed run actually fired >= 2 bucket flushes
  (``collective.bucketed_all_reduce.calls``) — zero buckets = vacuous;
* the prefetch run actually hit the queue (``data.prefetch.hits`` > 0)
  AND cut summed data_wait_s by >= the ratio — zero hits = vacuous;
* both knobs default OFF (``gradient_bucket_bytes == prefetch_to_device
  == 0`` on a fresh compile) — the fused single-launch schedule stays the
  default; bucketing is an overlap knob, not a silent regression;
* no retraces: each schedule's compiled step has ``_cache_size() == 1``
  after its multi-epoch run.

Writes ``BENCH_STEP.json`` (see ``--out``).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_DEVICES = int(os.environ.get("TPU_DIST_BENCH_DEVICES", 1))
if _DEVICES > 1 and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{_DEVICES}").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tpu_dist.data import Dataset
from tpu_dist.models import Dense, Sequential
from tpu_dist.observe import metrics
from tpu_dist.observe.telemetry import Telemetry

FEATURES = 256
CLASSES = 10


def _model(*, bucket_bytes: int = 0, prefetch: int = 0) -> Sequential:
    m = Sequential(
        [Dense(512, activation="relu"), Dense(512, activation="relu"),
         Dense(256, activation="relu"), Dense(CLASSES)],
        input_shape=(FEATURES,))
    m.compile(loss="sparse_categorical_crossentropy", optimizer="sgd",
              metrics=[], gradient_bucket_bytes=bucket_bytes,
              prefetch_to_device=prefetch)
    if _DEVICES > 1:
        from tpu_dist.parallel import MirroredStrategy

        m.strategy = MirroredStrategy()
    return m


def _dataset(*, steps: int, batch: int, delay_s: float = 0.0) -> Dataset:
    rng = np.random.default_rng(7)
    n = steps * batch
    y = rng.integers(CLASSES, size=n).astype(np.int64)
    x = rng.normal(0, 1, (n, FEATURES)).astype(np.float32)
    ds = Dataset.from_tensor_slices((x, y)).batch(batch)
    if delay_s > 0:

        def slow(bx, by):
            time.sleep(delay_s)  # host-side: a slow storage/augment stage
            return bx, by

        ds = ds.map(slow)
    return ds


def _fit_run(*, bucket_bytes: int, prefetch: int, epochs: int, steps: int,
             batch: int, delay_s: float, seed: int) -> dict:
    """One measured fit under Telemetry; returns losses + the registry's
    step.* / data.prefetch.* / collective.bucketed_all_reduce.* view."""
    registry = metrics.get_registry()
    registry.reset()
    metrics.enable()
    try:
        m = _model(bucket_bytes=bucket_bytes, prefetch=prefetch)
        h = m.fit(_dataset(steps=steps, batch=batch, delay_s=delay_s),
                  epochs=epochs, steps_per_epoch=steps, verbose=0,
                  seed=seed, callbacks=[Telemetry(registry=registry)])
        snap = registry.snapshot()
        cache_size = m._trainer._train_step._cache_size()
    finally:
        metrics.disable()
    dists, counters = snap["distributions"], snap["counters"]
    data_wait = dists.get("step.data_wait_s") or {}
    epoch_times = h.history["epoch_time"][1:]  # epoch 0 carries compile
    return {
        "bucket_bytes": bucket_bytes,
        "prefetch_to_device": prefetch,
        "losses": [float(v) for v in h.history["loss"]],
        "final_loss": float(h.history["loss"][-1]),
        "data_wait_sum_s": round(float(data_wait.get("sum", 0.0)), 6),
        "data_wait": data_wait,
        "comm_wait": dists.get("step.comm_wait_s"),
        "prefetch_hits": counters.get("data.prefetch.hits", 0),
        "prefetch_misses": counters.get("data.prefetch.misses", 0),
        "bucket_flushes": counters.get(
            "collective.bucketed_all_reduce.calls", 0),
        "train_step_cache_size": cache_size,
        "steps_per_s": (round(steps * len(epoch_times) / sum(epoch_times), 2)
                        if epoch_times and sum(epoch_times) > 0 else None),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--steps", type=int, default=24,
                   help="steps per epoch (default 24)")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--bucket-bytes", type=int, default=256 * 1024,
                   help="bucket size for the bucketed run (default 256 KiB)")
    p.add_argument("--prefetch-depth", type=int, default=4)
    p.add_argument("--fetch-delay-ms", type=float, default=4.0,
                   help="host-side per-batch delay for the data-wait pair "
                        "(default 4 ms; the step must outlast it for the "
                        "producer thread to hide the wait)")
    p.add_argument("--data-wait-cut", type=float, default=0.50,
                   help="gate: prefetch cuts summed data_wait_s by at "
                        "least this fraction (default 0.50)")
    p.add_argument("--loss-tol", type=float, default=1e-5)
    p.add_argument("--out", default=str(pathlib.Path(__file__).parent.parent
                                        / "BENCH_STEP.json"))
    args = p.parse_args(argv)

    # Warmup absorbs the first jit compile so neither measured pair's
    # epoch-0 skew lands on one schedule only.
    print("warmup (compile)...", file=sys.stderr)
    _fit_run(bucket_bytes=0, prefetch=0, epochs=1, steps=4,
             batch=args.batch, delay_s=0.0, seed=5)

    print("measuring fused schedule...", file=sys.stderr)
    fused = _fit_run(bucket_bytes=0, prefetch=0, epochs=args.epochs,
                     steps=args.steps, batch=args.batch, delay_s=0.0, seed=5)
    print("measuring bucketed schedule...", file=sys.stderr)
    bucketed = _fit_run(bucket_bytes=args.bucket_bytes, prefetch=0,
                        epochs=args.epochs, steps=args.steps,
                        batch=args.batch, delay_s=0.0, seed=5)

    delay_s = args.fetch_delay_ms / 1e3
    print("measuring cold input path (no prefetch)...", file=sys.stderr)
    cold = _fit_run(bucket_bytes=0, prefetch=0, epochs=args.epochs,
                    steps=args.steps, batch=args.batch, delay_s=delay_s,
                    seed=5)
    print("measuring double-buffered input path...", file=sys.stderr)
    warm = _fit_run(bucket_bytes=0, prefetch=args.prefetch_depth,
                    epochs=args.epochs, steps=args.steps, batch=args.batch,
                    delay_s=delay_s, seed=5)

    loss_diffs = [abs(a - b)
                  for a, b in zip(fused["losses"], bucketed["losses"])]
    wait_cut = (1.0 - warm["data_wait_sum_s"] / cold["data_wait_sum_s"]
                if cold["data_wait_sum_s"] > 0 else None)
    fresh = Sequential([Dense(2)], input_shape=(2,))
    fresh.compile(optimizer="sgd", loss="mse")
    gates = {
        "loss_parity_allclose": bool(loss_diffs
                                     and max(loss_diffs) <= args.loss_tol),
        "buckets_fired": bucketed["bucket_flushes"] >= 2,
        "prefetch_hit_queue": warm["prefetch_hits"] > 0,
        "data_wait_cut_met": (wait_cut is not None
                              and wait_cut >= args.data_wait_cut),
        "knobs_default_off": (fresh.gradient_bucket_bytes == 0
                              and fresh.prefetch_to_device == 0),
        "no_retraces": (fused["train_step_cache_size"] == 1
                        and bucketed["train_step_cache_size"] == 1
                        and warm["train_step_cache_size"] == 1),
    }
    report = {
        "bench": "step",
        "config": {"epochs": args.epochs, "steps_per_epoch": args.steps,
                   "batch": args.batch, "bucket_bytes": args.bucket_bytes,
                   "prefetch_depth": args.prefetch_depth,
                   "fetch_delay_ms": args.fetch_delay_ms,
                   "data_wait_cut_gate": args.data_wait_cut,
                   "loss_tol": args.loss_tol, "devices": _DEVICES},
        "fused": fused,
        "bucketed": bucketed,
        "cold_input": cold,
        "prefetched_input": warm,
        "max_abs_loss_diff": (round(max(loss_diffs), 10)
                              if loss_diffs else None),
        "data_wait_cut": round(wait_cut, 4) if wait_cut is not None else None,
        "gates": gates,
        "ok": all(gates.values()),
    }
    out = pathlib.Path(args.out)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report, indent=2))
    print(f"wrote {out}", file=sys.stderr)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
