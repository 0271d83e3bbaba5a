"""Telemetry wiring: step timers, the collective observe hook, and the
``Telemetry`` fit callback.

This is the glue between the passive :mod:`~tpu_dist.observe.metrics`
registry and the places time is actually spent:

* :class:`StepTimer` — the trainer's hot loop (training/trainer.py) wraps
  each compiled execution's **fetch** (host input pipeline) and
  **dispatch** (host->device launch of the jitted program) in
  ``utils.profiler.span`` and hands the two durations here, with the time
  the host really **waited** for the device: the bounded-dispatch wait
  where the backend needs one, else nothing per execution — telemetry
  adds no ``block_until_ready`` of its own, so the program it measures is
  the program that runs without it. The trainer finds the timer through
  :func:`active_step_timer` — a module global, not a callback argument —
  so the hot loop pays one global read when telemetry is off.
* :func:`registry_collective_hook` — plugs into the observe-hook seam in
  ``parallel/collectives.py`` (the sibling of the resilience fault hook)
  and turns every wrapper call into per-op counters (calls, payload
  bytes) and host-wall-time distributions.
* :class:`Telemetry` — the built-in callback that arms all of the above
  for one ``fit`` span, times the epoch-end wait for the loss, exchanges
  per-rank step times (epoch wall time over steps) through
  ``collectives.host_all_gather`` at each epoch end, runs straggler
  detection on the chief, emits ``step_timing`` / ``straggler_detected``
  records into the resilience :mod:`~tpu_dist.resilience.events` log,
  and exports JSONL/Prometheus snapshots.

Like the fault plan, telemetry can ride in through the environment:
``TPU_DIST_OBSERVE_DIR=/some/dir`` makes every ``fit`` in the process
attach a :class:`Telemetry` writing ``metrics.jsonl`` + ``metrics.prom``
there — the Supervisor uses exactly this to instrument chaos workers
without code edits (:func:`maybe_telemetry_from_env`).
"""

from __future__ import annotations

import logging
import os
import time
from pathlib import Path
from typing import Optional

from tpu_dist.observe import exporters, straggler
from tpu_dist.observe import metrics as metrics_lib
from tpu_dist.training.callbacks import Callback

logger = logging.getLogger("tpu_dist.observe")

#: Environment variable arming per-fit telemetry (directory for exports);
#: set by the resilience Supervisor for chaos workers.
OBSERVE_DIR_ENV = "TPU_DIST_OBSERVE_DIR"

#: The StepTimer the trainer's hot loop reports to; None when no Telemetry
#: span is active (the common case — one global read per execution).
_ACTIVE_TIMER: Optional["StepTimer"] = None


def active_step_timer() -> Optional["StepTimer"]:
    return _ACTIVE_TIMER


def set_active_step_timer(timer: Optional["StepTimer"]):
    """Install (or with None, clear) the hot-loop step timer; returns the
    previous one so callers can restore it."""
    global _ACTIVE_TIMER
    prev = _ACTIVE_TIMER
    _ACTIVE_TIMER = timer
    return prev


class StepTimer:
    """Per-execution host timing, recorded as per-step means.

    One compiled execution covers ``steps`` train steps (1, or K under
    ``steps_per_execution``); the split is divided by ``steps`` before
    recording so the distributions are per-step regardless of K. Epoch
    aggregates accumulate alongside for the ``step_timing`` event. What
    the host cannot see per execution — the step's wall time on a
    free-running device — is taken once an epoch (:meth:`record_epoch`).
    """

    def __init__(self, registry: Optional[metrics_lib.MetricsRegistry] = None):
        self.registry = registry or metrics_lib.get_registry()
        r = self.registry
        self._count = r.counter("step.count")
        self._total = r.distribution("step.total_s")
        self._data = r.distribution("step.data_wait_s")
        self._dispatch = r.distribution("step.dispatch_s")
        self._device = r.distribution("step.device_block_s")
        self.reset_epoch()

    def reset_epoch(self) -> None:
        self.epoch_steps = 0
        self.epoch_wall_s = 0.0
        self.epoch_data_wait_s = 0.0
        self.epoch_dispatch_s = 0.0
        self.epoch_device_s = 0.0

    def record_execution(self, *, steps: int, data_wait_s: float,
                         dispatch_s: float,
                         device_block_s: float = 0.0) -> None:
        """One execution's fetch and dispatch (the two spans' durations)
        and the time the host waited for the device inside the loop."""
        if steps <= 0:
            return
        per = 1.0 / steps
        self._count.inc(steps)
        self._data.observe(data_wait_s * per)
        self._dispatch.observe(dispatch_s * per)
        if device_block_s:
            self._device.observe(device_block_s * per)
        self.epoch_steps += steps
        self.epoch_data_wait_s += data_wait_s
        self.epoch_dispatch_s += dispatch_s
        self.epoch_device_s += device_block_s

    def record_epoch(self, *, wall_s: float, loss_wait_s: float) -> None:
        """The epoch as the host clock saw it: ``wall_s`` from its first
        fetch to the arrival of its loss, of which ``loss_wait_s`` was the
        wait for that loss (the device finishing what the host had
        dispatched ahead)."""
        self.epoch_wall_s = wall_s
        self.epoch_device_s += loss_wait_s
        if self.epoch_steps:
            self._total.observe(wall_s / self.epoch_steps)
            self._device.observe(loss_wait_s / self.epoch_steps)

    def epoch_mean_step_s(self) -> float:
        if self.epoch_steps == 0:
            return 0.0
        return self.epoch_wall_s / self.epoch_steps


def registry_collective_hook(
        registry: Optional[metrics_lib.MetricsRegistry] = None):
    """A collective observe hook (``parallel/collectives.py`` seam) that
    records per-op calls, payload bytes, and host wall time into a
    registry. Trace-time firings (a wrapper traced into a jitted program
    runs once at trace time, not per step) are counted separately so a
    reader never mistakes compile-time activity for steady-state traffic.
    """
    r = registry or metrics_lib.get_registry()

    def hook(op: str, *, phase: str, leaves: int, nbytes: int,
             seconds: Optional[float] = None) -> None:
        r.counter(f"collective.{op}.calls").inc()
        if phase == "trace":
            r.counter(f"collective.{op}.trace_calls").inc()
        if nbytes:
            r.counter(f"collective.{op}.bytes").inc(nbytes)
        if seconds is not None:
            r.distribution(f"collective.{op}.host_seconds").observe(seconds)
            if phase != "trace":
                # Host-visible collective wait, aggregated across ops —
                # the measured sibling of the cost model's comm tail.
                r.distribution("step.comm_wait_s").observe(seconds)

    return hook


class Telemetry(Callback):
    """Arm metrics + collective telemetry + straggler detection for one fit.

    Scoped strictly to the fit span: ``on_train_begin`` resets and enables
    the registry (each span's series starts from a clean slate — sequential
    fits on the shared default registry must not bleed counts into each
    other), installs the collective observe hook and the hot-loop step
    timer; ``on_train_end`` restores every previous state, so sequential
    fits compose. Exports are optional — without paths the callback only
    populates the registry (and the event log, if armed).
    """

    def __init__(self, *,
                 jsonl_path: Optional[str | os.PathLike] = None,
                 prometheus_path: Optional[str | os.PathLike] = None,
                 registry: Optional[metrics_lib.MetricsRegistry] = None,
                 straggler_threshold: float = straggler.DEFAULT_THRESHOLD):
        self.registry = registry or metrics_lib.get_registry()
        self.jsonl_path = jsonl_path
        self.prometheus_path = prometheus_path
        self.straggler_threshold = straggler_threshold
        self.timer: Optional[StepTimer] = None
        self._exporter: Optional[exporters.JsonlExporter] = None
        self._prev_hook = None
        self._prev_timer = None
        self._prev_registry = None
        self._was_enabled = False
        self._armed = False
        self._t_epoch = 0.0

    # -- lifecycle -----------------------------------------------------------

    def on_train_begin(self) -> None:
        from tpu_dist.parallel import collectives

        self._was_enabled = self.registry.enabled
        self.registry.reset()
        self.registry.enable()
        # The program's spans and counters go to the default registry:
        # for this fit that is the one the step timer reads.
        self._prev_registry = metrics_lib.install_registry(self.registry)
        self._prev_hook = collectives.install_observe_hook(
            registry_collective_hook(self.registry))
        self.timer = StepTimer(self.registry)
        self._prev_timer = set_active_step_timer(self.timer)
        if self.jsonl_path is not None:
            self._exporter = exporters.JsonlExporter(self.jsonl_path)
        self._armed = True

    def on_train_end(self) -> None:
        if not self._armed:
            return
        from tpu_dist.parallel import collectives

        self._export(kind="final", epoch=None)
        collectives.install_observe_hook(self._prev_hook)
        set_active_step_timer(self._prev_timer)
        metrics_lib.install_registry(self._prev_registry)
        if not self._was_enabled:
            self.registry.disable()
        if self._exporter is not None:
            self._exporter.close()
            self._exporter = None
        self._armed = False

    def on_epoch_begin(self, epoch: int) -> None:
        if self.timer is not None:
            self.timer.reset_epoch()
        self._t_epoch = time.perf_counter()

    # -- per-epoch aggregation -----------------------------------------------

    def on_epoch_end(self, epoch: int, logs: dict) -> None:
        if not self._armed:
            return
        import numpy as np

        from tpu_dist.parallel import collectives
        from tpu_dist.resilience import events

        r = self.registry
        timer = self.timer
        # The one wait telemetry makes, and one the fit makes anyway when
        # anything reads the epoch's loss: until the device has finished
        # what the host dispatched ahead. ``logs["epoch_time"]`` stops
        # before it, so the epoch's wall time is taken here, from
        # on_epoch_begin to the loss's arrival.
        t_wait = time.perf_counter()
        if hasattr(logs, "materialize"):
            logs.materialize()
        now = time.perf_counter()
        epoch_time = now - self._t_epoch
        timer.record_epoch(wall_s=epoch_time, loss_wait_s=now - t_wait)
        steps = timer.epoch_steps
        if steps and epoch_time > 0:
            r.gauge("epoch.steps_per_s").set(steps / epoch_time)
        mean_step = timer.epoch_mean_step_s()

        # Cross-rank exchange of this epoch's mean step time. Runs through
        # the instrumented host collective, so even a single-process run
        # records collective traffic (and its host wall time) — the demo's
        # non-vacuity check depends on this.
        per_rank = collectives.host_all_gather(np.float32(mean_step))
        per_rank = [float(t) for t in np.asarray(per_rank).reshape(-1)]
        for rank_i, t in enumerate(per_rank):
            r.gauge(f"rank{rank_i}.step_time_s").set(t)

        import jax

        rank = jax.process_index()
        events.maybe_log(
            "step_timing", rank=rank, epoch=epoch, steps=steps,
            mean_step_s=round(mean_step, 6),
            data_wait_s=round(timer.epoch_data_wait_s, 6),
            dispatch_s=round(timer.epoch_dispatch_s, 6),
            device_s=round(timer.epoch_device_s, 6))

        from tpu_dist.cluster import bootstrap

        if bootstrap.is_chief():
            for verdict in straggler.detect_stragglers(
                    per_rank, threshold=self.straggler_threshold):
                r.counter("straggler.flags").inc()
                logger.warning(
                    "straggler: rank %d at %.4fs/step, %.1fx the gang "
                    "median", verdict.rank, verdict.step_s, verdict.ratio)
                events.maybe_log("straggler_detected", epoch=epoch,
                                 **verdict.to_dict())
        self._export(kind="epoch", epoch=epoch)

    def _export(self, *, kind: str, epoch: Optional[int]) -> None:
        if self._exporter is None and self.prometheus_path is None:
            return
        snapshot = self.registry.snapshot()
        stamp = {"kind": kind}
        if epoch is not None:
            stamp["epoch"] = epoch
        try:
            if self._exporter is not None:
                self._exporter.write(snapshot, **stamp)
            if self.prometheus_path is not None:
                exporters.write_prometheus_textfile(
                    snapshot, self.prometheus_path)
        except OSError as exc:  # diagnostics must never kill the run
            logger.warning("telemetry export failed: %s", exc)


def maybe_telemetry_from_env() -> Optional[Telemetry]:
    """A :class:`Telemetry` writing under ``$TPU_DIST_OBSERVE_DIR``, or None
    when the variable is unset — the trainer calls this in ``fit`` so a
    Supervisor (or a shell) can instrument any run without code edits."""
    d = os.environ.get(OBSERVE_DIR_ENV)
    if not d:
        return None
    base = Path(d)
    return Telemetry(jsonl_path=base / "metrics.jsonl",
                     prometheus_path=base / "metrics.prom")
