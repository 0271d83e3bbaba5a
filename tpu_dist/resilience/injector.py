"""FaultInjector: executes a FaultPlan from inside the training loop.

The injector is a standard :class:`~tpu_dist.training.callbacks.Callback` —
the same hook surface the reference's chaos tooling rode
(``multi_process_runner`` killing workers between steps, SURVEY.md §4) —
plus two seams it installs for the fault kinds a callback alone cannot
reach:

* :func:`tpu_dist.parallel.collectives.install_fault_hook` for
  ``delay_collective`` / ``hang_collective`` — host-level collectives
  (barriers, chief broadcasts, host reductions) stall as if the fabric did;
* :func:`tpu_dist.training.checkpoint.install_write_fault_hook` for
  ``checkpoint_fail`` — a staged-but-unpublished checkpoint write either
  raises (``transient``) or is corrupted in place (``truncate``) — and for
  ``kill_during_save`` — ``os._exit`` from inside the seam, i.e. a death
  with the checkpoint staged but unpublished. Under the async pipeline the
  seam runs on the background writer thread (``os._exit`` kills the whole
  process regardless of thread), making this the deterministic mid-async-
  save preemption.
* :func:`tpu_dist.training.integrity.install_batch_fault_hook` for the
  SEMANTIC faults ``nan_loss`` / ``grad_spike`` / ``corrupt_batch`` — the
  target step's batch is poisoned right before dispatch, so the fault is
  indistinguishable (to the trainer) from bad data or numerics. ``bitflip``
  rides ``on_batch_end`` instead: it corrupts one replica's copy of a
  parameter via :func:`tpu_dist.training.integrity.flip_param_bit` — silent
  data corruption only the cross-replica SDC audit can see.

Step accounting: ``on_batch_end(step, logs)`` fires once per compiled
execution with the in-epoch step index; the injector tracks the GLOBAL step
as ``epoch * steps_per_epoch + step`` so fault coordinates survive resume
(a restarted run that restores epoch N re-enters the loop at the same
global step numbering). ``FaultSpec.due_at_step`` uses ``>=``, so
``steps_per_execution > 1`` cannot jump past a target.

Kills are ``os._exit(exit_code)`` — no Python cleanup, no atexit, no
``jax.distributed.shutdown``: the closest single-process analog of a
preempted host.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional, Sequence

from tpu_dist.resilience import events
from tpu_dist.resilience import faults as faults_mod
from tpu_dist.resilience.faults import (FaultPlan, FaultSpec, HANG_SECONDS)
from tpu_dist.training.callbacks import Callback

logger = logging.getLogger("tpu_dist.resilience")


def integrity_mod():
    """Lazy import of :mod:`tpu_dist.training.integrity` — the injector is
    imported by plan-parsing tests before jax is configured, so training
    modules load only when an integrity fault is actually armed."""
    from tpu_dist.training import integrity

    return integrity


class FaultInjector(Callback):
    """Arms a process's slice of a FaultPlan for one fit() run."""

    wants_batches = True  # global-step tracking needs per-execution hooks

    def __init__(self, faults: Sequence[FaultSpec], *, steps_per_epoch: int,
                 event_log: Optional[events.EventLog] = None):
        self.faults = list(faults)
        self.steps_per_epoch = int(steps_per_epoch)
        self._events = event_log
        #: Remaining firings per fault (specs are frozen; state lives here).
        self._remaining = [f.count for f in self.faults]
        self._epoch = 0
        self._global_step = 0
        self._prev_collective_hook = None
        self._prev_write_hook = None
        self._prev_batch_hook = None
        self._installed = False

    # -- event plumbing ------------------------------------------------------

    def _log(self, event: str, **fields) -> None:
        try:
            log = self._events or events.log_from_env()
            if log is not None:
                log.append(event, attempt=events.current_attempt(), **fields)
        except OSError:  # observability must never fail the run
            pass

    # -- seam installation ---------------------------------------------------

    def on_train_begin(self) -> None:
        if any(f.kind in ("delay_collective", "hang_collective")
               for f in self.faults):
            from tpu_dist.parallel import collectives

            self._prev_collective_hook = collectives.install_fault_hook(
                self._collective_hook)
        if any(f.kind in ("checkpoint_fail", "kill_during_save")
               for f in self.faults):
            from tpu_dist.training import checkpoint

            self._prev_write_hook = checkpoint.install_write_fault_hook(
                self._write_hook)
        if any(f.kind in integrity_mod().BATCH_FAULT_KINDS
               for f in self.faults):
            self._prev_batch_hook = integrity_mod().install_batch_fault_hook(
                self._batch_hook)
        self._installed = True
        for f in self.faults:
            self._log("fault_armed", kind=f.kind, step=f.step, epoch=f.epoch,
                      rank=f.rank)
        if events.current_attempt() > 0:
            self._log("resumed")

    def on_train_end(self) -> None:
        if not self._installed:
            return
        self._installed = False
        if any(f.kind in ("delay_collective", "hang_collective")
               for f in self.faults):
            from tpu_dist.parallel import collectives

            collectives.install_fault_hook(self._prev_collective_hook)
        if any(f.kind in ("checkpoint_fail", "kill_during_save")
               for f in self.faults):
            from tpu_dist.training import checkpoint

            checkpoint.install_write_fault_hook(self._prev_write_hook)
        if any(f.kind in integrity_mod().BATCH_FAULT_KINDS
               for f in self.faults):
            integrity_mod().install_batch_fault_hook(self._prev_batch_hook)

    # -- firing --------------------------------------------------------------

    def on_epoch_begin(self, epoch: int) -> None:
        self._epoch = epoch
        self._global_step = epoch * self.steps_per_epoch
        for i, f in enumerate(self.faults):
            if (f.kind in ("kill", "preempt") and self._remaining[i] > 0
                    and f.step is None and f.due_at_epoch(epoch)):
                if f.kind == "kill":
                    self._fire_kill(i, f, at=f"epoch {epoch}")
                else:
                    self._fire_preempt(i, f, at=f"epoch {epoch}")

    def on_batch_end(self, step: int, logs: dict) -> None:
        # ``step`` is the in-epoch index of the last step in the execution
        # that just finished; faults address the GLOBAL step so their
        # coordinates are stable across resume.
        gstep = self._epoch * self.steps_per_epoch + step
        self._global_step = gstep
        for i, f in enumerate(self.faults):
            if self._remaining[i] <= 0 or f.step is None:
                continue
            if not f.due_at_step(gstep):
                continue
            if f.kind == "kill":
                self._fire_kill(i, f, at=f"step {gstep}")
            elif f.kind == "job_kill":
                # Same hard death as ``kill``, but scoped to ONE packed
                # job: maybe_injector_from_env only arms it in the gang
                # whose $TPU_DIST_JOB_INDEX matches the @jobN coordinate,
                # so neighbors on the other submesh slices never see it.
                self._fire_kill(i, f, at=f"job {f.job} step {gstep}",
                                kind="job_kill")
            elif f.kind == "job_hang":
                self._remaining[i] -= 1
                self._log("fault_fired", kind="job_hang", job=f.job,
                          step=gstep, seconds=f.seconds)
                logger.warning("fault injection: hanging job %s worker "
                               "%.1fs at step %d", f.job, f.seconds, gstep)
                time.sleep(f.seconds)
            elif f.kind == "preempt":
                self._fire_preempt(i, f, at=f"step {gstep}")
            elif f.kind == "slow_input":
                self._remaining[i] -= 1
                self._log("fault_fired", kind=f.kind, step=gstep,
                          seconds=f.seconds)
                time.sleep(f.seconds)
            elif f.kind == "bitflip":
                # Silent data corruption: flip one bit of one device's
                # copy/shard of the addressed parameter leaf (:leafK,
                # default 0; :replicaR, default the fault's rank). Nothing
                # in the step will notice — only the SDC audit's
                # shard-group checksum compare can. The flipped state is
                # consumed by the NEXT dispatch.
                self._remaining[i] -= 1
                trainer = getattr(self.model, "_trainer", None)
                if trainer is None or trainer.variables is None:
                    self._log("fault_skipped", kind="bitflip", step=gstep,
                              reason="no live trainer variables")
                    continue
                info = integrity_mod().flip_param_bit(
                    trainer.variables,
                    replica=f.rank if f.replica is None else f.replica,
                    leaf=0 if f.leaf is None else f.leaf)
                self._log("fault_fired", kind="bitflip", step=gstep, **info)
                logger.warning("fault injection: flipped bit %d (effective "
                               "%d) of %s on replica %d at step %d",
                               info["bit"], info["effective_bit"],
                               info["leaf"], info["replica"], gstep)

    def _fire_kill(self, i: int, f: FaultSpec, *, at: str,
                   kind: str = "kill") -> None:
        self._remaining[i] -= 1
        self._log("fault_fired", kind=kind, at=at, exit_code=f.exit_code)
        logger.warning("fault injection: killing process at %s "
                       "(exit %d)", at, f.exit_code)
        os._exit(f.exit_code)

    def _fire_preempt(self, i: int, f: FaultSpec, *, at: str) -> None:
        """Deliver a REAL SIGTERM to this process — the graceful preemption.

        Unlike ``kill`` this does not end the process here: the SIGTERM seam
        (:func:`tpu_dist.resilience.entrypoints.install_sigterm_handler`)
        records the request and the :class:`PreemptionDrain` callback stops
        training at this very step boundary, so the whole production drain
        path runs under the fault. Without the seam installed, SIGTERM's
        default action kills the process (exit -15) — also a legitimate
        chaos outcome (an UNgraceful worker).
        """
        import signal

        self._remaining[i] -= 1
        self._log("fault_fired", kind="preempt", at=at)
        logger.warning("fault injection: delivering SIGTERM to self at %s",
                       at)
        os.kill(os.getpid(), signal.SIGTERM)
        # The Python-level handler runs on this (main) thread at the next
        # bytecode boundary; yield until it has, so the drain callback later
        # in this same callback round deterministically sees the request.
        from tpu_dist.resilience import entrypoints

        if entrypoints.preemption_armed():
            deadline = time.monotonic() + 5.0
            while (not entrypoints.preemption_requested()
                   and time.monotonic() < deadline):
                time.sleep(0.001)

    # -- seam hooks ----------------------------------------------------------

    def _collective_hook(self, op: str) -> None:
        for i, f in enumerate(self.faults):
            if f.kind not in ("delay_collective", "hang_collective"):
                continue
            if self._remaining[i] <= 0:
                continue
            due = (f.due_at_step(self._global_step) if f.step is not None
                   else f.due_at_epoch(self._epoch))
            if not due:
                continue
            self._remaining[i] -= 1
            seconds = (HANG_SECONDS if f.kind == "hang_collective"
                       else f.seconds)
            self._log("fault_fired", kind=f.kind, op=op, seconds=seconds)
            logger.warning("fault injection: stalling collective %r for "
                           "%.1fs", op, seconds)
            time.sleep(seconds)
        if self._prev_collective_hook is not None:
            self._prev_collective_hook(op)

    def _write_hook(self, stage_dir, step: int) -> None:
        # ``step`` here is the CHECKPOINT's step coordinate (the epoch number
        # for ModelCheckpoint's per-epoch saves), matched against the fault's
        # epoch when one is given. Under the async pipeline this hook runs on
        # the background writer thread — fine for both effects (raising is
        # delivered at the next commit point; os._exit is process-wide).
        for i, f in enumerate(self.faults):
            if (f.kind not in ("checkpoint_fail", "kill_during_save")
                    or self._remaining[i] <= 0):
                continue
            due = (f.due_at_epoch(step) if f.epoch is not None
                   else f.due_at_step(step))
            if not due:
                continue
            self._remaining[i] -= 1
            if f.kind == "kill_during_save":
                self._log("fault_fired", kind="kill_during_save", step=step,
                          exit_code=f.exit_code)
                logger.warning(
                    "fault injection: killing process during checkpoint "
                    "save of step %d (stage %s unpublished, exit %d)",
                    step, stage_dir, f.exit_code)
                os._exit(f.exit_code)
            self._log("fault_fired", kind="checkpoint_fail", mode=f.mode,
                      step=step)
            if f.mode == "transient":
                raise OSError(
                    f"injected transient checkpoint write failure at "
                    f"step {step}")
            _truncate_stage(stage_dir)
        if self._prev_write_hook is not None:
            self._prev_write_hook(stage_dir, step)

    def _batch_hook(self, first_gstep: int, k: int, x, y):
        """Poison the batch of a due semantic fault (pre-dispatch seam).

        Fires when the execution window ``[first_gstep, first_gstep + k)``
        reaches the fault's step (same ``>=`` semantics as ``due_at_step``,
        so multi-step windows cannot jump past a target); the count is
        consumed, so a post-rollback replay of the same window trains on
        the CLEAN batch — that is what makes exact loss parity possible.
        """
        import jax.numpy as jnp

        for i, f in enumerate(self.faults):
            if (f.kind not in integrity_mod().BATCH_FAULT_KINDS
                    or self._remaining[i] <= 0 or f.step is None
                    or f.step >= first_gstep + k):
                continue
            self._remaining[i] -= 1
            self._log("fault_fired", kind=f.kind, step=f.step,
                      window_start=first_gstep, window=k)
            logger.warning("fault injection: %s poisoning batch window "
                           "[%d, %d)", f.kind, first_gstep, first_gstep + k)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.integer):
                # Token batches (LMs): an int id stream has no NaN to
                # multiply in, and embedding reads clamp out-of-range ids,
                # so poisoning x alone would be silently absorbed. Real
                # buffer corruption of an id batch lands out-of-range
                # LABELS too, and the label gather's fill semantics
                # (take_along_axis) surface those as a nonfinite loss the
                # guard catches — so poison y far outside any vocab;
                # corrupt_batch/grad_spike additionally garble x so the
                # poisoned window provably trained on different tokens.
                bad = jnp.asarray(2 ** 30, jnp.asarray(y).dtype)
                garble = jnp.asarray(-7, jnp.asarray(x).dtype)
                if k > 1 and f.step - first_gstep < x.shape[0]:
                    s = f.step - first_gstep
                    y = y.at[s].set(bad)
                    if f.kind != "nan_loss":
                        x = x.at[s].multiply(garble)
                else:
                    y = jnp.full_like(y, bad)
                    if f.kind != "nan_loss":
                        x = x * garble
                continue
            if f.kind == "nan_loss":
                scale = jnp.asarray(float("nan"), x.dtype)
            elif f.kind == "grad_spike":
                scale = jnp.asarray(1e6, x.dtype)
            else:  # corrupt_batch: wildly out-of-distribution features
                scale = jnp.asarray(-1e7, x.dtype)
            if k > 1 and f.step - first_gstep < x.shape[0]:
                # Stacked multi-step window: poison only the target step's
                # slice so the window's other steps stay faithful.
                x = x.at[f.step - first_gstep].multiply(scale)
            else:
                x = x * scale
        if self._prev_batch_hook is not None:
            return self._prev_batch_hook(first_gstep, k, x, y)
        return x, y


def _truncate_stage(stage_dir) -> None:
    """Cut every staged .npz short — the footprint of a writer that died
    mid-write on a filesystem whose publish was not atomic. The zip central
    directory lives at the end of the file, so a truncated npz fails to
    open and restore-side validation must reject the step."""
    import pathlib

    for npz in sorted(pathlib.Path(stage_dir).glob("*.npz")):
        size = npz.stat().st_size
        with open(npz, "r+b") as fh:
            fh.truncate(max(1, size // 2))
        logger.warning("fault injection: truncated %s to %d bytes",
                       npz, max(1, size // 2))


def maybe_injector_from_env(*, steps_per_epoch: int,
                            rank: Optional[int] = None,
                            attempt: Optional[int] = None
                            ) -> Optional[FaultInjector]:
    """Build the injector for this process's slice of ``$TPU_DIST_FAULT_PLAN``,
    or None when no plan is set or no fault targets (rank, attempt)."""
    plan = FaultPlan.from_env()
    if not plan:
        return None
    if rank is None:
        import jax

        env_rank = os.environ.get("TPU_DIST_REJOIN_RANK")
        if env_rank is not None and jax.process_count() == 1:
            # Supervised single-process workers all see process_index() == 0;
            # their true gang rank flows through the environment (the same
            # convention the rejoin gates use), so a `:rankN` fault coordinate
            # can actually target rank N.
            rank = int(env_rank)
        else:
            rank = jax.process_index()
    if attempt is None:
        attempt = events.current_attempt()
        # A worker relaunched INTO a live attempt (per-rank rejoin / gang
        # reform) inherits the attempt number — folding its incarnation in
        # keeps attempt-0 one-shot faults from re-firing forever in every
        # replacement.
        try:
            attempt += int(os.environ.get("TPU_DIST_GANG_REJOIN", "0") or 0)
        except ValueError:
            pass
    mine = plan.for_process(rank, attempt)
    # Job-domain filter: faults carrying a @jobN coordinate arm only in
    # the worker gang whose $TPU_DIST_JOB_INDEX matches — the same plan is
    # broadcast to every job of a packed pool, and this line is what keeps
    # job N's chaos out of its submesh neighbors.
    job_index = faults_mod.current_job_index()
    mine = [f for f in mine if f.matches_job(job_index)]
    import jax

    if jax.process_count() == 1:
        # Single-process multi-device runs: a bitflip's rank names the LOCAL
        # replica (device) to corrupt, not a process — arm it here even when
        # rank != 0 instead of dropping it as another process's fault.
        mine += [f for f in plan.faults
                 if f.kind == "bitflip" and f not in mine
                 and (f.attempt is None or attempt == f.attempt)]
    if not mine:
        return None
    logger.info("fault plan armed for rank %d attempt %d: %d fault(s)",
                rank, attempt, len(mine))
    return FaultInjector(mine, steps_per_epoch=steps_per_epoch)


class ServeFaultInjector:
    """Executes the SERVE slice of a FaultPlan from inside the engine loop.

    Not a training callback — the :class:`~tpu_dist.serve.engine.ServeEngine`
    calls the two seams directly each decode round:

    * ``on_decode()`` — between decode dispatch and host materialization,
      deliberately INSIDE the engine's stall-watchdog window: a due
      ``decode_stall`` sleeps there, indistinguishable from a hung runtime
      call, so the watchdog (not the injector) is what ends the process.
    * ``on_step_end(done_count)`` — after retirements but BEFORE the
      journal flush: a due ``engine_crash@reqN`` (fires once ``done_count``
      reaches N completed requests) is ``os._exit`` with the journal's
      unflushed tail lost, the harsher recovery case for the parity gate.

    ``request_storm`` is a submission-side fault: the chaos driver
    (``serve/chaos.py``) interprets it, not this injector.
    """

    ENGINE_KINDS = ("engine_crash", "decode_stall")

    def __init__(self, faults: Sequence[FaultSpec],
                 event_log: Optional[events.EventLog] = None):
        self.faults = [f for f in faults if f.kind in self.ENGINE_KINDS]
        self._events = event_log
        self._remaining = [f.count for f in self.faults]
        self._done = 0

    def _log(self, event: str, **fields) -> None:
        try:
            log = self._events or events.log_from_env()
            if log is not None:
                log.append(event, attempt=events.current_attempt(), **fields)
        except OSError:
            pass

    def arm(self) -> "ServeFaultInjector":
        for f in self.faults:
            self._log("fault_armed", kind=f.kind, req=f.req)
        if events.current_attempt() > 0:
            self._log("resumed")
        return self

    def on_decode(self) -> None:
        for i, f in enumerate(self.faults):
            if (f.kind != "decode_stall" or self._remaining[i] <= 0
                    or not f.due_at_req(self._done)):
                continue
            self._remaining[i] -= 1
            self._log("fault_fired", kind="decode_stall", req=f.req,
                      seconds=f.seconds)
            logger.warning("fault injection: stalling decode step for "
                           "%.1fs (after %d completed)", f.seconds,
                           self._done)
            time.sleep(f.seconds)

    def on_step_end(self, done_count: int) -> None:
        self._done = int(done_count)
        for i, f in enumerate(self.faults):
            if (f.kind != "engine_crash" or self._remaining[i] <= 0
                    or not f.due_at_req(done_count)):
                continue
            self._remaining[i] -= 1
            self._log("fault_fired", kind="engine_crash", req=f.req,
                      done=done_count, exit_code=f.exit_code)
            logger.warning("fault injection: killing serve engine after "
                           "%d completed requests (exit %d)", done_count,
                           f.exit_code)
            os._exit(f.exit_code)


def maybe_serve_injector_from_env(*, attempt: Optional[int] = None
                                  ) -> Optional[ServeFaultInjector]:
    """Build this serve process's injector from ``$TPU_DIST_FAULT_PLAN``,
    or None when no plan is set or no engine-side serve fault targets this
    attempt (serve workers are single-process: rank 0)."""
    plan = FaultPlan.from_env()
    if not plan:
        return None
    if attempt is None:
        attempt = events.current_attempt()
    mine = [f for f in plan.for_process(0, attempt)
            if f.kind in ServeFaultInjector.ENGINE_KINDS]
    if not mine:
        return None
    logger.info("serve fault plan armed for attempt %d: %d fault(s)",
                attempt, len(mine))
    return ServeFaultInjector(mine).arm()


class PreemptionDrain(Callback):
    """Stops training at the first step boundary after a SIGTERM.

    The signal handler (:func:`tpu_dist.resilience.entrypoints.
    install_sigterm_handler`) only *records* the preemption notice — a signal
    handler cannot safely unwind a training loop that may be inside XLA. This
    callback is the loop-side half of the seam: every step boundary it checks
    the flag and raises :class:`StopTraining`, which ``fit`` catches; the
    ``finally: on_train_end()`` path then closes :class:`ModelCheckpoint`,
    joining and PUBLISHING any in-flight async save before the process exits
    ``EXIT_PREEMPTED``.

    Parity note: the drain deliberately does NOT write a new checkpoint for
    the partially-trained epoch. Resume is epoch-granular (epoch-keyed RNG,
    epoch-boundary saves), so publishing mid-epoch state would double-train
    part of an epoch after restore. The interrupted epoch is replayed
    identically instead — that is what keeps the chaos gate's exact loss
    parity honest.
    """

    wants_batches = True

    def on_batch_end(self, step: int, logs: dict) -> None:
        self._maybe_stop(f"step boundary (in-epoch step {step})")

    def on_epoch_begin(self, epoch: int) -> None:
        # Covers a SIGTERM that lands between epochs (e.g. during eval or
        # checkpointing) — don't start another epoch just to notice it.
        self._maybe_stop(f"epoch {epoch} boundary")

    def _maybe_stop(self, where: str) -> None:
        from tpu_dist.resilience import entrypoints
        from tpu_dist.training.callbacks import StopTraining

        if entrypoints.preemption_requested():
            logger.warning("preemption drain: stopping training at %s",
                           where)
            raise StopTraining(f"preempted (drained at {where})")


def maybe_preemption_drain() -> Optional[PreemptionDrain]:
    """A :class:`PreemptionDrain` when the SIGTERM seam is armed (i.e. the
    process was launched through ``run_entry``), else None — an unsupervised
    notebook ``fit`` pays no per-batch hook for a handler that isn't there."""
    from tpu_dist.resilience import entrypoints

    if not entrypoints.preemption_armed():
        return None
    return PreemptionDrain()


class RejoinGate(Callback):
    """Epoch-boundary rendezvous: holds every worker at ``on_epoch_begin``
    until the whole gang has arrived, so a recovered worker re-enters the
    loop at the *next* epoch boundary instead of forcing a full gang restart.

    The barrier is the file-based :func:`tpu_dist.cluster.bootstrap.
    epoch_rendezvous` — deliberately NOT a jax collective, because the whole
    point is that the rejoining worker is a fresh process that is not (yet)
    part of any collective clique. Survivors publish their epoch marker and
    wait; the relaunched worker restores the shared checkpoint, publishes its
    own marker for the epoch it resumes at, and from that boundary on the
    gang steps together again.
    """

    def __init__(self, directory: str, *, world: Optional[int] = None,
                 rank: Optional[int] = None, timeout_s: float = 120.0):
        self.directory = directory
        self.world = world
        self.rank = rank
        self.timeout_s = float(timeout_s)

    def on_epoch_begin(self, epoch: int) -> None:
        from tpu_dist.cluster import bootstrap

        t0 = time.monotonic()
        ranks = bootstrap.epoch_rendezvous(
            self.directory, epoch=epoch, rank=self.rank, world=self.world,
            timeout_s=self.timeout_s)
        wait_s = time.monotonic() - t0
        log = events.log_from_env()
        if log is not None:
            log.append("rejoin_rendezvous", attempt=events.current_attempt(),
                       epoch=epoch, ranks=ranks, wait_s=round(wait_s, 6))


def maybe_rejoin_gate() -> Optional[RejoinGate]:
    """A :class:`RejoinGate` when ``$TPU_DIST_REJOIN_DIR`` names the
    rendezvous directory, else None. ``$TPU_DIST_REJOIN_WORLD`` /
    ``$TPU_DIST_REJOIN_RANK`` override the gang coordinates (they default to
    ``jax.process_count()`` / ``jax.process_index()``, which is right for
    real multi-process gangs but not for supervised single-process workers
    that each see themselves as process 0); ``$TPU_DIST_REJOIN_TIMEOUT_S``
    bounds the wait (default 120)."""
    from tpu_dist.cluster import bootstrap

    directory = os.environ.get(bootstrap.REJOIN_DIR_ENV)
    if not directory:
        return None
    world = os.environ.get("TPU_DIST_REJOIN_WORLD")
    rank = os.environ.get("TPU_DIST_REJOIN_RANK")
    timeout_s = float(os.environ.get("TPU_DIST_REJOIN_TIMEOUT_S", "120"))
    return RejoinGate(directory,
                      world=int(world) if world else None,
                      rank=int(rank) if rank else None,
                      timeout_s=timeout_s)
