"""Supervisor: launch, watch, restart, resume.

The reference stack delegates this whole layer to an external cluster
manager — Kubernetes restarts a dead worker pod, the TF server blocks until
the cluster re-forms (SURVEY.md §5.3: fault tolerance "is provided by the
surrounding infrastructure, not the strategy"). This module is that
surrounding infrastructure, scaled to one host: a parent process that

* launches the training job as ``num_workers`` subprocesses (the same
  loopback TF_CONFIG fabrication as ``tests/multiprocess_harness.py``, with
  fresh coordination-service ports per attempt — the old coordinator died
  with rank 0);
* watches exit codes, classifying them against the resilience protocol
  (0 clean, :data:`~tpu_dist.resilience.faults.EXIT_FAULT_KILL` injected
  kill, :data:`~tpu_dist.resilience.faults.EXIT_PEER_UNAVAILABLE` liveness
  surrender, anything else a crash);
* gang-restarts on failure — synchronous data parallelism cannot run a
  partial cluster, so when one rank dies the rest are grace-killed and the
  whole gang relaunches (the reference's own semantics: every collective
  blocks until the full cluster is back) — with exponential backoff, a
  restart budget, and a per-attempt wall-clock deadline that converts hangs
  (a wedged collective, an injected ``hang_collective``) into restarts;
* resumes step-accurately for free: workers re-enter ``fit(checkpoint_dir=)``
  and restore the newest checkpoint that passes manifest validation.

Worker stdout/stderr stream to per-(attempt, rank) log files — PIPEs would
deadlock once a killed worker stops draining — and every lifecycle event
lands in the shared :mod:`~tpu_dist.resilience.events` JSONL log.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import pathlib
import subprocess
import sys
import time
from typing import Optional, Sequence

from tpu_dist.resilience import events
from tpu_dist.resilience.faults import EXIT_INTEGRITY, EXIT_PREEMPTED

logger = logging.getLogger("tpu_dist.resilience")

#: How long a surviving rank gets to exit on its own after a gang member
#: died, before the supervisor escalates (see :class:`GracePolicy`; it is
#: usually wedged in a collective waiting for the dead peer).
GANG_GRACE_S = 5.0

_POLL_S = 0.1


@dataclasses.dataclass(frozen=True)
class BackoffPolicy:
    """Exponential restart backoff: ``min(max_s, initial_s * multiplier**n)``
    before restart attempt ``n`` (0-based over *restarts*, so the first
    restart waits ``initial_s``)."""

    initial_s: float = 0.5
    multiplier: float = 2.0
    max_s: float = 30.0

    def delay(self, restart: int) -> float:
        if restart < 0:
            raise ValueError(f"restart index must be >= 0, got {restart}")
        return min(self.max_s, self.initial_s * self.multiplier ** restart)


@dataclasses.dataclass(frozen=True)
class GracePolicy:
    """How a condemned gang is taken down: the spot-fleet preemption contract.

    The supervisor first waits ``exit_grace_s`` for survivors to exit on
    their own, then delivers SIGTERM — which a worker launched through
    ``run_entry`` answers with the graceful drain (stop at the next step
    boundary, publish in-flight checkpoints, exit
    :data:`~tpu_dist.resilience.faults.EXIT_PREEMPTED`) — waits
    ``term_grace_s`` for the drain, and only then escalates to SIGKILL.
    A deadline-hit (hung) attempt skips straight to SIGKILL: its main
    thread is wedged, so the Python-level SIGTERM drain cannot run and
    waiting the term grace would just slow every hang-chaos run down.
    """

    exit_grace_s: float = GANG_GRACE_S
    term_grace_s: float = 10.0


@dataclasses.dataclass
class AttemptOutcome:
    attempt: int
    exit_codes: list
    duration_s: float
    deadline_hit: bool = False
    #: Gang shape this attempt ran at (elastic schedules vary these).
    num_workers: Optional[int] = None
    device_count: Optional[int] = None
    #: Per-rank relaunches absorbed without a gang restart.
    rejoins: int = 0
    #: Longest SIGTERM→drained duration any rank of this attempt reported
    #: (from ``preempt_drained`` events); None when nothing drained.
    drain_s: Optional[float] = None
    #: Mid-epoch gang reforms absorbed within this attempt (step-rejoin
    #: mode: survivors kept their processes; only the clique re-formed).
    gang_reforms: int = 0
    #: ``time.monotonic()`` when this attempt's first worker death was
    #: DETECTED — the honest zero point for recovery_wall_s, measured the
    #: same way whether recovery is a gang restart or a mid-epoch rejoin.
    first_failure_t: Optional[float] = None

    @property
    def succeeded(self) -> bool:
        return (not self.deadline_hit
                and all(c == 0 for c in self.exit_codes))

    @property
    def preempted(self) -> bool:
        """True when every nonzero exit was a clean SIGTERM drain."""
        nonzero = [c for c in self.exit_codes if c != 0]
        return bool(nonzero) and all(c == EXIT_PREEMPTED for c in nonzero)


@dataclasses.dataclass
class SupervisorReport:
    success: bool
    attempts: int
    restarts: int
    outcomes: list
    wall_time_s: float
    #: Wall-clock from the first detected failure to final success (the
    #: recovery cost a chaos report quotes); None when nothing failed.
    recovery_wall_s: Optional[float] = None

    def to_json(self) -> dict:
        return {
            "success": self.success,
            "attempts": self.attempts,
            "restarts": self.restarts,
            "wall_time_s": round(self.wall_time_s, 3),
            "recovery_wall_s": (None if self.recovery_wall_s is None
                                else round(self.recovery_wall_s, 3)),
            "exit_codes": [o.exit_codes for o in self.outcomes],
            "exit_kinds": [[classify_exit(c) for c in o.exit_codes]
                           for o in self.outcomes],
            "gang_shapes": [{"num_workers": o.num_workers,
                             "device_count": o.device_count}
                            for o in self.outcomes],
            "rejoins": [o.rejoins for o in self.outcomes],
            "drain_s": [None if o.drain_s is None else round(o.drain_s, 3)
                        for o in self.outcomes],
            "gang_reforms": [o.gang_reforms for o in self.outcomes],
        }


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def classify_exit(code: Optional[int]) -> str:
    """Name a worker's exit for reports. Delegates to the central protocol
    registry in :mod:`tpu_dist.resilience.faults` (one source of truth for
    0/17/19/41/43), keeping only the process-never-exited case here."""
    if code is None:
        return "crash"
    from tpu_dist.resilience.faults import classify_exit_code

    return classify_exit_code(code)


class Supervisor:
    """Run ``cmd`` as a supervised (optionally multi-worker) job.

    ``cmd`` is the worker argv (e.g. ``[sys.executable, "-m",
    "tpu_dist.resilience.entrypoints"]``); every worker of every attempt
    runs the same argv and is differentiated through the environment:
    per-rank ``TF_CONFIG`` (only when ``num_workers > 1``),
    ``TPU_DIST_RESILIENCE_ATTEMPT``, and whatever the caller passes in
    ``env``.

    ``observe_dir`` arms per-worker telemetry: each rank gets
    ``TPU_DIST_OBSERVE_DIR=<observe_dir>/rank<r>`` so its ``fit`` attaches
    a :class:`~tpu_dist.observe.telemetry.Telemetry` callback, and its
    ``step_timing``/``straggler_detected`` records land in the shared
    event log (exports append across restarts — one series per rank).
    """

    def __init__(self, cmd: Sequence[str], *, num_workers: int = 1,
                 max_restarts: int = 3,
                 attempt_deadline_s: Optional[float] = None,
                 backoff: BackoffPolicy = BackoffPolicy(),
                 grace: GracePolicy = GracePolicy(),
                 env: Optional[dict] = None,
                 log_dir: str | os.PathLike = "resilience-logs",
                 event_log: Optional[events.EventLog] = None,
                 observe_dir: Optional[str | os.PathLike] = None,
                 worker_schedule: Optional[Sequence[int]] = None,
                 device_schedule: Optional[Sequence[int]] = None,
                 rejoin_window_s: float = 0.0,
                 max_rejoins: int = 4,
                 no_restart_exits: Sequence[int] = (EXIT_INTEGRITY,),
                 step_rejoin_dir: Optional[str | os.PathLike] = None,
                 reform_ack_timeout_s: float = 60.0,
                 rank_scoped_env_keys: Sequence[str] = ()):
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        if max_restarts < 0:
            raise ValueError("max_restarts must be >= 0")
        for name, sched in (("worker_schedule", worker_schedule),
                            ("device_schedule", device_schedule)):
            if sched is not None and (
                    not sched or any(int(n) < 1 for n in sched)):
                raise ValueError(
                    f"{name} must be a non-empty sequence of positive "
                    f"ints, got {sched!r}")
        self.cmd = list(cmd)
        self.num_workers = num_workers
        self.max_restarts = max_restarts
        self.attempt_deadline_s = attempt_deadline_s
        self.backoff = backoff
        self.grace = grace
        self.env = dict(env or {})
        self.log_dir = pathlib.Path(log_dir)
        self.events = event_log
        self.observe_dir = (pathlib.Path(observe_dir)
                            if observe_dir is not None else None)
        #: Elastic schedules: entry ``a`` is the gang shape for attempt
        #: ``a`` (the last entry repeats for later attempts), so a chaos
        #: plan can RESHAPE the job across a restart — fewer/more worker
        #: processes, or fewer/more devices per worker (the CPU-backend
        #: reshape vehicle: ``--xla_force_host_platform_device_count``).
        self.worker_schedule = (None if worker_schedule is None
                                else [int(n) for n in worker_schedule])
        self.device_schedule = (None if device_schedule is None
                                else [int(n) for n in device_schedule])
        #: Per-rank relaunch: with ``rejoin_window_s > 0`` a non-chief
        #: worker that dies while the rest of the gang keeps running is
        #: relaunched into the SAME attempt (it rejoins at the next epoch
        #: rendezvous) instead of condemning the gang.
        self.rejoin_window_s = float(rejoin_window_s)
        self.max_rejoins = int(max_rejoins)
        #: Exit codes that stop supervision instead of triggering a
        #: restart: the worker declared its failure non-recoverable (by
        #: default ``integrity_abort`` — a restart restores the same
        #: checkpoints and replays into the same wall). Serve supervision
        #: overrides this: ``serve_abort`` (a wedged decode runtime) IS
        #: cured by a fresh process.
        self.no_restart_exits = frozenset(int(c) for c in no_restart_exits)
        #: Mid-epoch gang reform (step-rejoin mode): a shared directory for
        #: the gang-generation protocol. When set, a lost rank triggers a
        #: REFORM — survivors drain at the next step boundary, ack, and the
        #: replacement meets them at a generation rendezvous — instead of a
        #: gang restart. Rejoin eligibility is implied (no separate window).
        self.step_rejoin_dir = (pathlib.Path(step_rejoin_dir)
                                if step_rejoin_dir is not None else None)
        #: How long survivors get to drain + ack a reform before the
        #: supervisor gives up and condemns the attempt (gang restart).
        self.reform_ack_timeout_s = float(reform_ack_timeout_s)
        #: Env var names whose values get a ``/rank{r}`` suffix per worker —
        #: e.g. the checkpoint dir, so two single-process workers that each
        #: believe they are the chief don't race the same staging files.
        self.rank_scoped_env_keys = tuple(rank_scoped_env_keys)
        #: Current committed gang generation (bumped by each reform).
        self._generation = 0
        #: Consensus restore step of the latest reform (for replacements).
        self._restore_step: Optional[int] = None

    # -- elastic gang shapes -------------------------------------------------

    def gang_size(self, attempt: int) -> int:
        """Worker count for ``attempt`` (worker_schedule, else static)."""
        if self.worker_schedule is None:
            return self.num_workers
        return self.worker_schedule[min(attempt, len(self.worker_schedule) - 1)]

    def device_count(self, attempt: int) -> Optional[int]:
        """Per-worker forced device count for ``attempt``, or None."""
        if self.device_schedule is None:
            return None
        return self.device_schedule[min(attempt, len(self.device_schedule) - 1)]

    # -- launching -----------------------------------------------------------

    def _worker_env(self, rank: int, attempt: int, rejoin: int = 0) -> dict:
        env = dict(os.environ)
        env.update(self.env)
        env[events.ATTEMPT_ENV] = str(attempt)
        if self.observe_dir is not None:
            from tpu_dist.observe.telemetry import OBSERVE_DIR_ENV

            env[OBSERVE_DIR_ENV] = str(self.observe_dir / f"rank{rank}")
        workers = self.gang_size(attempt)
        if workers > 1:
            from tpu_dist.cluster.config import make_local_cluster

            # Fresh ports every attempt: rank 0 hosted the coordination
            # service and took it down with itself; the old port may also
            # sit in TIME_WAIT.
            if rank == 0 and rejoin == 0:
                self._base_port = _free_port()
            cfg = make_local_cluster(workers, base_port=self._base_port)[rank]
            env.update({
                "TF_CONFIG": json.dumps(cfg),
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                # Gang coordinates for the file-based rendezvous layers:
                # each supervised worker is its own jax process (process
                # index 0), so its true rank must flow via the environment.
                "TPU_DIST_REJOIN_RANK": str(rank),
                "TPU_DIST_REJOIN_WORLD": str(workers),
            })
        devices = self.device_count(attempt)
        if devices is not None:
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={devices}",
            })
        if self.step_rejoin_dir is not None:
            from tpu_dist.cluster import bootstrap

            env[bootstrap.GANG_DIR_ENV] = str(self.step_rejoin_dir)
            env[bootstrap.GENERATION_ENV] = str(self._generation)
        if rejoin:
            # Incarnation counter for the relaunched process: attempt-0
            # fault specs must not re-fire in the replacement (it would
            # die again forever), so the injector folds this into its
            # effective attempt number.
            env["TPU_DIST_GANG_REJOIN"] = str(rejoin)
            if self.step_rejoin_dir is not None:
                # The replacement restores the reform's CONSENSUS step, not
                # its dead predecessor's latest ("none" = from scratch).
                step = getattr(self, "_restore_step", None)
                env["TPU_DIST_RESTORE_STEP"] = (
                    "none" if step is None else str(step))
        for key in self.rank_scoped_env_keys:
            if key in env and env[key]:
                env[key] = str(pathlib.Path(env[key]) / f"rank{rank}")
        return env

    def worker_log(self, attempt: int, rank: int,
                   rejoin: int = 0) -> pathlib.Path:
        suffix = f"-rejoin{rejoin}" if rejoin else ""
        return self.log_dir / f"attempt{attempt}-rank{rank}{suffix}.log"

    def _spawn(self, rank: int, attempt: int,
               rejoin: int = 0) -> subprocess.Popen:
        log_path = self.worker_log(attempt, rank, rejoin)
        # The file object can close right after spawn; the child holds
        # its own descriptor.
        with open(log_path, "wb") as log:
            return subprocess.Popen(
                self.cmd, env=self._worker_env(rank, attempt, rejoin),
                stdout=log, stderr=subprocess.STDOUT)

    def _launch(self, attempt: int) -> list:
        self.log_dir.mkdir(parents=True, exist_ok=True)
        procs = [self._spawn(rank, attempt)
                 for rank in range(self.gang_size(attempt))]
        self._log("attempt_start", attempt=attempt,
                  pids=[p.pid for p in procs],
                  num_workers=self.gang_size(attempt),
                  device_count=self.device_count(attempt))
        return procs

    def _log(self, event: str, **fields) -> None:
        if self.events is not None:
            try:
                self.events.append(event, **fields)
            except OSError:
                pass

    # -- watching ------------------------------------------------------------

    def _can_rejoin(self, rank: int, code: int, rejoins: int,
                    live_others: bool) -> bool:
        """Per-rank relaunch eligibility: rejoin mode armed, budget left,
        the rest of the gang still running, and not the chief — rank 0
        hosts the coordination service, so its death takes the clique's
        rendezvous medium with it and only a gang restart recovers. In
        step-rejoin (gang reform) mode the chief restriction lifts: the
        reformed clique gets a FRESH coordinator port, so a relaunched
        rank 0 can host it."""
        return ((self.rejoin_window_s > 0
                 or self.step_rejoin_dir is not None)
                and rejoins < self.max_rejoins
                and (rank != 0 or self.step_rejoin_dir is not None)
                and live_others
                and code != 0)

    def _watch(self, procs: list, attempt: int) -> AttemptOutcome:
        """Block until the gang exits, a member fails, or the deadline hits.

        Gang semantics: the first nonzero exit (or the deadline) condemns
        the attempt — unless rejoin mode can absorb it as a per-rank
        relaunch — after which survivors get the :class:`GracePolicy`
        escalation (exit grace → SIGTERM drain → term grace → SIGKILL).
        """
        t0 = time.monotonic()
        deadline = (t0 + self.attempt_deadline_s
                    if self.attempt_deadline_s else None)
        failed = False
        deadline_hit = False
        rejoins = 0
        gang_reforms = 0
        first_failure_t: Optional[float] = None
        # Per-rank last-seen-alive time: detect_s = detection minus this,
        # the vehicle-level analog of the heartbeat-timeout window that
        # dominates detection latency on a real backend.
        last_alive = {rank: t0 for rank in range(len(procs))}
        reported: set = set()
        while True:
            live = [p for p in procs if p.poll() is None]
            now = time.monotonic()
            for rank, p in enumerate(procs):
                if p.poll() is None:
                    last_alive[rank] = now
            for rank, p in enumerate(procs):
                code = p.poll()
                if code is not None and (rank, p.pid) not in reported:
                    reported.add((rank, p.pid))
                    self._log("worker_exit", attempt=attempt, rank=rank,
                              code=code, kind=classify_exit(code))
                    logger.info("supervisor: rank %d exited %s (%s)",
                                rank, code, classify_exit(code))
                    if code == 0:
                        continue
                    if first_failure_t is None:
                        first_failure_t = time.monotonic()
                    others_live = any(q.poll() is None for q in procs
                                      if q is not p)
                    if self._can_rejoin(rank, code, rejoins, others_live):
                        detect_s = time.monotonic() - last_alive[rank]
                        if self.step_rejoin_dir is not None:
                            if not self._begin_reform(procs, rank, attempt,
                                                      detect_s):
                                failed = True
                                continue
                            gang_reforms += 1
                        rejoins += 1
                        procs[rank] = self._spawn(rank, attempt,
                                                  rejoin=rejoins)
                        self._log("worker_rejoin", attempt=attempt,
                                  rank=rank, rejoin=rejoins,
                                  prior_code=code,
                                  pid=procs[rank].pid)
                        logger.info(
                            "supervisor: relaunched rank %d into attempt "
                            "%d (rejoin %d/%d)", rank, attempt, rejoins,
                            self.max_rejoins)
                    else:
                        failed = True
            if failed or not live:
                break
            if deadline is not None and time.monotonic() > deadline:
                deadline_hit = True
                self._log("attempt_deadline", attempt=attempt,
                          deadline_s=self.attempt_deadline_s)
                logger.warning("supervisor: attempt %d exceeded its %.1fs "
                               "deadline", attempt, self.attempt_deadline_s)
                break
            time.sleep(_POLL_S)
        # GracePolicy escalation for whoever is left. A deadline-hit gang
        # is wedged — skip straight to SIGKILL (GracePolicy docstring).
        if deadline_hit:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        else:
            grace_end = time.monotonic() + self.grace.exit_grace_s
            while (any(p.poll() is None for p in procs)
                   and time.monotonic() < grace_end):
                time.sleep(_POLL_S)
            termed = [rank for rank, p in enumerate(procs)
                      if p.poll() is None]
            if termed:
                for rank in termed:
                    procs[rank].terminate()  # SIGTERM: the drain request
                self._log("gang_sigterm", attempt=attempt, ranks=termed,
                          term_grace_s=self.grace.term_grace_s)
                logger.info("supervisor: SIGTERM to rank(s) %s; waiting "
                            "%.1fs for the drain", termed,
                            self.grace.term_grace_s)
                term_end = time.monotonic() + self.grace.term_grace_s
                while (any(p.poll() is None for p in procs)
                       and time.monotonic() < term_end):
                    time.sleep(_POLL_S)
            for rank, p in enumerate(procs):
                if p.poll() is None:
                    self._log("gang_sigkill", attempt=attempt, rank=rank)
                    p.kill()
        codes = []
        for rank, p in enumerate(procs):
            code = p.wait()
            codes.append(code)
            if (rank, p.pid) not in reported:
                self._log("worker_exit", attempt=attempt, rank=rank,
                          code=code, kind=classify_exit(code))
        return AttemptOutcome(attempt=attempt, exit_codes=codes,
                              duration_s=time.monotonic() - t0,
                              deadline_hit=deadline_hit,
                              num_workers=self.gang_size(attempt),
                              device_count=self.device_count(attempt),
                              rejoins=rejoins, gang_reforms=gang_reforms,
                              first_failure_t=first_failure_t)

    def _begin_reform(self, procs: list, lost_rank: int, attempt: int,
                      detect_s: float) -> bool:
        """Supervisor side of a mid-epoch gang reform.

        Publishes the reform request for generation g+1, waits for every
        survivor's drained-ack, computes the consensus restore step (the
        gang-wide minimum over the survivors' available checkpoints and the
        lost rank's directory), commits it plus the new generation, and
        returns True — the caller then spawns the replacement, which meets
        the survivors at the generation rendezvous. Returns False (condemn
        the attempt to a gang restart) if a survivor dies mid-reform or the
        acks don't arrive within ``reform_ack_timeout_s``.
        """
        from tpu_dist.cluster import bootstrap

        new_gen = self._generation + 1
        bootstrap.request_reform(self.step_rejoin_dir, generation=new_gen,
                                 lost_ranks=[lost_rank], detect_s=detect_s)
        survivors = [r for r, p in enumerate(procs)
                     if r != lost_rank and p.poll() is None]
        t0 = time.monotonic()
        ack_deadline = t0 + self.reform_ack_timeout_s
        while True:
            acks = bootstrap.read_reform_acks(self.step_rejoin_dir,
                                              generation=new_gen)
            if set(survivors) <= set(acks):
                break
            dead = [r for r in survivors if procs[r].poll() is not None]
            if dead:
                # Reform-during-reform: a SECOND rank died while the
                # survivors were draining. The reform can never complete
                # (the dead survivor will not ack), and its request must
                # not outlive the attempt — a restarted gang's rejoin gate
                # reading the stale g+1 request would re-enter a reform
                # nobody mediates. Withdraw it and condemn the attempt to
                # an ordinary gang restart.
                bootstrap.withdraw_reform(self.step_rejoin_dir)
                self._log("gang_reform_failed", attempt=attempt,
                          generation=new_gen, reason="survivor_died",
                          cause="second_loss", ranks=dead)
                logger.warning("supervisor: survivor rank(s) %s died "
                               "mid-reform (second loss); falling back to "
                               "gang restart", dead)
                return False
            if time.monotonic() > ack_deadline:
                bootstrap.withdraw_reform(self.step_rejoin_dir)
                self._log("gang_reform_failed", attempt=attempt,
                          generation=new_gen, reason="ack_timeout",
                          cause="ack_timeout",
                          acked=sorted(acks), survivors=survivors)
                logger.warning(
                    "supervisor: reform acks %s/%s within %.1fs; falling "
                    "back to gang restart", sorted(acks), survivors,
                    self.reform_ack_timeout_s)
                return False
            time.sleep(_POLL_S)
        ack_wait_s = time.monotonic() - t0

        # Consensus restore step: minimum over every gang member's durable
        # checkpoints — survivors report theirs in the ack; the lost rank's
        # directory is read here (it can be BEHIND the survivors: its async
        # save may never have published before the kill). Any member with
        # no checkpoint at all forces a from-scratch replay for everyone
        # (epoch-keyed RNG keeps that exact).
        steps = [acks[r].get("available_step") for r in survivors]
        if self.rank_scoped_env_keys:
            # Per-rank checkpoint dirs: the replacement restores from the
            # lost rank's directory, so its contents bound the consensus
            # too. (With a shared directory the survivors' acks already
            # describe exactly what the replacement will see.)
            steps.append(self._lost_rank_step(lost_rank))
        consensus = None if any(s is None for s in steps) else min(steps)
        bootstrap.publish_restore_step(self.step_rejoin_dir,
                                       generation=new_gen, step=consensus)
        self._restore_step = consensus
        self._generation = new_gen
        bootstrap.publish_generation(self.step_rejoin_dir, new_gen)
        self._log("gang_reform_requested", attempt=attempt,
                  generation=new_gen, lost_ranks=[lost_rank],
                  detect_s=round(detect_s, 6),
                  ack_wait_s=round(ack_wait_s, 6),
                  restore_step=consensus)
        logger.info(
            "supervisor: gang reform to generation %d (lost rank %d, "
            "restore step %s, acks in %.3fs)", new_gen, lost_rank,
            consensus, ack_wait_s)
        return True

    def _lost_rank_step(self, lost_rank: int) -> Optional[int]:
        """Newest complete checkpoint step in the lost rank's (rank-scoped)
        checkpoint directory, or None when unknown/absent."""
        for key in self.rank_scoped_env_keys:
            base = self.env.get(key) or os.environ.get(key)
            if not base:
                continue
            from tpu_dist.training import checkpoint as ckpt_lib

            try:
                return ckpt_lib.latest_complete_step(
                    pathlib.Path(base) / f"rank{lost_rank}")
            except OSError:
                return None
        return None

    def _attempt_drain_s(self, attempt: int) -> Optional[float]:
        """Longest drain any rank of ``attempt`` reported, from the shared
        event log's ``preempt_drained`` records; None without the log."""
        if self.events is None:
            return None
        try:
            drained = [e.get("drain_s") for e in
                       events.read_events(self.events.path,
                                          event="preempt_drained")
                       if e.get("attempt") == attempt
                       and isinstance(e.get("drain_s"), (int, float))]
        except OSError:
            return None
        return max(drained) if drained else None

    # -- the supervision loop ------------------------------------------------

    def run(self) -> SupervisorReport:
        t_start = time.monotonic()
        t_first_failure: Optional[float] = None
        outcomes: list = []
        attempt = 0
        while True:
            outcome = self._watch(self._launch(attempt), attempt)
            outcome.drain_s = self._attempt_drain_s(attempt)
            outcomes.append(outcome)
            # Recovery is measured from DETECTION of the first death — the
            # same zero point whether recovery was a gang restart or a
            # mid-epoch rejoin absorbed inside a succeeding attempt.
            if t_first_failure is None:
                t_first_failure = outcome.first_failure_t
            if outcome.succeeded:
                if attempt > 0 or outcome.rejoins:
                    self._log("recovered", attempt=attempt,
                              restarts=attempt, rejoins=outcome.rejoins,
                              gang_reforms=outcome.gang_reforms)
                break
            if t_first_failure is None:
                t_first_failure = time.monotonic()
            fatal = [c for c in outcome.exit_codes
                     if c is not None and c in self.no_restart_exits]
            if fatal:
                # The worker declared this failure non-recoverable (e.g.
                # integrity_abort: the in-process rollback budget is spent;
                # a gang restart restores the same checkpoints and replays
                # into the same wall). Stop and surface for triage.
                logger.error("supervisor: worker exited %s (%s) — "
                             "restarting cannot help; stopping",
                             fatal[0], classify_exit(fatal[0]))
                self._log("no_restart_stop", attempt=attempt,
                          exit_codes=outcome.exit_codes,
                          kinds=[classify_exit(c) for c in fatal])
                break
            if attempt >= self.max_restarts:
                logger.error("supervisor: restart budget (%d) exhausted",
                             self.max_restarts)
                break
            delay = self.backoff.delay(attempt)
            self._log("restart", attempt=attempt + 1, backoff_s=delay,
                      prior_exit_codes=outcome.exit_codes)
            logger.info("supervisor: restarting (attempt %d) after %.2fs "
                        "backoff", attempt + 1, delay)
            time.sleep(delay)
            attempt += 1
        wall = time.monotonic() - t_start
        success = outcomes[-1].succeeded
        recovery = (time.monotonic() - t_first_failure
                    if success and t_first_failure is not None else None)
        report = SupervisorReport(
            success=success, attempts=len(outcomes),
            restarts=len(outcomes) - 1, outcomes=outcomes,
            wall_time_s=wall, recovery_wall_s=recovery)
        self._log("run_complete", **report.to_json())
        return report
