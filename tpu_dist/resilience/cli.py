"""``python -m tpu_dist.resilience`` — run a chaos experiment, emit a report.

The experiment: run the entry point once uninterrupted (the baseline), then
run it again under the :class:`~tpu_dist.resilience.supervisor.Supervisor`
with a :class:`~tpu_dist.resilience.faults.FaultPlan` armed, and compare.
The JSON report answers the questions a recovery SLO asks:

* did the faults actually fire (``faults_fired``, from the event log — a
  chaos run whose fault never fired is a vacuous pass and FAILS);
* how many restarts did recovery take (``restarts``);
* how long did recovery cost (``recovery_wall_s``);
* did the recovered run converge to the SAME place (``final_loss`` vs
  ``baseline_final_loss``, gated by ``--parity-atol``) — the end-to-end
  proof that resume was step-accurate and nothing trained twice or not
  at all.

Example::

    python -m tpu_dist.resilience --plan kill-worker@step5

kills the demo worker at global step 5 of a 12-step run; the supervisor
restarts it, it resumes from the epoch-0 checkpoint, and the report shows
loss parity with the uninterrupted baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile
from typing import Optional

from tpu_dist.resilience import events
from tpu_dist.resilience.entrypoints import CHECKPOINT_DIR_ENV, ENTRY_ENV
from tpu_dist.resilience.faults import FAULT_PLAN_ENV, FaultPlan, describe
from tpu_dist.resilience.supervisor import (BackoffPolicy, Supervisor)

_RESULT_PREFIX = "RESULT:"

#: Fault kinds recovered IN-PROCESS by the training-integrity guard
#: (rollback-and-replay) rather than by a supervisor gang restart.
INTEGRITY_KINDS = frozenset(
    {"nan_loss", "grad_spike", "bitflip", "corrupt_batch"})


def parse_result_line(text: str) -> Optional[dict]:
    """The LAST ``RESULT:{...}`` line in ``text`` — a restarted worker's log
    holds one per completed run; the last is the one that finished."""
    result = None
    for line in text.splitlines():
        if line.startswith(_RESULT_PREFIX):
            try:
                result = json.loads(line[len(_RESULT_PREFIX):])
            except ValueError:
                continue
    return result


def _worker_cmd() -> list:
    return [sys.executable, "-m", "tpu_dist.resilience.entrypoints"]


def _clean_env(extra: dict) -> dict:
    """os.environ minus any resilience/observe wiring from OUR caller, plus
    ``extra`` — each run (baseline, chaos) gets exactly its own knobs."""
    from tpu_dist.cluster import bootstrap
    from tpu_dist.observe.telemetry import OBSERVE_DIR_ENV

    env = {k: v for k, v in os.environ.items()
           if k not in (FAULT_PLAN_ENV, events.EVENT_LOG_ENV,
                        events.ATTEMPT_ENV, CHECKPOINT_DIR_ENV,
                        OBSERVE_DIR_ENV, bootstrap.REJOIN_DIR_ENV,
                        bootstrap.GANG_DIR_ENV, bootstrap.GENERATION_ENV,
                        "TPU_DIST_GANG_REJOIN", "TPU_DIST_RESTORE_STEP",
                        "TPU_DIST_REJOIN_RANK", "TPU_DIST_REJOIN_WORLD")
           and not k.startswith("TPU_DIST_INTEGRITY")}
    env.update(extra)
    return env


def run_baseline(workdir: pathlib.Path, *, timeout: float,
                 extra_env: Optional[dict] = None) -> Optional[dict]:
    """One uninterrupted run in a subprocess; returns its RESULT dict."""
    log_path = workdir / "baseline.log"
    env = _clean_env({CHECKPOINT_DIR_ENV: str(workdir / "baseline-ckpt"),
                      **(extra_env or {})})
    with open(log_path, "wb") as log:
        code = subprocess.call(_worker_cmd(), env=env, stdout=log,
                               stderr=subprocess.STDOUT, timeout=timeout)
    text = log_path.read_text(errors="replace")
    if code != 0:
        raise RuntimeError(
            f"baseline run exited {code}; see {log_path}:\n{text[-2000:]}")
    return parse_result_line(text)


def _parse_reshape(arg: Optional[str]) -> Optional[list]:
    if not arg:
        return None
    try:
        counts = [int(tok) for tok in arg.split(",") if tok.strip()]
    except ValueError:
        counts = []
    if len(counts) < 2 or any(n < 1 for n in counts):
        raise SystemExit(
            f"error: --reshape wants >= 2 comma-separated positive device "
            f"counts (e.g. 8,4), got {arg!r}")
    return counts


def _supervised_leg(args, plan, leg_dir: pathlib.Path, *, workers: int,
                    step_rejoin: bool):
    """One supervised chaos run in ``leg_dir``; returns (sup, report, events).

    Both legs of the step-rejoin comparison run through here with identical
    knobs except ``step_rejoin`` — the control recovers the ISSUE's status
    quo way (gang restart), the reform leg via mid-epoch rejoin — so their
    recovery_wall_s difference measures exactly the mechanism under test.
    Checkpoint dirs are rank-scoped: each single-process worker believes it
    is the chief, and two async writers must not race one staging dir.
    """
    leg_dir.mkdir(parents=True, exist_ok=True)
    event_path = leg_dir / "events.jsonl"
    extra_env = {
        FAULT_PLAN_ENV: plan.dumps(),
        events.EVENT_LOG_ENV: str(event_path),
        CHECKPOINT_DIR_ENV: str(leg_dir / "ckpt"),
    }
    if args.entry:
        extra_env[ENTRY_ENV] = args.entry
    sup = Supervisor(
        _worker_cmd(), num_workers=workers,
        max_restarts=args.max_restarts, attempt_deadline_s=args.deadline,
        backoff=BackoffPolicy(initial_s=args.backoff),
        env=_clean_env(extra_env), log_dir=leg_dir / "logs",
        event_log=events.EventLog(event_path, role="supervisor"),
        observe_dir=leg_dir / "observe",
        step_rejoin_dir=(leg_dir / "gang") if step_rejoin else None,
        rank_scoped_env_keys=(CHECKPOINT_DIR_ENV,))
    return sup, sup.run(), event_path


def _run_step_rejoin(args, plan, workdir: pathlib.Path) -> int:
    """The mid-epoch rejoin experiment: baseline, control (gang restart),
    reform (gang-generation rejoin); gates per ISSUE acceptance criteria."""
    workers = max(2, args.workers)
    baseline = None
    if not args.no_baseline:
        print("running baseline (no faults)...", file=sys.stderr)
        # Pin the baseline to the SAME device env the gang workers get
        # (supervisor multi-worker branch forces 1 device per process) —
        # an inherited XLA_FLAGS device count would compare losses across
        # different meshes and fail the exact-parity gate spuriously.
        baseline = run_baseline(workdir, timeout=args.timeout, extra_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })

    print(f"running control leg (gang restart, {workers} workers)...",
          file=sys.stderr)
    control_sup, control, control_events = _supervised_leg(
        args, plan, workdir / "control", workers=workers, step_rejoin=False)
    print("running reform leg (mid-epoch rejoin)...", file=sys.stderr)
    reform_sup, reform, reform_events = _supervised_leg(
        args, plan, workdir / "reform", workers=workers, step_rejoin=True)

    final = None
    if reform.success:
        final = parse_result_line(reform_sup.worker_log(
            reform.attempts - 1, 0).read_text(errors="replace"))

    control_json = control.to_json()
    reform_json = reform.to_json()
    reforms = events.read_events(reform_events, "gang_reform")
    reform_requests = events.read_events(reform_events,
                                         "gang_reform_requested")
    rejoins = events.read_events(reform_events, "worker_rejoin")
    fired_control = events.read_events(control_events, "fault_fired")
    fired_reform = events.read_events(reform_events, "fault_fired")

    # Phase-split recovery accounting: detection comes from the supervisor
    # (it watches the gang), drain/reform/restore from the survivors'
    # gang_reform events — worst rank, since the gang moves at its pace.
    def _worst(records, key):
        vals = [r.get(key) for r in records
                if isinstance(r.get(key), (int, float))]
        return round(max(vals), 6) if vals else None

    breakdown = {
        "detect_s": _worst(reform_requests, "detect_s"),
        "drain_s": _worst(reforms, "drain_s"),
        "reform_s": _worst(reforms, "reform_s"),
        "restore_s": _worst(reforms, "restore_s"),
    }

    report = {
        "plan": plan.to_json(),
        "mode": "step_rejoin",
        "workdir": str(workdir),
        "success": control.success and reform.success,
        "step_rejoin": {
            "control": {
                "recovery_wall_s": control_json["recovery_wall_s"],
                "wall_time_s": control_json["wall_time_s"],
                "restarts": control.restarts,
                "attempts": control.attempts,
                "exit_codes": control_json["exit_codes"],
                "exit_kinds": control_json["exit_kinds"],
            },
            "reform": {
                "recovery_wall_s": reform_json["recovery_wall_s"],
                "wall_time_s": reform_json["wall_time_s"],
                "restarts": reform.restarts,
                "attempts": reform.attempts,
                "exit_codes": reform_json["exit_codes"],
                "exit_kinds": reform_json["exit_kinds"],
                "rejoins": reform_json["rejoins"],
                "gang_reforms": reform_json["gang_reforms"],
            },
        },
        "recovery_wall_s": reform_json["recovery_wall_s"],
        "recovery_breakdown": breakdown,
        "gang_reform_events": len(reforms),
        "final_loss": (final or {}).get("final_loss"),
    }

    ok = control.success and reform.success
    failures = []
    if not fired_control or not fired_reform:
        failures.append("no fault fired — vacuous chaos run")
    if reform.restarts != 0:
        failures.append(
            f"reform leg leaned on a gang restart (restarts="
            f"{reform.restarts}) instead of a mid-epoch rejoin")
    if not reforms:
        failures.append("no gang_reform event — vacuous rejoin run")
    if not rejoins:
        failures.append("no worker_rejoin — the lost rank never relaunched")
    ctrl_rec = control_json["recovery_wall_s"]
    ref_rec = reform_json["recovery_wall_s"]
    if ctrl_rec is None or ref_rec is None:
        failures.append("missing recovery_wall_s in a leg")
    elif not ref_rec < ctrl_rec:
        failures.append(
            f"rejoin recovery ({ref_rec:.3f}s) not strictly below "
            f"gang-restart recovery ({ctrl_rec:.3f}s)")
    else:
        report["step_rejoin"]["speedup"] = round(ctrl_rec / ref_rec, 3)
    if baseline is not None:
        report["baseline_final_loss"] = baseline.get("final_loss")
        if (report["final_loss"] is None
                or report["baseline_final_loss"] is None):
            failures.append("missing final loss for the parity check")
            report["parity_ok"] = False
        else:
            delta = abs(report["final_loss"]
                        - report["baseline_final_loss"])
            report["loss_delta"] = delta
            # EXACT parity: the reform replays from the consensus
            # checkpoint with epoch-keyed RNG — bit-identical, not merely
            # close, so no atol.
            report["parity_ok"] = delta == 0.0
            if delta != 0.0:
                failures.append(f"loss parity not exact (delta={delta})")
    if failures:
        ok = False
        report["failure"] = "; ".join(failures)
    report["ok"] = ok
    out = json.dumps(report, indent=2)
    print(out)
    if args.report:
        pathlib.Path(args.report).write_text(out + "\n")
    return 0 if ok else 1


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tpu_dist.resilience",
        description="Fault-injection chaos runner for tpu_dist training "
                    "jobs: baseline run, supervised chaos run, JSON report.")
    p.add_argument("--plan", required=False, default=None,
                   help="fault plan: compact spec (kill-worker@step5; "
                        "bitflip additionally takes leaf/shard coordinates, "
                        "e.g. bitflip@step9:leaf1:replica5), inline JSON, "
                        "or @path/to/plan.json")
    p.add_argument("--entry", default=None,
                   help="module:callable to train with (default: the "
                        "built-in synthetic-MNIST demo)")
    p.add_argument("--workers", type=int, default=1,
                   help="worker processes (default 1; >1 needs a backend "
                        "with multi-process collectives)")
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument("--deadline", type=float, default=300.0, metavar="S",
                   help="per-attempt wall-clock deadline (converts hangs "
                        "into restarts; default 300)")
    p.add_argument("--backoff", type=float, default=0.5, metavar="S",
                   help="initial restart backoff, doubling per restart")
    p.add_argument("--parity-atol", type=float, default=1e-5,
                   help="max |final_loss - baseline_final_loss| (default "
                        "1e-5)")
    p.add_argument("--workdir", default=None,
                   help="working directory for checkpoints/logs/events "
                        "(default: a fresh temp dir)")
    p.add_argument("--report", default=None,
                   help="also write the JSON report to this path")
    p.add_argument("--no-baseline", action="store_true",
                   help="skip the baseline run (no parity check)")
    p.add_argument("--timeout", type=float, default=600.0,
                   help="overall per-run timeout for the baseline")
    p.add_argument("--step-rejoin", action="store_true",
                   help="mid-epoch gang-reform scenario: run the SAME kill "
                        "plan twice on a >= 2-worker gang — once recovering "
                        "by full gang restart (the control), once by "
                        "mid-epoch worker rejoin under a reformed gang "
                        "generation — and gate on rejoin recovery_wall_s "
                        "strictly below the control's, zero survivor "
                        "restarts, >= 1 gang_reform event, and EXACT loss "
                        "parity (delta 0.0) vs the fault-free baseline")
    p.add_argument("--reshape", default=None, metavar="N,M[,...]",
                   help="elastic reshape schedule: attempt k runs on the "
                        "k-th device count (last repeats), e.g. 8,4 = die "
                        "on 8 devices, restart reshaped onto 4. Arms the "
                        "demo's multi-device sharded mode and requires a "
                        "reshape_restore to actually happen (else the run "
                        "is vacuous and fails). The baseline runs at the "
                        "first count.")
    p.add_argument("--ps-chaos", action="store_true",
                   help="parameter-server chaos legs instead of a --plan "
                        "run: calibrated 10x straggler (async vs a "
                        "measured sync collapse), kill-worker (zero "
                        "restarts), server-kill (checkpoint restore). "
                        "Fault plans are derived per leg; --plan is "
                        "ignored")
    p.add_argument("--ps-world", type=int, default=2,
                   help="PS worker ranks per leg (default 2)")
    p.add_argument("--ps-epochs", type=int, default=2)
    p.add_argument("--ps-steps", type=int, default=4,
                   help="steps per epoch per worker (budget = "
                        "epochs*steps*world)")
    p.add_argument("--ps-batch", type=int, default=8)
    p.add_argument("--ps-staleness", type=int, default=4,
                   help="bounded-staleness window for the async legs")
    p.add_argument("--ps-tol", type=float, default=0.1,
                   help="max |final_loss| delta for the PS convergence "
                        "gates (bounded staleness reorders applies, so "
                        "this is a convergence tolerance, not parity)")
    p.add_argument("--ps-legs", default="all",
                   help="comma subset of straggler,kill,server,sync (or "
                        "'all'); the clean async reference leg always "
                        "runs")
    return p


def main(argv: Optional[list] = None) -> int:
    args = build_parser().parse_args(argv)
    workdir = pathlib.Path(args.workdir or tempfile.mkdtemp(
        prefix="tpu-dist-chaos-"))
    workdir.mkdir(parents=True, exist_ok=True)
    print(f"chaos workdir: {workdir}", file=sys.stderr)
    if args.ps_chaos:
        from tpu_dist.resilience.ps_chaos import run_ps_chaos
        return run_ps_chaos(args, workdir)
    if not args.plan:
        print("error: --plan is required (or use --ps-chaos)",
              file=sys.stderr)
        return 2
    plan = FaultPlan.parse(args.plan)
    if not plan:
        print("error: --plan parsed to an empty fault plan", file=sys.stderr)
        return 2
    for line in describe(plan):
        print(f"fault: {line}", file=sys.stderr)

    if args.step_rejoin:
        if args.reshape:
            print("error: --step-rejoin and --reshape are mutually "
                  "exclusive", file=sys.stderr)
            return 2
        return _run_step_rejoin(args, plan, workdir)

    reshape = _parse_reshape(args.reshape)
    # Reshape runs flip the demo into explicit multi-device mode: a
    # MirroredStrategy over every (forced-host-platform) local device plus
    # a v2 SHARDED checkpoint, so the restart actually exercises
    # stitch-the-shards + re-shard-onto-Q-devices rather than a replicated
    # v1 broadcast.
    demo_env = ({"TPU_DIST_DEMO_STRATEGY": "mirrored",
                 "TPU_DIST_DEMO_SHARDED": "1"} if reshape else {})
    # Integrity fault plans arm the in-fit guard in BOTH runs (the baseline
    # proves an armed guard changes nothing on a clean run); bitflip
    # additionally needs a real multi-device mesh — the SDC audit compares
    # replica copies — plus the periodic audit switched on.
    integrity_faults = [f for f in plan.faults
                        if f.kind in INTEGRITY_KINDS]
    if integrity_faults:
        demo_env.update({"TPU_DIST_INTEGRITY": "1",
                         "TPU_DIST_INTEGRITY_BUDGET": "3"})
        if any(f.kind == "bitflip" for f in integrity_faults):
            demo_env.update({
                "TPU_DIST_INTEGRITY_AUDIT_N": "2",
                "TPU_DIST_DEMO_STRATEGY": "mirrored",
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            })

    baseline = None
    if not args.no_baseline:
        print("running baseline (no faults)...", file=sys.stderr)
        baseline_env = dict(demo_env)
        if reshape:
            baseline_env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={reshape[0]}",
            })
        baseline = run_baseline(workdir, timeout=args.timeout,
                                extra_env=baseline_env)

    event_path = workdir / "events.jsonl"
    extra_env = {
        FAULT_PLAN_ENV: plan.dumps(),
        events.EVENT_LOG_ENV: str(event_path),
        CHECKPOINT_DIR_ENV: str(workdir / "ckpt"),
        **demo_env,
    }
    if args.entry:
        extra_env[ENTRY_ENV] = args.entry
    print("running chaos experiment...", file=sys.stderr)
    sup = Supervisor(
        _worker_cmd(), num_workers=args.workers,
        max_restarts=args.max_restarts, attempt_deadline_s=args.deadline,
        backoff=BackoffPolicy(initial_s=args.backoff),
        env=_clean_env(extra_env), log_dir=workdir / "logs",
        event_log=events.EventLog(event_path, role="supervisor"),
        observe_dir=workdir / "observe",
        device_schedule=reshape)
    sup_report = sup.run()

    final = None
    if sup_report.success:
        final = parse_result_line(sup.worker_log(
            sup_report.attempts - 1, 0).read_text(errors="replace"))

    fired = events.read_events(event_path, "fault_fired")
    sup_json = sup_report.to_json()
    reshape_events = events.read_events(event_path, "reshape_restore")
    drained = events.read_events(event_path, "preempt_drained")
    report = {
        "plan": plan.to_json(),
        "workdir": str(workdir),
        "success": sup_report.success,
        "attempts": sup_report.attempts,
        "restarts": sup_report.restarts,
        "recovery_wall_s": sup_json["recovery_wall_s"],
        "wall_time_s": sup_json["wall_time_s"],
        "exit_codes": [o.exit_codes for o in sup_report.outcomes],
        "exit_kinds": sup_json["exit_kinds"],
        "gang_shapes": sup_json["gang_shapes"],
        "drain_s": sup_json["drain_s"],
        "reshape_restores": [
            {k: r.get(k) for k in ("step", "saved_device_count",
                                   "device_count", "saved_process_count",
                                   "process_count")}
            for r in reshape_events],
        "faults_fired": [
            {k: r.get(k) for k in ("kind", "at", "step", "op", "mode")
             if r.get(k) is not None} for r in fired],
        "events": len(events.read_events(event_path)),
        # Which checkpoint step each restarted attempt resumed from, in
        # order — the proof that recovery came from the last PUBLISHED step
        # (a kill_during_save run must show the pre-kill step here, never
        # the step whose save was torn mid-flight).
        "resumed_from": [r.get("step") for r in
                         events.read_events(event_path, "checkpoint_resume")],
        "final_loss": (final or {}).get("final_loss"),
    }
    # Per-rank telemetry (the workers run with TPU_DIST_OBSERVE_DIR armed,
    # so their Telemetry callbacks emit step_timing/straggler_detected into
    # the shared event log).
    timing = events.read_events(event_path, "step_timing")
    per_rank: dict = {}
    for rec in timing:
        per_rank.setdefault(int(rec.get("rank", 0)), []).append(
            float(rec.get("mean_step_s", 0.0)))
    report["telemetry"] = {
        "observe_dir": str(workdir / "observe"),
        "step_timing_events": len(timing),
        "per_rank_mean_step_s": {
            str(rank): round(sum(v) / len(v), 6)
            for rank, v in sorted(per_rank.items()) if v},
        "stragglers": [
            {k: rec.get(k) for k in ("epoch", "rank", "step_s",
                                     "median_s", "ratio")}
            for rec in events.read_events(event_path, "straggler_detected")],
    }
    ok = sup_report.success and bool(fired)
    if not fired:
        report["failure"] = "no fault fired — vacuous chaos run"
    # Anti-vacuity gates for the elastic machinery: a preempt plan must
    # show a real SIGTERM drain (preempted exit + preempt_drained event),
    # and a --reshape run must show an actual cross-topology restore.
    if any(f.kind == "preempt" for f in plan.faults):
        preempted = any("preempted" in kinds
                        for kinds in sup_json["exit_kinds"])
        if not (preempted and drained):
            ok = False
            report["failure"] = (
                "preempt plan but no graceful drain observed "
                f"(preempted_exit={preempted}, drained={bool(drained)})")
    if reshape:
        if not reshape_events:
            ok = False
            report["failure"] = ("--reshape given but no reshape_restore "
                                 "happened — vacuous reshape run")
    # Integrity gates: the fault must have triggered an ACTUAL in-process
    # rollback-and-replay (else the run is vacuous), and recovery must NOT
    # have leaned on a supervisor gang restart — the whole point of the
    # guard is recovering without one.
    if integrity_faults:
        rollbacks = events.read_events(event_path, "integrity_rollback")
        anomalies = events.read_events(event_path, "integrity_anomaly")
        sdc = events.read_events(event_path, "integrity_sdc")
        report["integrity"] = {
            "anomalies": [{k: r.get(k) for k in ("kind", "step", "window")}
                          for r in anomalies],
            "rollbacks": [{k: r.get(k)
                           for k in ("kind", "step", "restored_step",
                                     "next_epoch")} for r in rollbacks],
            "sdc_detections": [{k: r.get(k) for k in ("step", "culprits")}
                               for r in sdc],
        }
        if not rollbacks:
            ok = False
            report["failure"] = ("integrity plan but no rollback-and-replay "
                                 "happened — vacuous integrity run")
        elif sup_report.restarts != 0:
            ok = False
            report["failure"] = (
                f"integrity recovery leaned on a gang restart "
                f"(restarts={sup_report.restarts}) instead of in-process "
                f"rollback-and-replay")
    if baseline is not None:
        report["baseline_final_loss"] = baseline.get("final_loss")
        if (report["final_loss"] is not None
                and report["baseline_final_loss"] is not None):
            delta = abs(report["final_loss"]
                        - report["baseline_final_loss"])
            report["loss_delta"] = delta
            report["parity_ok"] = delta <= args.parity_atol
            ok = ok and report["parity_ok"]
        else:
            report["parity_ok"] = False
            ok = False
    report["ok"] = ok
    out = json.dumps(report, indent=2)
    print(out)
    if args.report:
        pathlib.Path(args.report).write_text(out + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
