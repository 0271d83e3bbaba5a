"""Supervisable training entry points.

A supervised worker is an ordinary training script with three properties:

1. it trains with ``fit(checkpoint_dir=...)`` so a restart resumes from the
   newest complete checkpoint instead of step 0;
2. it converts a liveness verdict
   (:class:`~tpu_dist.cluster.liveness.PeerUnavailableError`) into the
   protocol exit code :data:`~tpu_dist.resilience.faults.
   EXIT_PEER_UNAVAILABLE` so the supervisor restarts it as a victim rather
   than treating it as a crash;
3. it reports its result as one machine-parseable ``RESULT:{...}`` stdout
   line (the same convention as ``tests/multiprocess_harness.py``).

:func:`run_entry` wraps any callable in (2)+(3); :func:`demo_train` is the
built-in deterministic workload — a synthetic-MNIST run of the reference CNN
(SURVEY.md R5) small enough for CI, deterministic enough that a killed-and-
resumed run reproduces the uninterrupted run's final loss bit-for-bit (the
trainer derives each epoch's RNG keys from the epoch index alone, and the
dataset's cardinality equals ``steps_per_epoch``, so epoch N sees identical
batches whether or not the process was restarted in between).

A fourth property makes a worker ELASTIC: :func:`run_entry` installs a
SIGTERM seam (:func:`install_sigterm_handler`) before training starts, so a
preemption notice — from the cloud provider, from the Supervisor's grace
policy, or from an injected ``preempt`` fault — triggers the graceful drain:
the :class:`~tpu_dist.resilience.injector.PreemptionDrain` callback stops the
fit at the next step boundary, ``on_train_end`` publishes any in-flight
``save_async``, and the worker exits
:data:`~tpu_dist.resilience.faults.EXIT_PREEMPTED` — all inside a bounded
deadline (``TPU_DIST_PREEMPT_DEADLINE_S``): a watchdog hard-exits a drain
that wedges, and the Supervisor's SIGKILL escalation backstops even that.
Resume stays exactly-reproducible because the drain never publishes torn
mid-epoch state — the restarted attempt replays the interrupted epoch from
its last epoch-boundary checkpoint with the same epoch-derived RNG keys.

Configuration comes through the environment so the supervisor can launch
the same argv for every worker of every attempt:

====================================  =======================================
``TPU_DIST_CHECKPOINT_DIR``           checkpoint/resume directory (unset =
                                      no checkpointing, no resume)
``TPU_DIST_DEMO_EPOCHS``              epochs (default 3)
``TPU_DIST_DEMO_STEPS_PER_EPOCH``     steps per epoch (default 4)
``TPU_DIST_DEMO_BATCH``               global batch size (default 32)
``TPU_DIST_DEMO_STRATEGY``            ``mirrored`` = data-parallel over all
                                      local devices (the elastic/reshape
                                      demo); default: single-device
``TPU_DIST_DEMO_SHARDED``             ``1`` = per-epoch checkpoints use the
                                      v2 sharded layout
``TPU_DIST_PREEMPT_DEADLINE_S``       graceful-drain watchdog deadline
                                      (default 60)
``TPU_DIST_ENTRY``                    ``module:callable`` to run instead of
                                      :func:`demo_train` (``python -m
                                      tpu_dist.resilience.entrypoints``)
====================================  =======================================
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Callable, Optional

from tpu_dist.resilience import events
from tpu_dist.resilience.faults import (EXIT_INTEGRITY,
                                        EXIT_PEER_UNAVAILABLE, EXIT_PREEMPTED)

CHECKPOINT_DIR_ENV = "TPU_DIST_CHECKPOINT_DIR"
ENTRY_ENV = "TPU_DIST_ENTRY"
PREEMPT_DEADLINE_ENV = "TPU_DIST_PREEMPT_DEADLINE_S"


# -- graceful-preemption seam -------------------------------------------------
# Module-level so the trainer (via injector.maybe_preemption_drain) and the
# entry-point wrapper observe the same request without passing state through
# the fit call chain. One process == one preemption lifecycle.

_PREEMPT_LOCK = threading.Lock()
_PREEMPT_ARMED = False
_PREEMPT_REQUESTED_AT: Optional[float] = None


def preemption_armed() -> bool:
    """True once :func:`install_sigterm_handler` ran in this process — the
    trainer arms its drain callback off this, so unsupervised fits never pay
    the per-step flag check."""
    return _PREEMPT_ARMED


def preemption_requested() -> bool:
    return _PREEMPT_REQUESTED_AT is not None


def preemption_requested_at() -> Optional[float]:
    """``time.monotonic()`` of the first SIGTERM, or None."""
    return _PREEMPT_REQUESTED_AT


def _drain_deadline_s() -> float:
    try:
        return float(os.environ.get(PREEMPT_DEADLINE_ENV, "60"))
    except ValueError:
        return 60.0


def install_sigterm_handler() -> None:
    """Arm the graceful-preemption seam (idempotent, main thread only).

    On SIGTERM: record the request (the ``PreemptionDrain`` callback stops
    training at the next step boundary), log it
    (``preempt_requested``), and start the drain watchdog — a daemon
    timer that hard-exits the process if the drain outlives its deadline,
    so a wedged drain (a hung collective inside the final commit) cannot
    outstall the supervisor's own SIGKILL escalation."""
    global _PREEMPT_ARMED
    import signal

    def _on_sigterm(signum, frame):
        global _PREEMPT_REQUESTED_AT
        with _PREEMPT_LOCK:
            if _PREEMPT_REQUESTED_AT is not None:
                return  # duplicate notice; drain already underway
            _PREEMPT_REQUESTED_AT = time.monotonic()
        deadline = _drain_deadline_s()
        events.maybe_log("preempt_requested", deadline_s=deadline,
                         attempt=events.current_attempt())
        print(f"tpu_dist.resilience: SIGTERM received — draining at the "
              f"next step boundary (deadline {deadline:.0f}s)",
              file=sys.stderr, flush=True)

        def _watchdog():
            time.sleep(deadline)
            # Still alive past the deadline: the drain wedged. Exit hard
            # with a crash code (NOT EXIT_PREEMPTED — the checkpoint may be
            # torn, and the supervisor must not classify this as a clean
            # drain).
            events.maybe_log("preempt_drain_timeout", deadline_s=deadline,
                             attempt=events.current_attempt())
            os._exit(1)

        threading.Thread(target=_watchdog, daemon=True,
                         name="tpu-dist-preempt-watchdog").start()

    signal.signal(signal.SIGTERM, _on_sigterm)
    _PREEMPT_ARMED = True


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def demo_dataset(*, n: int, batch: int, seed: int = 0):
    """Synthetic MNIST-shaped data, identical in every process and attempt."""
    import numpy as np

    from tpu_dist.data.pipeline import Dataset

    rng = np.random.RandomState(seed)
    x = rng.rand(n, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 10, size=(n,)).astype(np.int32)
    return Dataset.from_tensor_slices((x, y)).batch(batch)


def demo_train() -> dict:
    """The chaos-demo workload: reference CNN on synthetic MNIST.

    Returns ``{"final_loss": ..., "epochs_run": ..., "losses": [...]}``;
    under ``TPU_DIST_CHECKPOINT_DIR`` a restarted run resumes and its
    ``final_loss`` matches the uninterrupted run's exactly.
    """
    import contextlib

    from tpu_dist.models.cnn import build_and_compile_cnn_model

    epochs = _env_int("TPU_DIST_DEMO_EPOCHS", 3)
    steps_per_epoch = _env_int("TPU_DIST_DEMO_STEPS_PER_EPOCH", 4)
    batch = _env_int("TPU_DIST_DEMO_BATCH", 32)
    # Dataset cardinality == steps_per_epoch: the load-bearing determinism
    # property (module docstring) — every epoch consumes exactly one pass.
    ds = demo_dataset(n=batch * steps_per_epoch, batch=batch)
    # The elastic/reshape chaos plans run data-parallel over however many
    # devices THIS attempt's launcher provisioned (the Supervisor resizes
    # the gang between attempts via XLA_FLAGS) — losses are insensitive to
    # the device count because the global batch is fixed, so a run resumed
    # on a different mesh still reproduces the baseline bit-for-bit.
    scope = contextlib.nullcontext()
    if os.environ.get("TPU_DIST_DEMO_STRATEGY", "").lower() == "mirrored":
        from tpu_dist.parallel.strategy import MirroredStrategy

        scope = MirroredStrategy().scope()
    with scope:
        model = build_and_compile_cnn_model(learning_rate=0.01)
        callbacks = []
        ckpt_dir = os.environ.get(CHECKPOINT_DIR_ENV)
        if ckpt_dir and os.environ.get("TPU_DIST_DEMO_SHARDED") == "1":
            from tpu_dist.training.callbacks import ModelCheckpoint

            # Passing the callback explicitly (same dir) suppresses fit's
            # auto-appended v1 ModelCheckpoint — the per-epoch saves then
            # exercise the v2 sharded layout reshape-on-restore stitches.
            callbacks.append(ModelCheckpoint(ckpt_dir, sharded=True))
        history = model.fit(
            ds, epochs=epochs, steps_per_epoch=steps_per_epoch, verbose=0,
            callbacks=callbacks, checkpoint_dir=ckpt_dir)
    losses = [round(float(l), 10) for l in history.history.get("loss", [])]
    return {
        "final_loss": losses[-1] if losses else None,
        "epochs_run": len(losses),
        "losses": losses,
    }


def demo_ps_worker() -> dict:
    """The PS-chaos worker workload: same CNN/synthetic-MNIST demo as
    :func:`demo_train`, but fit under a :class:`~tpu_dist.parallel.
    ps_strategy.ParameterServerStrategy` scope — pull → local step → push,
    no collective, terminated by the server's STOP. Every worker consumes
    the SAME dataset (seed 0) so async-vs-sync convergence is tightly
    comparable on the demo; real deployments shard per rank.

    Configured by ``TPU_DIST_PS_DIR``/``_RANK``/``_WORLD``/``_STALENESS``
    (+ the ``TPU_DIST_DEMO_*`` knobs above).
    """
    from tpu_dist.models.cnn import build_and_compile_cnn_model
    from tpu_dist.parallel.ps_strategy import ParameterServerStrategy

    epochs = _env_int("TPU_DIST_DEMO_EPOCHS", 3)
    steps_per_epoch = _env_int("TPU_DIST_DEMO_STEPS_PER_EPOCH", 4)
    batch = _env_int("TPU_DIST_DEMO_BATCH", 32)
    ds = demo_dataset(n=batch * steps_per_epoch, batch=batch)
    strategy = ParameterServerStrategy()
    with strategy.scope():
        model = build_and_compile_cnn_model(learning_rate=0.01)
        history = model.fit(ds, epochs=epochs,
                            steps_per_epoch=steps_per_epoch, verbose=0)
    losses = [round(float(l), 10) for l in history.history.get("loss", [])]
    return {
        "role": "worker",
        "rank": strategy.rank,
        "pushes": strategy.pushed,
        "final_loss": losses[-1] if losses else None,
        "losses": losses,
    }


def demo_ps_server() -> dict:
    """The PS-chaos server workload: owns params + optimizer state, applies
    pushed gradients until the apply budget (``TPU_DIST_PS_BUDGET``,
    default epochs*steps*world) is spent, then evaluates the final
    parameters on the demo dataset — the ``final_loss`` the convergence
    gate compares against the sync control's."""
    import jax
    import numpy as np

    from tpu_dist.cluster import ps_transport
    from tpu_dist.cluster.ps_transport import PSDir
    from tpu_dist.models.cnn import build_and_compile_cnn_model
    from tpu_dist.parallel.ps_strategy import PSServer

    epochs = _env_int("TPU_DIST_DEMO_EPOCHS", 3)
    steps_per_epoch = _env_int("TPU_DIST_DEMO_STEPS_PER_EPOCH", 4)
    batch = _env_int("TPU_DIST_DEMO_BATCH", 32)
    world = ps_transport.world_from_env()
    budget = _env_int("TPU_DIST_PS_BUDGET", epochs * steps_per_epoch * world)
    ps_dir = os.environ.get(ps_transport.PS_DIR_ENV)
    if not ps_dir:
        raise ValueError(f"demo_ps_server needs ${ps_transport.PS_DIR_ENV}")
    model = build_and_compile_cnn_model(learning_rate=0.01)
    server = PSServer(
        model, PSDir(ps_dir), num_workers=world, budget=budget,
        sync=ps_transport.sync_from_env(),
        checkpoint_dir=os.environ.get(CHECKPOINT_DIR_ENV),
        ckpt_every=_env_int("TPU_DIST_PS_CKPT_EVERY", 8),
        retain_grads=os.environ.get("TPU_DIST_PS_RETAIN_GRADS") == "1")
    stats = server.run()
    # Final-parameter eval on the demo dataset: the PS analog of the sync
    # demo's last-epoch loss, and the number the convergence gate reads.
    loss_obj = model.loss
    fwd = jax.jit(lambda p, s, x: model.apply(p, s, x, training=False)[0])
    losses = []
    for xb, yb in demo_dataset(n=batch * steps_per_epoch,
                               batch=batch).as_numpy_iterator():
        losses.append(float(loss_obj(
            fwd(server.variables["params"], server.variables["state"], xb),
            yb)))
    return {
        "role": "server",
        "final_loss": round(float(np.mean(losses)), 10) if losses else None,
        **stats,
    }


def run_entry(fn: Callable[[], Optional[dict]]) -> int:
    """Run ``fn`` under the resilience protocol; returns the exit code.

    Emits the ``RESULT:`` line on success; maps PeerUnavailableError to
    EXIT_PEER_UNAVAILABLE (logged as ``peer_unavailable``) and any other
    exception to 1 (logged as ``worker_error``). Arms the SIGTERM seam
    first: a run that a preemption notice drained returns
    :data:`EXIT_PREEMPTED` (logged as ``preempt_drained`` with the
    measured drain duration) and emits NO ``RESULT:`` line — the run did
    not finish; its checkpoint, published during the drain, is the
    hand-off to the restarted attempt.
    """
    from tpu_dist.cluster.liveness import PeerUnavailableError
    from tpu_dist.training.integrity import IntegrityAbort

    install_sigterm_handler()
    try:
        result = fn()
    except PeerUnavailableError as exc:
        events.maybe_log("peer_unavailable", error=str(exc))
        print(f"tpu_dist.resilience: giving up on dead peer: {exc}",
              file=sys.stderr, flush=True)
        return EXIT_PEER_UNAVAILABLE
    except IntegrityAbort as exc:
        # Rollback-and-replay did not converge: a restart would restore the
        # same checkpoints and replay into the same wall. Exit with the
        # dedicated code so the Supervisor classifies ``integrity_abort``
        # and does NOT burn its restart budget.
        events.maybe_log("integrity_abort", error=str(exc))
        print(f"tpu_dist.resilience: integrity rollback budget exhausted: "
              f"{exc}; exiting {EXIT_INTEGRITY} (integrity_abort)",
              file=sys.stderr, flush=True)
        return EXIT_INTEGRITY
    except Exception as exc:  # surfaced via exit code; supervisor restarts
        events.maybe_log("worker_error", error=f"{type(exc).__name__}: {exc}")
        import traceback

        traceback.print_exc()
        return 1
    if preemption_requested():
        # fit() returned because PreemptionDrain stopped it; every callback
        # (including ModelCheckpoint's async close) has already finalized,
        # so the last epoch-boundary checkpoint is published by now.
        drain_s = time.monotonic() - (preemption_requested_at() or 0.0)
        from tpu_dist.observe import metrics as metrics_lib

        metrics_lib.observe_value("elastic.drain_s", drain_s)
        events.maybe_log("preempt_drained", drain_s=round(drain_s, 6),
                         attempt=events.current_attempt())
        print(f"tpu_dist.resilience: drain complete in {drain_s:.3f}s; "
              f"exiting {EXIT_PREEMPTED} (preempted)",
              file=sys.stderr, flush=True)
        return EXIT_PREEMPTED
    if result is not None:
        print("RESULT:" + json.dumps(result), flush=True)
    return 0


def resolve_entry() -> Callable[[], Optional[dict]]:
    """The callable named by ``$TPU_DIST_ENTRY`` (``module:callable``),
    defaulting to :func:`demo_train`."""
    spec = os.environ.get(ENTRY_ENV)
    if not spec:
        return demo_train
    mod_name, sep, fn_name = spec.partition(":")
    if not sep or not mod_name or not fn_name:
        raise ValueError(
            f"${ENTRY_ENV} must be 'module:callable', got {spec!r}")
    import importlib

    fn = getattr(importlib.import_module(mod_name), fn_name)
    if not callable(fn):
        raise TypeError(f"{spec} is not callable")
    return fn


if __name__ == "__main__":
    # Delegate to the canonical module instance: under ``python -m`` this
    # file executes as ``__main__``, a SECOND module object — arming the
    # preemption seam here would leave the instance the trainer imports
    # (tpu_dist.resilience.entrypoints, via maybe_preemption_drain) unarmed
    # and the drain callback permanently off.
    from tpu_dist.resilience import entrypoints as _canonical

    sys.exit(_canonical.run_entry(_canonical.resolve_entry()))
