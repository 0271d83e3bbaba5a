"""ParameterServerStrategy: async bounded-staleness training, for real.

The reference lineage names ``tf.distribute.experimental.
ParameterServerStrategy`` as the one execution model it never runs
(PAPER.md L57) — it recommends ring-allreduce over PS because a central
server is a bandwidth bottleneck, and this reproduction long kept the class
as a raising stub. This module builds it as a genuine **second execution
model** beside the gang-synchronous stack:

* **server rank** owns the authoritative parameters AND the optimizer
  state; it discovers pushed gradient packets, applies them in arrival
  order (recording that order in an apply log), publishes versioned
  parameter snapshots, checkpoints asynchronously, and checksums its
  authoritative leaves per apply-epoch
  (:func:`tpu_dist.training.integrity.host_leaf_checksums`);
* **worker ranks** run a collective-free hot loop — pull params, one local
  forward/backward, push grads — and never rendezvous with each other. A
  lost worker is a *non-event*: nobody waits on it, nothing restarts.

Transport is the host-side file protocol of
:mod:`tpu_dist.cluster.ps_transport` (atomic tmp+``os.replace``, the same
idiom as bootstrap rendezvous and checkpoint publish) — no sockets, no
``jax.distributed``, which is exactly what makes worker death free.

**Bounded staleness** (``TPU_DIST_PS_STALENESS``, default
:data:`~tpu_dist.cluster.ps_transport.DEFAULT_STALENESS`) is enforced at
pull time: a worker with more than S of its own pushes still unapplied
blocks until the server catches up. S=0 degenerates to per-worker
lock-step; ``TPU_DIST_PS_SYNC=1`` additionally makes the server gang-
synchronous (one packet from every live rank per round, applied in rank
order) — the measured *control* the straggler gate compares against.

**The exactness contract changes honestly.** The sync stack gates on
bit-parity; an async run has no bit-identical twin. What IS pinned:

* determinism given the apply-order log — worker RNG is derived from
  (rank, local step) alone, every apply records (rank, seq, base version),
  and :func:`replay_apply_log` re-applies the retained packets in logged
  order to bit-identical final checksums;
* bounded-staleness convergence — the async final loss lands within a
  stated tolerance of the sync control on the deterministic demo workload
  (gated by ``python -m tpu_dist.resilience --ps-chaos``);
* the straggler gate — a 10x-delayed worker costs <10% async throughput
  while the sync control collapses (ROADMAP's reason this model exists).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Optional

import numpy as np

from tpu_dist.cluster import ps_transport
from tpu_dist.cluster.ps_transport import (DEFAULT_STALENESS, PSDir,
                                           PS_DIR_ENV)
from tpu_dist.parallel.strategy import Strategy

logger = logging.getLogger("tpu_dist.parallel.ps")

#: Per-rank RNG stream spacing: worker r's local step k folds
#: ``(r + 1) * _RANK_STRIDE + k`` into the root key — disjoint streams per
#: rank, derived from coordinates alone so a replayed packet is
#: reproducible without any recorded randomness.
_RANK_STRIDE = 10_000_019


def tree_to_arrays(tree: Any) -> dict:
    """Flatten a pytree to ``{keystr: host ndarray}`` — the npz payload
    namespace shared by publish, push, and replay."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = np.asarray(leaf)
    return out


def arrays_to_tree(template: Any, arrays: dict) -> Any:
    """Rebuild ``template``'s structure from :func:`tree_to_arrays` output."""
    import jax

    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths_leaves:
        key = jax.tree_util.keystr(path)
        if key not in arrays:
            raise KeyError(f"PS snapshot missing array {key!r}")
        arr = arrays[key]
        if tuple(arr.shape) != tuple(np.shape(leaf)):
            raise ValueError(
                f"PS snapshot array {key!r} has shape {arr.shape}, "
                f"expected {np.shape(leaf)}")
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def worker_step_key(root_key, *, rank: int, local_step: int):
    """The step-derived RNG key for worker ``rank``'s ``local_step`` —
    a pure function of coordinates, the property that makes an apply-log
    replay exact."""
    import jax

    return jax.random.fold_in(root_key,
                              (rank + 1) * _RANK_STRIDE + local_step)


class ParameterServerStrategy(Strategy):
    """Async parameter-server training over host-side file transport.

    Role comes from ``TPU_DIST_PS_ROLE`` (or the ``role=`` argument):
    ``"worker"`` scopes a collective-free single-device strategy whose
    ``fit`` runs pull → local step → push (training/trainer.py), and
    ``"server"`` marks the process that runs :class:`PSServer`. Both sides
    share one :class:`~tpu_dist.cluster.ps_transport.PSDir` session
    directory (``TPU_DIST_PS_DIR``).
    """

    def __init__(self, ps_dir: Optional[str] = None, *,
                 role: Optional[str] = None, rank: Optional[int] = None,
                 num_workers: Optional[int] = None,
                 staleness: Optional[int] = None,
                 sync: Optional[bool] = None,
                 pull_timeout_s: Optional[float] = None):
        import jax

        ps_dir = ps_dir or os.environ.get(PS_DIR_ENV)
        if not ps_dir:
            raise ValueError(
                "ParameterServerStrategy needs a session directory: pass "
                f"ps_dir= or set ${PS_DIR_ENV}")
        # The worker hot loop is single-device and collective-free by
        # construction: the mesh is one local device, so nothing in a
        # compiled step can psum across workers even by accident.
        super().__init__(devices=[jax.local_devices()[0]])
        self.psdir = PSDir(ps_dir).ensure()
        self.role = role or ps_transport.role_from_env() or "worker"
        if self.role not in ("server", "worker"):
            raise ValueError(f"PS role must be server/worker, got "
                             f"{self.role!r}")
        self.rank = ps_transport.rank_from_env() if rank is None else int(rank)
        self.num_workers = (ps_transport.world_from_env()
                            if num_workers is None else int(num_workers))
        self.staleness = (ps_transport.staleness_from_env()
                          if staleness is None else max(0, int(staleness)))
        self.sync = ps_transport.sync_from_env() if sync is None else bool(sync)
        if self.sync:
            # Gang-synchronous control mode: every round waits for every
            # rank, so a worker running ahead of its own applies would
            # deadlock the round. Pin lock-step.
            self.staleness = 0
        self.pull_timeout_s = (ps_transport.pull_timeout_from_env()
                               if pull_timeout_s is None
                               else float(pull_timeout_s))
        self._pushed = 0
        self._last_version: Optional[int] = None
        logger.info("ParameterServerStrategy: role=%s rank=%d world=%d "
                    "staleness=%d sync=%s dir=%s", self.role, self.rank,
                    self.num_workers, self.staleness, self.sync, ps_dir)

    # -- role predicates -----------------------------------------------------

    @property
    def is_worker(self) -> bool:
        return self.role == "worker"

    @property
    def is_server(self) -> bool:
        return self.role == "server"

    @property
    def pushed(self) -> int:
        """Gradient packets this worker has pushed so far."""
        return self._pushed

    # -- worker transport -----------------------------------------------------

    def pull(self, params_template: Any) -> Optional[tuple]:
        """Blocking bounded-staleness pull: the freshest published params,
        or None once the server ordered STOP.

        Blocks while more than ``staleness`` of THIS worker's pushes are
        still unapplied — the per-worker window that both bounds how stale
        the gradients the server ingests can be and throttles a runaway
        worker. Verifies the snapshot against the manifest's published
        leaf checksums (transport-level SDC: a torn or bit-flipped
        snapshot must never train).
        """
        from tpu_dist.training import integrity

        deadline = time.perf_counter() + self.pull_timeout_s
        rank_key = str(self.rank)
        while True:
            loaded = self.psdir.load_published()
            if loaded is not None:
                manifest, arrays = loaded
                applied_mine = int(manifest.get("applied", {})
                                   .get(rank_key, 0))
                pending = self._pushed - applied_mine
                if pending <= self.staleness:
                    break
            if self.psdir.stop_requested() is not None:
                return None
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"PS pull timed out after {self.pull_timeout_s:.0f}s "
                    f"(rank {self.rank}: {self._pushed} pushed, server "
                    "silent) — is the server process alive?")
            time.sleep(0.002)
        integrity.verify_pull_checksums(arrays, manifest)
        self._last_version = int(manifest["version"])
        params = arrays_to_tree(params_template, arrays)
        return params, self._last_version

    def push(self, grads: Any, *, loss: float) -> int:
        """Publish one gradient packet; returns this worker's push seq."""
        seq = self._pushed
        self.psdir.push_grad(
            tree_to_arrays(grads), rank=self.rank, seq=seq,
            meta={"base_version": self._last_version,
                  "loss": float(loss), "time": time.time()})
        self._pushed += 1
        return seq

    def heartbeat(self, *, step: int) -> None:
        self.psdir.heartbeat(self.rank, step=step)

    def mark_done(self, *, steps: int) -> None:
        self.psdir.mark_done(self.rank, steps=steps)


class PSServer:
    """The server rank: authoritative params + optimizer state, arrival-
    order applies, versioned publishes, async checkpoints, apply-epoch
    checksums.

    Single-threaded by design (the async checkpointer owns the only
    background thread, and its writer never touches PS state): discover →
    apply → log → publish, in one loop, so the apply order IS the log
    order.
    """

    def __init__(self, model, psdir: PSDir, *, num_workers: int,
                 budget: int, seed: int = 0, sync: bool = False,
                 checkpoint_dir: Optional[str] = None,
                 publish_every: int = 1, ckpt_every: int = 8,
                 checksum_every: Optional[int] = None,
                 dead_after_s: float = 20.0,
                 retain_grads: bool = False,
                 idle_timeout_s: float = 300.0):
        import jax

        self.model = model
        self.psdir = psdir.ensure()
        self.num_workers = int(num_workers)
        self.budget = int(budget)
        self.sync = bool(sync)
        self.checkpoint_dir = checkpoint_dir
        self.publish_every = max(1, int(publish_every))
        self.ckpt_every = max(1, int(ckpt_every))
        # Apply-epoch length for the server-side checksum audit: default =
        # one "virtual gang step" worth of applies.
        self.checksum_every = max(1, int(checksum_every or num_workers))
        self.dead_after_s = float(dead_after_s)
        self.retain_grads = bool(retain_grads)
        self.idle_timeout_s = float(idle_timeout_s)

        model_vars = model.init(seed)
        self.variables = {
            "params": model_vars["params"],
            "state": model_vars["state"],
            "opt": model.optimizer.init(model_vars["params"]),
        }
        optimizer = model.optimizer

        def apply(params, opt_state, grads):
            return optimizer.update(grads, opt_state, params)

        self._apply = jax.jit(apply)
        self.applies = 0
        self.applied_by_rank: dict = {r: 0 for r in range(self.num_workers)}
        self._seen: set = set()
        self._ckpt_covered = 0  # applies covered by a published checkpoint
        self._t_first_apply: Optional[float] = None
        self._t_last_apply: Optional[float] = None
        self.restored_from: Optional[int] = None
        self._faults = self._arm_faults()

    # -- fault seam (the chaos runner addresses the server by apply index) ----

    @staticmethod
    def _arm_faults():
        from tpu_dist.resilience.faults import FAULT_PLAN_ENV, FaultPlan

        spec = os.environ.get(FAULT_PLAN_ENV)
        if not spec:
            return []
        rank = ps_transport.rank_from_env()
        from tpu_dist.resilience import events

        plan = FaultPlan.parse(spec)
        return [f for f in plan.for_process(rank, events.current_attempt())
                if f.kind == "kill"]

    def _check_faults(self) -> None:
        from tpu_dist.resilience import events

        for f in self._faults:
            if f.due_at_step(self.applies):
                events.maybe_log("fault_fired", kind="kill",
                                 at=f"server apply {self.applies}",
                                 exit_code=f.exit_code)
                logger.warning("fault injection: killing PS server at "
                               "apply %d (exit %d)", self.applies,
                               f.exit_code)
                os._exit(f.exit_code)

    # -- restore --------------------------------------------------------------

    def maybe_restore(self) -> None:
        """Server restart path: restore params/opt from the newest complete
        async checkpoint, rewind the apply log to it, and re-verify the
        restored leaves against the log's checksum epoch — storage
        corruption between checkpoint and restart must abort, not train.

        Packets applied after the restored step still sit in ``grads/``
        (deletion lags checkpoint coverage by contract), so the loop
        re-discovers and re-applies them on the new timeline.
        """
        if not self.checkpoint_dir:
            return
        from tpu_dist.training import checkpoint as ckpt_lib
        from tpu_dist.training import integrity

        step = ckpt_lib.latest_complete_step(self.checkpoint_dir)
        if step is None:
            return
        restored, step = ckpt_lib.restore(self.checkpoint_dir,
                                          self.variables, step=step)
        self.variables = restored
        self.applies = self._ckpt_covered = step
        self.restored_from = step
        log = self.psdir.read_apply_log()
        kept = []
        for r in log:
            if r.get("event") == "checksum_epoch":
                if int(r.get("applies", 0)) <= step:
                    kept.append(r)
            elif "rank" in r and int(r.get("apply", 0)) <= step:
                kept.append(r)
        self.psdir.rewrite_apply_log(kept)
        for rec in kept:
            if "rank" in rec:
                self.applied_by_rank[int(rec["rank"])] = (
                    self.applied_by_rank.get(int(rec["rank"]), 0) + 1)
                name = f"g-r{int(rec['rank'])}-{int(rec['seq']):08d}.npz"
                self._seen.add(name)
                if not self.retain_grads:
                    try:
                        (self.psdir.grads / name).unlink()
                    except OSError:
                        pass
        # Checksum-epoch re-verification at the restore point.
        epochs = [r for r in kept if r.get("event") == "checksum_epoch"
                  and int(r.get("applies", -1)) == step]
        if epochs:
            live = integrity.host_leaf_checksums(
                tree_to_arrays(self.variables["params"]))
            logged = {k: int(v) for k, v in epochs[-1]["checksums"].items()}
            if live != logged:
                raise integrity.IntegrityAbort(
                    f"PS server restore: restored params at apply {step} do "
                    "not match the apply log's checksum epoch — storage "
                    "corruption between checkpoint and restart")
        from tpu_dist.resilience import events

        events.maybe_log("ps_server_restore", step=step)
        logger.info("PS server restored apply %d from %s", step,
                    self.checkpoint_dir)

    # -- publish / checkpoint --------------------------------------------------

    def _publish(self) -> None:
        from tpu_dist.training import integrity

        arrays = tree_to_arrays(self.variables["params"])
        self.psdir.publish_params(
            arrays, version=self.applies, applied=self.applied_by_rank,
            checksums=integrity.host_leaf_checksums(arrays))

    def _checksum_epoch(self) -> None:
        from tpu_dist.resilience import events
        from tpu_dist.training import integrity

        sums = integrity.host_leaf_checksums(
            tree_to_arrays(self.variables["params"]))
        self.psdir.append_apply_log({
            "event": "checksum_epoch",
            "applies": self.applies,
            "epoch": self.applies // self.checksum_every,
            "checksums": sums,
        })
        events.maybe_log("ps_checksum_epoch", applies=self.applies,
                         n_leaves=len(sums))

    def _gc_grads(self) -> None:
        """Delete packets only once a PUBLISHED checkpoint covers their
        apply — a server killed mid-interval must find every uncovered
        packet still on disk to re-apply."""
        if self.retain_grads:
            return
        log = self.psdir.read_apply_log()
        for rec in log:
            if "rank" in rec and rec.get("apply", 0) <= self._ckpt_covered:
                try:
                    (self.psdir.grads /
                     f"g-r{int(rec['rank'])}-{int(rec['seq']):08d}.npz"
                     ).unlink()
                except OSError:
                    pass

    # -- liveness --------------------------------------------------------------

    def _live_ranks(self) -> list:
        done = self.psdir.done_ranks()
        live = []
        for r in range(self.num_workers):
            if r in done:
                continue
            age = self.psdir.heartbeat_age_s(r)
            if age is not None and age > self.dead_after_s:
                continue  # silent too long: dead, a non-event
            live.append(r)
        return live

    # -- the loop --------------------------------------------------------------

    def _apply_packet(self, path) -> bool:
        import jax

        from tpu_dist.observe import metrics

        loaded = PSDir.load_grad(path)
        self._seen.add(path.name)
        if loaded is None:
            return False  # raced a GC unlink; never a torn file
        meta, arrays = loaded
        grads = arrays_to_tree(self.variables["params"], arrays)
        new_params, new_opt = self._apply(
            self.variables["params"], self.variables["opt"], grads)
        self.variables["params"] = new_params
        self.variables["opt"] = new_opt
        self.applies += 1
        now = time.perf_counter()
        if self._t_first_apply is None:
            self._t_first_apply = now
        self._t_last_apply = now
        rank = int(meta["rank"])
        self.applied_by_rank[rank] = self.applied_by_rank.get(rank, 0) + 1
        lag = max(0.0, time.time() - float(meta.get("time", time.time())))
        metrics.observe_value("ps.apply_lag", lag)
        # The apply log is the bit-exact replay contract: coordinates
        # only, never wall-clock (lag lives in the ps.apply_lag metric).
        self.psdir.append_apply_log({
            "apply": self.applies, "rank": rank, "seq": int(meta["seq"]),
            "base_version": meta.get("base_version"),
            "loss": meta.get("loss"),
        })
        if self.applies % self.checksum_every == 0:
            jax.block_until_ready(new_params)
            self._checksum_epoch()
        if self.applies % self.publish_every == 0:
            self._publish()
        if self.checkpoint_dir and self.applies % self.ckpt_every == 0:
            self._save_async()
        return True

    def _save_async(self) -> None:
        if self._ckpt is not None:
            self._ckpt.save_async(self.variables, step=self.applies)

    def run(self) -> dict:
        """Serve until the apply budget is reached (STOP is then ordered)
        or every worker is done/dead with no packets pending. Returns the
        session stats the chaos runner and bench gate on."""
        from tpu_dist.resilience import events
        from tpu_dist.training.checkpoint import AsyncCheckpointer

        self._ckpt = (AsyncCheckpointer(self.checkpoint_dir)
                      if self.checkpoint_dir else None)
        self.maybe_restore()
        self._publish()  # version 0 (or the restored version): the
        # rendezvous — workers block in pull until this lands.
        events.maybe_log("ps_server_start", applies=self.applies,
                         budget=self.budget, sync=self.sync,
                         restored_from=self.restored_from)
        t0 = time.perf_counter()
        last_progress = t0
        stop_reason = None
        while True:
            self._check_faults()
            if self.applies >= self.budget:
                stop_reason = "budget"
                break
            pending = self.psdir.scan_grads(seen=self._seen)
            if self.sync:
                progressed = self._sync_round(pending)
            else:
                progressed = False
                for path in pending:
                    if self._apply_packet(path):
                        progressed = True
                    self._check_faults()
                    if self.applies >= self.budget:
                        break
            now = time.perf_counter()
            if progressed:
                last_progress = now
                # Coverage comes from the directory, not from bookkeeping:
                # a save_async handed to the writer is NOT durable until
                # latest_complete_step can see it, and a packet deleted on
                # the strength of an unfinished save would be unrecoverable
                # after a server kill.
                if self._ckpt is not None:
                    from tpu_dist.training import checkpoint as ckpt_lib

                    done_step = ckpt_lib.latest_complete_step(
                        self.checkpoint_dir)
                    if done_step is not None:
                        self._ckpt_covered = max(self._ckpt_covered,
                                                 done_step)
                    self._gc_grads()
                continue
            if not self._live_ranks():
                if not self.psdir.scan_grads(seen=self._seen):
                    stop_reason = "workers_done"
                    break
            if now - last_progress > self.idle_timeout_s:
                stop_reason = "idle_timeout"
                break
            time.sleep(0.002)
        wall_s = time.perf_counter() - t0
        self.psdir.write_stop(reason=stop_reason, applies=self.applies)
        self._publish()
        if self._ckpt is not None:
            self._ckpt.save_async(self.variables, step=self.applies)
            self._ckpt.close()
            self._ckpt_covered = self.applies
            self._gc_grads()
        # Throughput over the apply SPAN (first→last apply): the gated
        # number. Total wall includes worker jit compiles and process
        # startup — constant noise that would swamp a <10% gate at demo
        # scale.
        span_s = ((self._t_last_apply or 0.0) - (self._t_first_apply or 0.0))
        throughput = (round((self.applies - 1) / span_s, 6)
                      if span_s > 0 and self.applies > 1 else None)
        events.maybe_log("ps_server_stop", reason=stop_reason,
                         applies=self.applies, wall_s=round(wall_s, 6),
                         throughput_sps=throughput)
        return {
            "applies": self.applies,
            "wall_s": round(wall_s, 6),
            "apply_span_s": round(span_s, 6),
            "throughput_sps": throughput,
            "stop_reason": stop_reason,
            "applied_by_rank": {str(r): n for r, n in
                                sorted(self.applied_by_rank.items())},
            "restored_from": self.restored_from,
            "sync": self.sync,
        }

    def _sync_round(self, pending: list) -> bool:
        """Gang-synchronous control: apply exactly one packet from EVERY
        live rank, in rank order — the round advances at the slowest
        rank's pace, which is the collapse the straggler gate measures."""
        by_rank: dict = {}
        for path in pending:
            r = int(path.name.split("-")[1][1:])
            by_rank.setdefault(r, []).append(path)
        live = self._live_ranks()
        if not live:
            return False
        if not all(r in by_rank for r in live):
            return False  # round incomplete: wait for the stragglers
        for r in live:
            self._apply_packet(by_rank[r][0])
        return True


def replay_apply_log(psdir: PSDir, model, *, seed: int = 0) -> dict:
    """Re-apply the session's retained packets in logged order from the
    seed initialization; returns final ``{"applies", "checksums"}``.

    The reproducibility half of the PS exactness contract: arrival order
    is nondeterministic across runs, but any run is exactly reproducible
    GIVEN its log — same packets, same order, same optimizer math ⇒
    bit-identical parameters. Needs ``retain_grads=True`` on the recording
    server (GC'd packets cannot be replayed).
    """
    import jax

    from tpu_dist.training import integrity

    model_vars = model.init(seed)
    params = model_vars["params"]
    opt = model.optimizer.init(params)
    optimizer = model.optimizer
    apply = jax.jit(lambda p, o, g: optimizer.update(g, o, p))
    applies = 0
    for rec in psdir.read_apply_log():
        if "rank" not in rec:
            continue
        path = (psdir.grads /
                f"g-r{int(rec['rank'])}-{int(rec['seq']):08d}.npz")
        loaded = PSDir.load_grad(path)
        if loaded is None:
            raise FileNotFoundError(
                f"replay needs retained packet {path.name}; record with "
                "retain_grads=True")
        _, arrays = loaded
        params, opt = apply(params, opt, arrays_to_tree(params, arrays))
        applies += 1
    return {
        "applies": applies,
        "checksums": integrity.host_leaf_checksums(tree_to_arrays(params)),
    }
