"""Training-integrity guard: anomaly detection, SDC audits, rollback-replay.

Crash-shaped failures (worker death, torn writes, preemption) are covered by
:mod:`tpu_dist.resilience`; the failures that burn the most accelerator-hours
at pod scale are SEMANTIC — a NaN loss, an exploding gradient, or a silent
bit-flip on one replica that crashes nothing and quietly poisons every
subsequent checkpoint. This module is the detection-and-recovery layer that
makes the previously landed recovery paths *trigger themselves*:

**In-step health vector.** :func:`health_summary` folds three scalars —
non-finite count, global grad-norm², update-norm² — into the compiled train
step itself (:meth:`Trainer._pure_step` calls it on values the step already
computes), so detection adds zero extra dispatches. The trainer hands each
execution's ``f32[3]`` health output to :meth:`IntegrityGuard.on_execution`,
which starts a NON-blocking device→host copy and inspects the *previous*
execution's vector — the same one-behind lazy-fetch discipline as
``LazyLogs``, so the dispatch pipeline never stalls on a health read.
Thresholds: any non-finite is absolute; grad-norm is judged relative to an
EMA of its own history (``spike_factor`` × EMA after ``warmup`` clean steps).

**Cross-replica SDC audit (shard-aware).** Every ``audit_every_n`` steps the
guard runs a collective-FREE compiled program (``shard_map`` over the whole
mesh, one output row per device) that checksums the parameter tree per
device: leaf bytes are bitcast to ``uint32`` and wrap-summed, giving a
``[n_devices, n_leaves]`` table. Sharded leaves are consumed SHARD-LOCALLY
(``in_specs`` taken from each leaf's live ``NamedSharding``), so TP/
pipeline/MoE params audit just like replicated ones and the program still
contains no collective. Rows are compared ON HOST through the existing
collectives seam (:func:`~tpu_dist.parallel.collectives.host_all_gather`)
*within shard groups* derived from the same shardings
(:func:`~tpu_dist.parallel.mesh.shard_groups`): devices holding the same
shard of a leaf are replicas of that shard and must agree — a TP-sharded
kernel has one group per shard (column block), a replicated bias one global
group. On mismatch the per-leaf columns name the corrupted leaf,
shard-group, device and rank. Replicated training makes this divergence
otherwise invisible — every replica keeps producing plausible losses. A
leaf shard held by only ONE device has no replica to compare against; its
singleton group is vacuously consistent (on real meshes the data axis
replicates every shard).

**Rollback-and-replay.** A confirmed anomaly raises
:class:`RollbackAndReplay`; ``Trainer.fit`` catches it, restores the last
*published* checkpoint (``latest_complete_step``/``restore_model`` — the
same path a gang restart resumes through, minus the restart), resets the
data iterator to the epoch boundary and replays. Epoch-index-derived RNG
keys and cardinality==steps_per_epoch demo datasets make the replay exact,
so a recovered run reproduces the no-fault baseline bit-for-bit. If replay
hits the same (or an earlier) anomaly again, the next rollback goes one
published checkpoint further back (``latest_complete_step(before=...)``).
A ``rollback_budget`` bounds the loop: exhausting it raises
:class:`IntegrityAbort`, which ``run_entry`` maps to
:data:`~tpu_dist.resilience.faults.EXIT_INTEGRITY` so the Supervisor
classifies the exit ``integrity_abort`` — restarts won't help, operators
should triage.

Environment knobs (read by :func:`maybe_guard_from_env`, set by the chaos
CLI for integrity plans):

==================================  =========================================
``TPU_DIST_INTEGRITY``              ``1`` arms the guard inside ``fit``
``TPU_DIST_INTEGRITY_SPIKE``        grad-norm spike factor vs EMA (default 50)
``TPU_DIST_INTEGRITY_AUDIT_N``      SDC-audit period in steps (0 = off)
``TPU_DIST_INTEGRITY_BUDGET``       rollbacks before abort (default 3)
``TPU_DIST_INTEGRITY_QUARANTINE``   ``1`` = skip-and-log a batch window that
                                    already triggered a rollback instead of
                                    re-running it (breaks exact replay
                                    parity; for data-dependent poison)
``TPU_DIST_INTEGRITY_LOSS_SCALE``   static loss scale S: grad norms are
                                    divided by S before the spike EMA, so
                                    scaled-loss training is judged in true
                                    gradient units (default 1)
``TPU_DIST_INTEGRITY_BF16_SLACK``   spike-factor multiplier applied when the
                                    param tree is low-precision (bf16/f16)
                                    — quantization makes grad norms
                                    noisier, so the threshold widens
                                    instead of false-positives (default 4)
==================================  =========================================

The module also owns the BATCH-fault seam (:func:`install_batch_fault_hook`)
through which the fault injector corrupts a target step's batch
(``nan_loss``/``grad_spike``/``corrupt_batch`` fault kinds) without touching
training code — the same hook pattern as the collectives and checkpoint
seams.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Any, Optional

import jax
import numpy as np

logger = logging.getLogger("tpu_dist.integrity")

#: Fault kinds delivered through the batch seam (the injector corrupts the
#: target step's batch; detection is the health vector's job).
BATCH_FAULT_KINDS = ("nan_loss", "grad_spike", "corrupt_batch")

INTEGRITY_ENV = "TPU_DIST_INTEGRITY"
SPIKE_ENV = "TPU_DIST_INTEGRITY_SPIKE"
AUDIT_N_ENV = "TPU_DIST_INTEGRITY_AUDIT_N"
BUDGET_ENV = "TPU_DIST_INTEGRITY_BUDGET"
QUARANTINE_ENV = "TPU_DIST_INTEGRITY_QUARANTINE"
LOSS_SCALE_ENV = "TPU_DIST_INTEGRITY_LOSS_SCALE"
BF16_SLACK_ENV = "TPU_DIST_INTEGRITY_BF16_SLACK"

#: Param dtypes whose quantization noise warrants the wider
#: ``bf16_spike_slack`` threshold.
_LOW_PRECISION_DTYPES = ("bfloat16", "float16")


class RollbackAndReplay(Exception):
    """A confirmed anomaly: unwind to ``fit``'s rollback handler, restore
    the last published checkpoint, replay. Never escapes ``fit``."""

    def __init__(self, kind: str, gstep: int, **detail: Any):
        self.kind = kind
        self.gstep = int(gstep)
        self.detail = detail
        super().__init__(
            f"training-integrity anomaly {kind!r} at global step {gstep}"
            + (f" ({detail})" if detail else ""))


class IntegrityAbort(Exception):
    """Rollback budget exhausted — recovery by replay is not converging.
    Escapes ``fit``; ``run_entry`` maps it to ``EXIT_INTEGRITY``."""


# -- batch-fault seam ---------------------------------------------------------
# Module-global hook + install/fire pair, same shape as
# collectives.install_fault_hook and checkpoint.install_write_fault_hook.

_BATCH_FAULT_HOOK = None


def install_batch_fault_hook(hook):
    """Install (or, with None, remove) the batch fault hook.

    ``hook(first_gstep, k, x, y) -> (x, y)`` is called once per compiled
    execution with the window's first global step, its step count ``k`` and
    the (already device-placed) batch; it returns the batch to actually
    train on. Returns the previously installed hook.
    """
    global _BATCH_FAULT_HOOK
    prev = _BATCH_FAULT_HOOK
    _BATCH_FAULT_HOOK = hook
    return prev


def fire_batch_hook(first_gstep: int, k: int, x, y):
    """Run the installed batch hook (identity when none is installed).
    Called by the trainer hot loop right before each dispatch; the no-hook
    fast path is one global read and a compare."""
    hook = _BATCH_FAULT_HOOK
    if hook is None:
        return x, y
    return hook(first_gstep, k, x, y)


# -- in-step health vector ----------------------------------------------------

def health_summary(loss, grads, params, new_params):
    """The device-side health vector, computed INSIDE the train step.

    ``f32[3] = [nonfinite_count, grad_norm², update_norm²]`` from values the
    step already produced — no extra forward/backward work, and XLA fuses
    the reductions into the step program, so the vector costs a few scalar
    ops and one tiny output buffer. All three entries are replicated
    scalars (grads are all-reduced, params mirrored), so the trainer's
    lazy fetch moves 12 bytes.
    """
    import jax.numpy as jnp

    def _sumsq(tree):
        total = jnp.float32(0.0)
        for leaf in jax.tree_util.tree_leaves(tree):
            total = total + jnp.sum(jnp.square(
                jnp.asarray(leaf, jnp.float32)))
        return total

    gsq = _sumsq(grads)
    usq = _sumsq(jax.tree_util.tree_map(
        lambda a, b: jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32),
        new_params, params))
    bad = ((~jnp.isfinite(jnp.asarray(loss, jnp.float32))).astype(jnp.float32)
           + (~jnp.isfinite(gsq)).astype(jnp.float32)
           + (~jnp.isfinite(usq)).astype(jnp.float32))
    return jnp.stack([bad, gsq, usq])


def reduce_window_health(healths):
    """Fold a scanned execution's ``[k, 3]`` per-step health stack into one
    ``f32[3]``: non-finite counts sum; norms take the window max (a single
    spiked step must survive the fold)."""
    import jax.numpy as jnp

    return jnp.stack([healths[:, 0].sum(),
                      healths[:, 1].max(),
                      healths[:, 2].max()])


# -- cross-replica SDC audit --------------------------------------------------

def build_audit_checksum(mesh, leaf_shapes_dtypes, leaf_specs=None):
    """The compiled per-device checksum program for one param-tree layout.

    A ``shard_map`` over the WHOLE mesh: every device checksums its own
    local copy — or, for sharded leaves, its own SHARD — of each leaf
    (bytes bitcast to ``uint32``, wrap-summed) and contributes one
    ``[1, n_leaves]`` row; rows concatenate across devices to the global
    ``[n_devices, n_leaves]`` table. ``leaf_specs`` carries one
    ``PartitionSpec`` per leaf taken from the live arrays' shardings
    (``None`` = all replicated, the pre-shard-aware behavior); devices
    holding the same shard produce equal checksums, which is exactly the
    shard-group comparison :meth:`IntegrityGuard.audit` runs on host. No
    collective appears in the program — so its baselined comm payload is
    exactly 0 bytes, replicated and sharded alike.
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    names = tuple(mesh.axis_names)
    n_leaves = len(leaf_shapes_dtypes)
    if leaf_specs is None:
        leaf_specs = tuple(P() for _ in range(n_leaves))

    def per_device(*leaves):
        sums = []
        for leaf in leaves:
            flat = jnp.ravel(jnp.asarray(leaf, jnp.float32))
            sums.append(jnp.sum(
                jax.lax.bitcast_convert_type(flat, jnp.uint32),
                dtype=jnp.uint32))
        return jnp.stack(sums).reshape(1, n_leaves)

    shmapped = jax.shard_map(per_device, mesh=mesh,
                             in_specs=tuple(leaf_specs),
                             out_specs=P(names), check_vma=False)
    return jax.jit(shmapped)


def _leaf_audit_spec(leaf, mesh):
    """The audit ``in_spec`` for one live leaf: its own PartitionSpec when
    it is a NamedSharding over the audited mesh, else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    sh = getattr(leaf, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh == mesh:
        return PartitionSpec(*sh.spec)
    return PartitionSpec()


def _leaf_shard_groups(leaf, mesh):
    """Shard groups (lists of checksum-table row indices) for one leaf —
    one global group when the leaf is not sharded over this mesh."""
    from jax.sharding import NamedSharding

    from tpu_dist.parallel import mesh as mesh_lib

    sh = getattr(leaf, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh == mesh:
        return mesh_lib.shard_groups(sh, leaf.shape)
    return [list(range(mesh.devices.size))]


def host_leaf_checksums(arrays: dict) -> dict:
    """Host-side mirror of :func:`build_audit_checksum`'s per-leaf math:
    ``{key: uint32 wrap-sum of the f32 bytes}`` for a ``{key: ndarray}``
    mapping.

    Same bit pattern as the compiled audit (f32 ravel → uint32 bitcast →
    wrap-sum), but in numpy so the PS server can checksum its authoritative
    params per apply-epoch and workers can verify pulled snapshots WITHOUT
    a device program — the PS audit runs where the data already is, on
    host, between transport and training.
    """
    out = {}
    for key in sorted(arrays):
        flat = np.ravel(np.asarray(arrays[key], np.float32))
        out[key] = int(flat.view(np.uint32).sum(dtype=np.uint32))
    return out


def verify_pull_checksums(arrays: dict, manifest: dict) -> None:
    """Worker-side transport audit: raise :class:`IntegrityAbort` when a
    pulled parameter snapshot does not match the checksums its manifest
    published. The server checksummed these exact bytes at publish time, so
    a mismatch is transport/storage SDC — the one corruption class the
    server-side apply-epoch audit cannot see."""
    expected = manifest.get("checksums") or {}
    if not expected:
        return
    missing = sorted(k for k in expected if k not in arrays)
    if missing:
        raise IntegrityAbort(
            f"PS pull: snapshot v{manifest.get('version')} is missing "
            f"published leaves {missing[:4]}")
    live = host_leaf_checksums({k: arrays[k] for k in expected})
    bad = sorted(k for k in expected if live[k] != int(expected[k]))
    if bad:
        raise IntegrityAbort(
            f"PS pull: checksum mismatch on leaves {bad[:4]} of snapshot "
            f"v{manifest.get('version')} — corruption between server "
            "publish and worker read")


#: Unsigned view dtype per element width for the dtype-aware bit flip.
_FLIP_VIEWS = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def flip_param_bit(variables: dict, *, replica: int, bit: int = 22,
                   leaf: int = 0) -> dict:
    """Inject silent data corruption: XOR one bit of element 0 of parameter
    leaf ``leaf`` (flatten order), on ONE device's copy/shard only.

    Used by the ``bitflip`` fault kind (``bitflip@stepN:leafK:replicaR``).
    Rebuilds the array from per-device local buffers via
    ``jax.make_array_from_single_device_arrays`` so exactly one device's
    data diverges — the SDC model: nothing crashes, the loss stays
    plausible, only a cross-replica checksum can see it. For a SHARDED
    leaf the flip lands in that one device's shard, so the audit must
    localize it to the right shard group. In multi-process runs the caller
    has already matched the fault's rank to this process, so the flip
    lands on local replica 0; single-process multi-device runs use
    ``replica`` as the device position (sorted by device id, which matches
    the mesh row order the audit reports).

    The flip is dtype-aware: ``bit`` is taken modulo the element width, on
    an unsigned view of matching width — so the default ``bit=22`` hits
    f32 mantissa bit 22 and bf16 bit ``22 % 16 == 6``, the TOP mantissa
    bit (a ~2^-1 relative change). A byte-wise flip here would land on a
    numerically invisible low bf16 mantissa bit. Returns a description of
    what was flipped — including the ``effective_bit`` — for the event
    log.
    """
    params = variables["params"]
    flat, treedef = jax.tree_util.tree_flatten(params)
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    leaf_idx = int(leaf) % len(flat)
    arr = flat[leaf_idx]
    leaf_name = jax.tree_util.keystr(paths[leaf_idx][0])
    shards = sorted(arr.addressable_shards, key=lambda s: s.device.id)
    datas = [np.array(s.data) for s in shards]
    idx = 0 if jax.process_count() > 1 else replica % len(datas)
    buf = datas[idx].reshape(-1)
    width = buf.dtype.itemsize * 8
    view = buf.view(_FLIP_VIEWS[buf.dtype.itemsize])
    eff_bit = int(bit) % width
    view[0] ^= view.dtype.type(1 << eff_bit)
    rebuilt = jax.make_array_from_single_device_arrays(
        arr.shape, arr.sharding,
        [jax.device_put(d, s.device) for d, s in zip(datas, shards)])
    flat[leaf_idx] = rebuilt
    variables["params"] = jax.tree_util.tree_unflatten(treedef, flat)
    return {"leaf": leaf_name, "leaf_index": leaf_idx, "replica": idx,
            "device": int(shards[idx].device.id), "bit": int(bit),
            "effective_bit": eff_bit, "dtype": str(buf.dtype)}


# -- the guard ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IntegrityConfig:
    spike_factor: float = 50.0     # grad-norm anomaly = factor x EMA
    ema_decay: float = 0.9
    warmup_steps: int = 3          # clean executions before spike checks arm
    audit_every_n: int = 0         # SDC-audit period in global steps; 0 = off
    rollback_budget: int = 3       # rollbacks before IntegrityAbort
    quarantine: bool = False       # skip-and-log windows that caused rollback
    loss_scale: float = 1.0        # grad norms divided by this before the EMA
    bf16_spike_slack: float = 4.0  # spike-factor multiplier on bf16/f16 params

    @classmethod
    def from_env(cls) -> "IntegrityConfig":
        def _f(name, default):
            try:
                return float(os.environ.get(name, default))
            except ValueError:
                return default

        return cls(
            spike_factor=_f(SPIKE_ENV, 50.0),
            audit_every_n=int(_f(AUDIT_N_ENV, 0)),
            rollback_budget=int(_f(BUDGET_ENV, 3)),
            quarantine=os.environ.get(QUARANTINE_ENV) == "1",
            loss_scale=_f(LOSS_SCALE_ENV, 1.0),
            bf16_spike_slack=_f(BF16_SLACK_ENV, 4.0),
        )


class IntegrityGuard:
    """Per-fit integrity state machine, driven by the trainer hot loop.

    NOT a callback on purpose: callbacks with batch hooks force the trainer
    into per-step blocking loss fetches (``eager_loss``); the guard instead
    rides the loop directly and reads health one execution behind, so an
    armed guard costs the hot path one method call and zero added syncs.
    """

    def __init__(self, config: Optional[IntegrityConfig] = None):
        self.cfg = config or IntegrityConfig()
        self._strategy = None
        self.checkpoint_dir: Optional[str] = None
        #: (first_gstep, k, device f32[3]) of the newest execution — its
        #: host copy is in flight; it is judged when the NEXT execution
        #: lands (or at flush()).
        self._pending: Optional[tuple] = None
        self._ema: Optional[float] = None
        self._ema_n = 0
        self._rollbacks = 0
        self._last_anomaly_gstep: Optional[int] = None
        self._last_restored: Optional[int] = None
        self.quarantined: set = set()
        self._audit_fn = None
        self._audit_key = None
        self._audit_paths = None
        self._audit_groups = None
        self._audit_devices = None
        #: Low-precision param trees get the bf16_spike_slack threshold;
        #: detected once from the first execution's params.
        self._low_precision = False
        self._lp_known = False

    def bind(self, strategy, *, checkpoint_dir=None) -> "IntegrityGuard":
        self._strategy = strategy
        if checkpoint_dir is not None:
            self.checkpoint_dir = os.fspath(checkpoint_dir)
        return self

    # -- hot-loop surface ----------------------------------------------------

    def on_execution(self, first_gstep: int, k: int, health, params) -> None:
        """Called once per compiled execution, right after dispatch.

        Starts the new health vector's async device→host copy, then judges
        the PREVIOUS execution's (already-arrived) vector — one execution
        of detection lag buys a hot loop with no blocking fetch. Runs the
        SDC audit when the period is due.
        """
        prev = self._pending
        self._pending = (first_gstep, k, health)
        try:
            health.copy_to_host_async()
        except AttributeError:  # plain numpy in unit tests
            pass
        if params is not None and not self._lp_known:
            self._lp_known = True
            self._low_precision = any(
                str(getattr(l, "dtype", "")) in _LOW_PRECISION_DTYPES
                for l in jax.tree_util.tree_leaves(params))
        if prev is not None:
            self._judge(*prev)
        n = self.cfg.audit_every_n
        if n and first_gstep and first_gstep % n == 0 and params is not None:
            self.audit(params, gstep=first_gstep)

    def flush(self) -> None:
        """Judge the in-flight health vector NOW — called at the epoch
        boundary BEFORE callbacks run, so a poisoned final step can never
        reach ModelCheckpoint's epoch-end save."""
        prev, self._pending = self._pending, None
        if prev is not None:
            self._judge(*prev)

    def should_skip(self, first_gstep: int, k: int) -> bool:
        """Quarantine check: True when this window already caused a
        rollback and the config says replaying it would just re-poison."""
        if not self.cfg.quarantine or not self.quarantined:
            return False
        return any(first_gstep + i in self.quarantined for i in range(k))

    # -- rollback bookkeeping (trainer-facing) -------------------------------

    def rollback_plan(self, rb: RollbackAndReplay) -> Optional[int]:
        """The ``before=`` bound for ``latest_complete_step``: None for a
        first-time anomaly (restore the newest published step); the last
        restored step when replay already hit this anomaly again without
        making progress — then the next restore must go strictly older."""
        if (self._last_anomaly_gstep is not None
                and rb.gstep <= self._last_anomaly_gstep
                and self._last_restored is not None):
            return self._last_restored
        return None

    def note_rollback(self, rb: RollbackAndReplay,
                      restored: Optional[int]) -> None:
        self._last_anomaly_gstep = rb.gstep
        self._last_restored = restored
        self._pending = None  # pre-rollback health is stale

    # -- judgement -----------------------------------------------------------

    def _judge(self, first_gstep: int, k: int, health) -> None:
        h = np.asarray(health, dtype=np.float64).reshape(-1)
        nonfinite, gsq, usq = float(h[0]), float(h[1]), float(h[2])
        if (nonfinite > 0 or not math.isfinite(gsq)
                or not math.isfinite(usq)):
            self._anomaly("nan_loss", first_gstep, k,
                          nonfinite=nonfinite)
        # Loss-scaled training reports S x larger raw grad norms; dividing
        # by the static scale judges (and logs) in true gradient units.
        gnorm = (math.sqrt(max(gsq, 0.0))
                 / max(float(self.cfg.loss_scale), 1e-30))
        factor = self.cfg.spike_factor
        if self._low_precision:
            factor *= max(float(self.cfg.bf16_spike_slack), 1.0)
        if (self._ema is not None and self._ema_n >= self.cfg.warmup_steps
                and gnorm > factor * max(self._ema, 1e-12)):
            self._anomaly("grad_spike", first_gstep, k,
                          grad_norm=round(gnorm, 6),
                          ema=round(self._ema, 6),
                          factor=round(factor, 6))
        d = self.cfg.ema_decay
        self._ema = gnorm if self._ema is None else d * self._ema + (1 - d) * gnorm
        self._ema_n += 1

    def _anomaly(self, kind: str, first_gstep: int, k: int,
                 **detail: Any) -> None:
        from tpu_dist.resilience import events

        events.maybe_log("integrity_anomaly", kind=kind, step=first_gstep,
                         window=k, attempt=events.current_attempt(), **detail)
        logger.warning("integrity anomaly %r at global step %d (+%d): %s",
                       kind, first_gstep, k, detail)
        self._rollbacks += 1
        if self.cfg.quarantine:
            self.quarantined.update(range(first_gstep, first_gstep + k))
        if self._rollbacks > self.cfg.rollback_budget:
            events.maybe_log("integrity_budget_exhausted", kind=kind,
                             step=first_gstep,
                             rollbacks=self._rollbacks - 1,
                             budget=self.cfg.rollback_budget)
            raise IntegrityAbort(
                f"rollback budget ({self.cfg.rollback_budget}) exhausted; "
                f"latest anomaly {kind!r} at step {first_gstep}")
        raise RollbackAndReplay(kind, first_gstep, **detail)

    # -- SDC audit -----------------------------------------------------------

    def audit(self, params, *, gstep: int) -> bool:
        """One shard-group checksum compare; True when every group agrees.

        Devices holding the same shard of a leaf (per its live
        NamedSharding) are replicas of that shard and must produce equal
        checksums; replicated leaves form one global group — so the audit
        covers TP/pipeline/MoE param trees, not just mirrored ones.
        Disagreement is a confirmed SDC anomaly: the per-leaf "bisection"
        names the corrupted leaf, shard-group, device and rank from the
        already-computed table (no extra dispatch), then the rollback
        machinery takes over.
        """
        mesh = getattr(self._strategy, "mesh", None)
        if mesh is None:
            return True
        flat_with_paths = jax.tree_util.tree_flatten_with_path(params)[0]
        leaves = [leaf for _, leaf in flat_with_paths]
        specs = tuple(_leaf_audit_spec(leaf, mesh) for leaf in leaves)
        key = tuple((tuple(l.shape), str(l.dtype), str(s))
                    for l, s in zip(leaves, specs))
        if self._audit_fn is None or self._audit_key != key:
            self._audit_fn = build_audit_checksum(mesh, key, specs)
            self._audit_key = key
            self._audit_paths = [jax.tree_util.keystr(p)
                                 for p, _ in flat_with_paths]
            self._audit_groups = [_leaf_shard_groups(leaf, mesh)
                                  for leaf in leaves]
            self._audit_devices = [(int(d.id), int(d.process_index))
                                   for d in mesh.devices.flat]
        table = self._audit_fn(*leaves)
        rows = self._host_rows(table)
        # Bisection: name every (device, leaf) cell that deviates from its
        # SHARD GROUP's majority value. A group with no strict majority
        # (e.g. one corrupted member out of two) localizes the mismatch to
        # the group, so every member is named.
        culprits = []
        for col, groups in enumerate(self._audit_groups):
            for gi, members in enumerate(groups):
                vals = rows[members, col]
                if bool((vals == vals[0]).all()):
                    continue
                uniq, counts = np.unique(vals, return_counts=True)
                if int(counts.max()) * 2 > len(members):
                    majority = uniq[int(np.argmax(counts))]
                    bad = [m for m, v in zip(members, vals)
                           if v != majority]
                else:
                    bad = list(members)
                for row in bad:
                    dev_id, rank = self._audit_devices[row]
                    culprits.append({"replica": int(row),
                                     "device": dev_id,
                                     "rank": rank,
                                     "shard_group": gi,
                                     "leaf": self._audit_paths[col]})
        if not culprits:
            return True
        from tpu_dist.resilience import events

        events.maybe_log("integrity_sdc", step=gstep, culprits=culprits,
                         attempt=events.current_attempt())
        logger.warning("SDC audit mismatch at step %d: %s", gstep, culprits)
        self._anomaly("sdc", gstep, 1, culprits=culprits)
        return False

    @staticmethod
    def _host_rows(table) -> np.ndarray:
        """The global ``[n_devices, n_leaves]`` checksum table on host,
        exchanged through the collectives seam: each process contributes
        its addressable rows and ``host_all_gather`` stacks them (a
        single-process run gathers trivially but still rides the seam, so
        the audit's comm accounting is uniform)."""
        shards = sorted(table.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        local = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
        from tpu_dist.parallel.collectives import host_all_gather

        gathered = np.asarray(host_all_gather(local))
        return gathered.reshape(-1, local.shape[-1])


def maybe_guard_from_env() -> Optional[IntegrityGuard]:
    """An :class:`IntegrityGuard` when ``$TPU_DIST_INTEGRITY=1`` (set by the
    chaos CLI for integrity fault plans, or by an operator), else None —
    an unarmed fit pays one env read."""
    if os.environ.get(INTEGRITY_ENV) != "1":
        return None
    return IntegrityGuard(IntegrityConfig.from_env())
