"""Distributed dataset delivery: per-host streams -> global device arrays.

The TPU-native replacement for TF's distributed-dataset wrappers (SURVEY.md
D14): where ``experimental_distribute_dataset`` built per-worker iterators and
PerReplica value structures (tf:python/distribute/input_lib.py), here each
process iterates its host-local numpy pipeline and every step's local batch is
assembled into ONE global ``jax.Array`` sharded over the mesh's data axis
(``jax.make_array_from_process_local_data`` multi-process,
``jax.device_put`` single-process). The jitted train step consumes the global
array; XLA sees a single SPMD program — there is no per-replica bookkeeping.

Two delivery modes, matching the reference's two supported paths (SURVEY.md
§3.4):

* **with_options(OFF)** (the reference's chosen mode, tf_dist_example.py:34-37):
  every worker iterates the full stream with an independent shuffle; each
  process's batch is its own contribution, so the effective global batch is
  ``local_batch x num_processes`` distinct samples (README.md:113-120).
* **distribute (AUTO/DATA/FILE)** (the commented alternative,
  tf_dist_example.py:36): the user batches to GLOBAL_BATCH_SIZE; each process
  keeps its 1/num_processes slice, so the global array's leading dim is the
  global batch size.
"""

from __future__ import annotations

import logging
from typing import Iterator

import numpy as np

from tpu_dist.data.pipeline import AutoShardPolicy, Dataset, _map_structure
from tpu_dist.data.sharding import resolve_policy, shard_dataset

logger = logging.getLogger("tpu_dist.data")


def _find_unseeded_shuffle(dataset) -> bool:
    """True if the recorded combinator chain contains a shuffle whose order
    differs per process (``seed=None`` + reshuffle => each worker draws an
    independent RNG, pipeline.py:284-288)."""
    node = dataset
    while node is not None:
        t = getattr(node, "_transform", None)
        if (t is not None and t[0] == "shuffle"
                and (t[1].get("seed") is None or t[1].get("auto_seeded"))):
            # seed=None => fresh rng per pass; auto_seeded => a fixed seed
            # drawn independently PER PROCESS at construction
            # (pipeline.py shuffle) — both diverge across processes.
            return True
        node = getattr(node, "_parent", None)
    return False


def require_replicated_determinism(dataset, num_shards: int,
                                 num_processes: int, path: str) -> None:
    """Guard for meshes whose data axis does not span all processes.

    On pipe/model-spanning meshes several processes sit at the same data
    coordinate and must contribute byte-identical local batches to the same
    global-array region — a nondeterministic pipeline silently diverges
    training (ADVICE r4). An unseeded shuffle detected in the chain is a
    *certain* divergence, so it is rejected; opaque generators can't be
    proven either way, so everything else gets the warning.
    """
    if num_shards >= num_processes:
        return
    if _find_unseeded_shuffle(dataset):
        raise ValueError(
            f"{path}: unseeded shuffle on a mesh whose data axis does not "
            f"span all {num_processes} processes — processes at the same "
            "data coordinate would draw different samples for the same "
            "global batch region and training would silently diverge. "
            "Pass shuffle(..., seed=...) so same-coordinate processes "
            "produce identical streams.")
    logger.warning(
        "%s on a mesh whose data axis does not span all %d processes: "
        "processes at the same data coordinate MUST yield identical "
        "batches (deterministic pipeline, seeded or no shuffle) or "
        "training silently diverges", path, num_processes)


class DistributedDataset:
    """Iterable of mesh-placed global batches for a strategy.

    ``strategy.experimental_distribute_dataset(dataset)`` returns one of these
    (the tf_dist_example.py:36 analog); ``fit`` also auto-wraps plain Datasets
    the way the Keras trainer does (keras:src/backend/tensorflow/
    trainer.py:750-755, SURVEY.md D15).
    """

    def __init__(self, dataset: Dataset, strategy,
                 policy: AutoShardPolicy | None = None,
                 prefetch: int | None = 2,
                 allow_device_transform: bool = False):
        import jax

        self._strategy = strategy
        self._num_processes = jax.process_count()
        self._process_index = jax.process_index()
        # Input shards follow the DATA-axis process structure, not the raw
        # process count: pipe/model-only multi-process meshes put every
        # process at the same data coordinate, and those processes must
        # feed IDENTICAL replicated batches (strategy.input_shard_info).
        info = getattr(strategy, "input_shard_info", None)
        self._num_shards, self._shard_id = (
            info() if info is not None
            else (self._num_processes, self._process_index))
        effective = (policy if policy is not None
                     else dataset.auto_shard_policy)
        if effective == AutoShardPolicy.OFF:
            # Reference mode: full stream per worker, local batch as produced.
            self._local = dataset
            self._policy = AutoShardPolicy.OFF
            require_replicated_determinism(
                dataset, self._num_shards, self._num_processes,
                "AutoShardPolicy.OFF")
        else:
            self._policy = resolve_policy(dataset, self._num_shards, effective)
            # ADVICE r4: same-data-coordinate processes get the same shard
            # id, so the sharded stream they build must be deterministic too
            # — the hazard is not OFF-specific.
            require_replicated_determinism(
                dataset, self._num_shards, self._num_processes,
                f"AutoShardPolicy.{self._policy.name}")
            self._local = shard_dataset(
                dataset, self._num_shards, self._shard_id,
                self._policy, pre_batched=True)
        # Vectorized chain rewrite (the Grappler map_and_batch/vectorize
        # analog, data/vectorize.py): index math + batched gathers replace
        # the per-element generator walk when the chain's shape allows.
        # The u8-over-the-wire + scale-on-device split is only taken when
        # the consumer declares it will apply device transforms (the
        # Trainer does; a user iterating this object in a custom loop has
        # no such obligation, so their batches must stay host-normalized
        # float32).
        from tpu_dist.data import vectorize

        fast = vectorize.try_rewrite(
            self._local,
            defer_scale_to_device=None if allow_device_transform else False)
        if fast is not None:
            self._local = fast
        # Host input off the step critical path by default (SURVEY.md §3.4 /
        # hard-part #5): background-prefetch the local stream unless the user
        # already did, mirroring TF's distribute-path auto-prefetch.
        # ``prefetch=None`` opts out.
        if prefetch and not getattr(self._local, "_prefetched", False):
            self._local = self._local.prefetch(prefetch)
        if self._num_processes > 1:
            logger.info(
                "DistributedDataset: policy=%s process=%d/%d",
                self._policy.name, self._process_index, self._num_processes)

    @property
    def auto_shard_policy(self) -> AutoShardPolicy:
        return self._policy

    @property
    def device_transform(self):
        """Jittable fn the trainer applies to the placed x batch inside the
        compiled step (None for plain pipelines) — the device half of the
        u8-over-the-wire normalization split."""
        return getattr(self._local, "_device_transform", None)

    def iter_local(self) -> Iterator:
        """Validated HOST batches (numpy) — the pre-placement stream. Used by
        the multi-step (steps_per_execution) path, which stacks K host
        batches before one device placement."""
        devices_per_process = len(self._strategy.mesh.local_devices)

        for batch in self._local:
            batch = _map_structure(np.asarray, batch)
            leading = {a.shape[0] for a in _leaves(batch)}
            if len(leading) != 1:
                raise ValueError(
                    f"batch components disagree on batch dim: {leading}")
            (b,) = leading
            if b % devices_per_process:
                raise ValueError(
                    f"per-process batch {b} not divisible by {devices_per_process} "
                    "local device(s); adjust the batch size so every replica "
                    "gets an equal shard (same constraint as TF per-replica "
                    "splitting)")
            yield batch

    def __iter__(self) -> Iterator:
        for batch in self.iter_local():
            yield self._strategy.distribute_batch(batch)


def _leaves(tree):
    out = []
    _map_structure(out.append, tree)
    return out
