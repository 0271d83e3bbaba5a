"""Mid-epoch gang reform: the step-granular half of elastic training.

The epoch-boundary :class:`~tpu_dist.resilience.injector.RejoinGate` lets a
relaunched worker back in only at the next ``on_epoch_begin``; a rank lost
mid-epoch still costs a full gang restart. This module closes that gap with
the gang-generation protocol (``tpu_dist.cluster.bootstrap``):

1. The Supervisor detects a dead rank and publishes a *reform request* for
   generation g+1 into the shared gang directory.
2. Every survivor's :class:`StepRejoinGate` sees the request at its next step
   boundary (the same drain seam PreemptionDrain uses) and raises
   :class:`GangReform` out of the hot loop.
3. ``Trainer.fit`` catches it: publishes the in-flight async checkpoint, acks
   the reform, re-initializes the collective clique under generation g+1
   (``bootstrap.reinitialize``), restores the last complete checkpoint, and
   meets the one relaunched rank at a ``generation_rendezvous`` — survivors
   keep their process; only the clique is reformed.
4. Replay from the restored epoch re-derives the same per-epoch RNG keys
   (rollback-and-replay discipline), so the final losses are bit-identical
   to a fault-free run.
"""

from __future__ import annotations

import os
import time
from typing import Optional

from tpu_dist.resilience import events
from tpu_dist.training.callbacks import Callback


class GangReform(Exception):
    """Raised out of the fit hot loop when a reform request is pending.

    Control transfer, not an error: ``Trainer.fit`` catches it at the retry
    seam (next to ``RollbackAndReplay``) and runs the survivor side of the
    reform protocol before resuming the epoch loop.
    """

    def __init__(self, request: dict, *, seen_at: float):
        self.request = request
        self.generation = int(request["generation"])
        self.lost_ranks = list(request.get("lost_ranks") or [])
        #: time.time() when the gate observed the request — the drain clock's
        #: zero point (drain_s = publish-ack time minus this).
        self.seen_at = seen_at
        super().__init__(
            f"gang reform requested: generation {self.generation}, "
            f"lost rank(s) {self.lost_ranks}")


class StepRejoinGate(Callback):
    """Step-boundary reform gate + generation-namespaced epoch barrier.

    Polls the gang directory for a pending reform request on every
    ``on_batch_end`` / ``on_epoch_begin`` (one ``stat`` of a small JSON file
    — the same cost class as PreemptionDrain's flag check) and raises
    :class:`GangReform` when one targets a newer generation than ours.
    Otherwise it holds each epoch boundary at a
    :func:`~tpu_dist.cluster.bootstrap.generation_rendezvous` on the
    ``epoch * steps_per_epoch`` step coordinate, so the whole gang — current
    generation stamped into the marker namespace — steps together.
    """

    wants_batches = True

    def __init__(self, directory: str, *, rank: int, world: int,
                 steps_per_epoch: int, timeout_s: float = 120.0):
        self.directory = directory
        self.rank = int(rank)
        self.world = int(world)
        self.steps_per_epoch = int(steps_per_epoch)
        self.timeout_s = float(timeout_s)
        self.generation: Optional[int] = None
        #: (generation, step) of the last rendezvous passed — lets
        #: ``_gang_reform`` run the post-restore barrier explicitly without
        #: the next ``on_epoch_begin`` repeating it.
        self._met_at: Optional[tuple] = None

    def on_train_begin(self) -> None:
        from tpu_dist.cluster import bootstrap

        # A relaunched worker carries the reformed generation in its env;
        # a survivor that raced the supervisor's commit adopts the published
        # file. Take the max so neither side can drag the gang backwards.
        self.generation = max(bootstrap.current_generation(),
                              bootstrap.read_generation(self.directory))

    def _check_reform(self) -> None:
        from tpu_dist.cluster import bootstrap

        req = bootstrap.read_reform_request(self.directory)
        if req is not None and int(req["generation"]) > (self.generation or 0):
            raise GangReform(req, seen_at=time.monotonic())

    def on_batch_end(self, step: int, logs: dict) -> None:
        self._check_reform()

    def rendezvous(self, *, step: int, epoch: Optional[int] = None) -> None:
        """Meet the gang at ``step`` under the current generation."""
        from tpu_dist.cluster import bootstrap

        coord = (self.generation, step)
        if self._met_at == coord:
            return
        t0 = time.monotonic()
        # abort_check: a rank parked here while a peer dies would otherwise
        # wait out the whole barrier timeout — the missing rank can never
        # publish THIS generation's marker. Raising GangReform from inside
        # the wait sends this rank into the reform path immediately.
        ranks = bootstrap.generation_rendezvous(
            self.directory, generation=self.generation or 0, step=step,
            rank=self.rank, world=self.world, timeout_s=self.timeout_s,
            abort_check=self._check_reform)
        wait_s = time.monotonic() - t0
        self._met_at = coord
        log = events.log_from_env()
        if log is not None:
            log.append("rejoin_rendezvous", attempt=events.current_attempt(),
                       generation=self.generation, step=step, epoch=epoch,
                       ranks=ranks, wait_s=round(wait_s, 6))

    def on_epoch_begin(self, epoch: int) -> None:
        self._check_reform()
        self.rendezvous(step=epoch * self.steps_per_epoch, epoch=epoch)


def maybe_step_rejoin_gate(*, steps_per_epoch: int) -> Optional[StepRejoinGate]:
    """A :class:`StepRejoinGate` when ``$TPU_DIST_GANG_DIR`` names the gang
    directory, else None. Gang coordinates come from ``$TPU_DIST_REJOIN_WORLD``
    / ``$TPU_DIST_REJOIN_RANK`` (same override convention as the epoch gate —
    supervised single-process workers each see ``jax.process_index() == 0``);
    ``$TPU_DIST_REJOIN_TIMEOUT_S`` bounds every barrier wait (default 120).
    """
    from tpu_dist.cluster import bootstrap

    directory = os.environ.get(bootstrap.GANG_DIR_ENV)
    if not directory:
        return None
    world = os.environ.get("TPU_DIST_REJOIN_WORLD")
    rank = os.environ.get("TPU_DIST_REJOIN_RANK")
    if world is None:
        world = bootstrap.process_count()
    if rank is None:
        rank = bootstrap.process_index()
    timeout_s = float(os.environ.get("TPU_DIST_REJOIN_TIMEOUT_S", "120"))
    return StepRejoinGate(directory, rank=int(rank), world=int(world),
                          steps_per_epoch=steps_per_epoch,
                          timeout_s=timeout_s)
