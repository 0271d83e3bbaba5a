"""The training engine: jitted SPMD train step + Keras-2-style fit loop.

Re-provides the Keras trainer + distributed optimizer path (SURVEY.md D15-D17,
§3.3) the reference drives through ``model.fit(x=dataset, epochs=10,
steps_per_epoch=20)`` (tf_dist_example.py:59). The idiom shift:

TF reference                          | here
--------------------------------------|------------------------------------
tf.function traces the step once      | jax.jit compiles the WHOLE step (fwd,
(graph, Grappler, per-op kernels)     | loss, bwd, all-reduce, update) into
                                      | one XLA program — always compiled
strategy.run + PerReplica values      | one global batch array, sharded on the
                                      | mesh data axis; no per-replica values
replica_context.all_reduce(SUM) on    | nothing explicit: params are
grads (keras optimizer:151-160)       | replicated, batch is sharded, so the
                                      | loss-mean's gradient REQUIRES a
                                      | cross-replica sum — XLA's partitioner
                                      | emits the AllReduce (over ICI/DCN) and
                                      | overlaps it with compute
merge_call per-variable updates       | optimizer update fused into the step
PerReplica metric reduce on host      | metric state replicated in-program

Because the loss is the mean over the *global* (sharded) batch and parameters
are replicated, the distributed step is numerically identical to a
single-device step over the concatenated batch — the reference's verified
invariant (identical losses on every worker, SURVEY.md §3.5).

Epoch semantics are Keras-2-era (SURVEY.md D15 era note): one persistent
iterator across epochs when ``steps_per_epoch`` is set, re-created (fresh
shuffle) on exhaustion.
"""

from __future__ import annotations

import itertools
import logging
import sys
import time
from typing import Any, Optional, Sequence

import jax
import numpy as np

from tpu_dist.cluster import bootstrap
from tpu_dist.data.distribute import DistributedDataset
from tpu_dist.data.pipeline import Dataset
from tpu_dist.training.callbacks import (CallbackList, History, LazyLogs,
                                         StopTraining)
from tpu_dist.utils import profiler
from tpu_dist.utils.progbar import ProgressBar

logger = logging.getLogger("tpu_dist.trainer")


def _aux_loss_total(state_tree):
    """Sum of every state leaf keyed 'aux_loss' (model-internal auxiliary
    losses — Keras add_loss analog; see parallel/expert.py). 0.0 when the
    model declares none, so pure models trace identically."""
    import jax.numpy as jnp

    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state_tree)[0]:
        last = path[-1] if path else None
        key = getattr(last, "key", None)
        if key == "aux_loss":
            total = total + jnp.asarray(leaf, jnp.float32)
    return total


def jnp_stack_keys(root_key, base: int, k: int):
    """[k, keydim] stacked fold_in keys for a scanned multi-step execution."""
    import jax.numpy as jnp

    return jax.vmap(lambda i: jax.random.fold_in(root_key, i))(
        base + jnp.arange(k))


def _current_job():
    """The active multi-tenant job scope — checked through sys.modules so
    a solo run that never imports :mod:`tpu_dist.jobs` pays nothing, not
    even the import (the jobs runtime's solo no-op contract)."""
    mod = sys.modules.get("tpu_dist.jobs.runtime")
    return mod.current_job() if mod is not None else None


#: Monotonic Trainer generation counter — the program-cache key component
#: that keeps one model's successive trainers (recompiles) from aliasing
#: each other's pool-cached programs.
_TRAINER_SERIALS = itertools.count()


class Trainer:
    """Owns device-resident training variables and the compiled steps."""

    def __init__(self, model):
        from tpu_dist.parallel.strategy import get_strategy

        self.model = model
        # Mesh acquisition goes through the job runtime when a job scope
        # is active: the strategy is the job's leased submesh slice, and
        # compiled programs land in the pool-owned cache (_acquire_program)
        # instead of on this instance alone.
        self._job = _current_job()
        self._serial = next(_TRAINER_SERIALS)
        if self._job is not None:
            self.strategy = model.strategy or self._job.strategy
        else:
            self.strategy = model.strategy or get_strategy()
        self.variables: Optional[dict] = None  # params/state/opt/metrics
        self._train_step = None
        self._eval_step = None
        self._predict_fn = None
        self._iterator = None
        self._iterator_source = None
        self._iterator_kind = "device"
        self._prefetcher = None
        self._bucket_bytes = 0
        self._multi_step = None
        self._built_policy: Optional[str] = None
        self._metric_init_fn = None
        self._loss_acc_init_fn = None
        self._class_weight: Optional[dict] = None
        #: Per-dataset jittable x-batch transforms (u8-over-the-wire
        #: normalization split, data/vectorize.py) — trace-time constants
        #: of the compiled steps, so a change invalidates the cache.
        self._device_transform = None
        self._eval_transform = None

    @staticmethod
    def _transform_key(t):
        """Semantic identity for device transforms: scale transforms with
        equal (op, scale) are the same program even when each
        DistributedDataset built a fresh closure — comparing by object
        identity would re-jit the step on EVERY fit()/evaluate() call."""
        if t is None:
            return None
        op, k = getattr(t, "_op", None), getattr(t, "_scale", None)
        return ("scale", op, k) if k is not None else id(t)

    def _sync_device_transform(self, dist, *, role: str) -> None:
        """Adopt ``dist``'s device transform for the given step family,
        recompiling if it changed. Train and eval keep separate slots so a
        fit with a u8-transform training set and a plain validation set
        doesn't thrash the caches every epoch."""
        t = getattr(dist, "device_transform", None)
        if role == "train":
            if self._transform_key(t) != self._transform_key(
                    self._device_transform):
                self._device_transform = t
                self._train_step = None
                self._multi_step = None
        else:
            if self._transform_key(t) != self._transform_key(
                    self._eval_transform):
                self._eval_transform = t
                self._eval_step = None
                self._predict_fn = None

    def _maybe_invalidate_for_policy(self) -> None:
        """Drop cached compiled steps when the global mixed-precision policy
        changed after they were traced — compute_dtype() is read at trace
        time, so a stale cache would silently keep the old dtype."""
        from tpu_dist.models.policy import policy

        current = policy()
        if self._built_policy is not None and self._built_policy != current:
            logger.info("precision policy changed %s -> %s; recompiling steps",
                        self._built_policy, current)
            self._train_step = None
            self._multi_step = None
            self._eval_step = None
            self._predict_fn = None
        self._built_policy = current

    # -- variable materialization (D4: mirrored init, chief broadcast) -------

    def ensure_variables(self, seed: int = 0) -> None:
        if self.variables is not None:
            return
        carried = getattr(self.model, "_carryover", None)
        if carried is not None:
            # Weights survive a recompile (Keras semantics); optimizer slots
            # are rebuilt for the (possibly new) optimizer.
            self.model._carryover = None
            host_params = jax.tree_util.tree_map(np.asarray, carried["params"])
            host = {
                "params": host_params,
                "state": jax.tree_util.tree_map(np.asarray, carried["state"]),
                "opt": self.model.optimizer.init(host_params)
                if self.model.optimizer else (),
            }
        else:
            model_vars = self.model.init(seed)
            host = {
                "params": model_vars["params"],
                "state": model_vars["state"],
                "opt": self.model.optimizer.init(model_vars["params"])
                if self.model.optimizer else (),
            }
        # Place onto the mesh; multi-process jobs broadcast process 0's
        # values so every replica starts identical (SURVEY.md D4, §3.2).
        # The strategy owns the per-leaf policy: mirrored everywhere on a
        # data(/seq) mesh, Megatron shards for params/optimizer under a
        # 'model' axis (parallel/tensor.py).
        placed = self.strategy.place_variables(host["params"], host)
        placed["metrics"] = self._init_metric_states()
        self.variables = placed
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(
            host["params"]))
        logger.info("%s: materialized %d parameters on %d replica(s)",
                    self.model.name, n_params, self.strategy.num_replicas_in_sync)

    def _device_zero_fn(self, make_host_tree):
        """A cached no-arg jit producing ``make_host_tree()`` as replicated
        device arrays. These zero-states are re-created every epoch; building
        them host-side (``strategy.replicate``) pays a host->device
        placement per leaf, while a compiled constant program is ~free."""
        rep = self.strategy.param_sharding()

        def zeros():
            import jax.numpy as jnp

            return jax.tree_util.tree_map(jnp.asarray, make_host_tree())

        out_sh = jax.tree_util.tree_map(lambda _: rep, jax.eval_shape(zeros))
        return jax.jit(zeros, out_shardings=out_sh)

    def _init_metric_states(self):
        if self._metric_init_fn is None:
            metrics = tuple(self.model.metrics)
            self._metric_init_fn = self._device_zero_fn(
                lambda: tuple(m.init() for m in metrics))
        return self._metric_init_fn()

    def _bounded_dispatch(self) -> bool:
        """True when in-flight compiled executions must be bounded to one.

        XLA:CPU runs every partition's thunks on one shared intra-op pool;
        with free-running async dispatch, a later execution's thunks can be
        queued ahead of an earlier execution's unfinished collective
        rendezvous and starve it — the runtime aborts the process after its
        40 s rendezvous termination timeout (observed on a 1-core host).
        Blocking on each execution's result keeps rendezvous pairs
        adjacent. The hazard is per-process (one shared pool per process),
        so this keys off LOCAL device count: multi-process CPU clusters
        with one device per process keep the pipeline, as do TPU/GPU —
        tiny steps there are dispatch-bound and pipelining is the point
        (BASELINE.md hard-part #5)."""
        return (jax.default_backend() == "cpu"
                and len(self.strategy.mesh.local_devices) > 1)

    def _init_loss_acc(self):
        if self._loss_acc_init_fn is None:
            self._loss_acc_init_fn = self._device_zero_fn(
                lambda: (np.float32(0.0), np.float32(0.0)))
        return self._loss_acc_init_fn()

    # -- compiled steps -------------------------------------------------------

    def _scoped(self, fn):
        """``fn``, traced under this trainer's strategy scope. Layers pick
        their kernels from the ACTIVE mesh at trace time (the flash
        kernel's per-shard mapping, the pipeline and expert schedules), so
        they must see the mesh the step is compiled for whether or not the
        caller's ``fit()`` sits inside ``strategy.scope()`` — the
        reference's own script calls it outside. On a multi-chip TPU mesh
        an unscoped trace hands the partitioner a bare Mosaic call, which
        it refuses to partition."""
        strategy = self.strategy

        def scoped(*args):
            with strategy.scope():
                return fn(*args)

        return scoped

    def _pure_step(self):
        """The un-jitted SPMD train step: (vars..., x, y, rng) -> (loss,
        vars...). Shared by the single-step jit and the scanned multi-step."""
        model, loss_obj, optimizer = (self.model, self.model.loss,
                                      self.model.optimizer)
        metrics = tuple(model.metrics)

        import jax.numpy as jnp

        class_weight = self._class_weight
        device_transform = self._device_transform

        def step(params, state, opt_state, metric_states, loss_acc, x, y, rng):
            if device_transform is not None:
                # The device half of the wire-dtype split (u8 arrives, scale
                # happens here) — fused by XLA into the first conv/matmul.
                x = device_transform(x)

            def loss_fn(p):
                logits, new_state = model.apply(p, state, x, training=True,
                                                rng=rng)
                # Model-internal auxiliary losses (the Keras add_loss
                # analog): any state leaf named 'aux_loss' — e.g. the MoE
                # load-balance term (parallel/expert.py, pre-scaled by the
                # layer) — joins the training objective. Metrics and
                # evaluate() keep reporting the pure task loss.
                aux = _aux_loss_total(new_state)
                if class_weight is not None:
                    # Keras class_weight semantics: scale each sample's loss
                    # contribution by its class's weight (default 1.0)
                    # before the batch-size mean. Built with per-class
                    # where() — an index table would CLAMP labels outside
                    # its range under jit, silently mis-weighting them.
                    if not jnp.issubdtype(y.dtype, jnp.integer):
                        raise ValueError(
                            "class_weight requires sparse integer labels; "
                            f"got labels of dtype {y.dtype}")
                    per = loss_obj.per_example(logits, y)
                    if per.shape != y.shape:
                        raise ValueError(
                            "class_weight requires per-example labels "
                            f"matching the loss (labels {y.shape} vs "
                            f"per-example loss {per.shape})")
                    w = jnp.ones_like(per)
                    for c, wt in class_weight.items():
                        w = jnp.where(y == c, jnp.float32(wt), w)
                    return (per * w).mean() + aux, (logits, new_state)
                return loss_obj(logits, y) + aux, (logits, new_state)

            (loss, (logits, new_state)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            with profiler.layer_scope("optimizer"):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
            new_metrics = tuple(
                m.update(ms, logits, y) for m, ms in zip(metrics, metric_states))
            # Device-side epoch-loss accumulator — the epoch 'loss' reported to
            # History/callbacks is the epoch mean (Keras semantics), not the
            # final batch's sample, and accumulating on device keeps the hot
            # loop free of host syncs.
            new_acc = (loss_acc[0] + loss, loss_acc[1] + 1.0)
            # In-step integrity health vector (tpu_dist.training.integrity):
            # f32[3] from values this step already computed — a few fused
            # scalar reductions, one tiny fresh (non-donated) output, read
            # one execution behind by the guard. Always present so an armed
            # guard reuses the SAME compiled program as an unarmed fit.
            from tpu_dist.training.integrity import health_summary

            # XLA fuses the health sums with the update: one scope.
            with profiler.layer_scope("optimizer"):
                health = health_summary(loss, grads, params, new_params)
            return (loss, new_params, new_state, new_opt, new_metrics,
                    new_acc, health)

        return step

    def _pure_step_bucketed(self, bucket_bytes: int):
        """The explicit-schedule variant of :meth:`_pure_step`: forward/
        backward runs per data shard under ``shard_map`` and the gradient
        tree is reduced by :func:`~tpu_dist.parallel.collectives.
        bucketed_all_reduce` in reverse-topological size buckets, instead
        of leaving one fused end-of-step AllReduce to the XLA partitioner.
        Each bucket is an independent psum launch the latency-hiding
        scheduler can overlap with the remaining backward compute.

        Parity contract: shards are equal-sized (iter_local validates
        divisibility), so the mean-of-per-shard-means loss and the
        bucket-packed gradient reduction match the fused schedule to float
        tolerance — NOT bitwise; sums are reassociated (gated by allclose
        in benchmarks/step_bench.py and tests/test_step_perf.py).
        """
        model, loss_obj, optimizer = (self.model, self.model.loss,
                                      self.model.optimizer)
        metrics = tuple(model.metrics)

        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from tpu_dist.parallel import collectives

        class_weight = self._class_weight
        device_transform = self._device_transform
        mesh = self.strategy.mesh
        axis = self.strategy.data_axis

        def shard_body(params, state, x, y, rng):
            def loss_fn(p):
                logits, new_state = model.apply(p, state, x, training=True,
                                                rng=rng)
                aux = _aux_loss_total(new_state)
                if class_weight is not None:
                    if not jnp.issubdtype(y.dtype, jnp.integer):
                        raise ValueError(
                            "class_weight requires sparse integer labels; "
                            f"got labels of dtype {y.dtype}")
                    per = loss_obj.per_example(logits, y)
                    if per.shape != y.shape:
                        raise ValueError(
                            "class_weight requires per-example labels "
                            f"matching the loss (labels {y.shape} vs "
                            f"per-example loss {per.shape})")
                    w = jnp.ones_like(per)
                    for c, wt in class_weight.items():
                        w = jnp.where(y == c, jnp.float32(wt), w)
                    return (per * w).mean() + aux, (logits, new_state)
                return loss_obj(logits, y) + aux, (logits, new_state)

            (loss, (logits, new_state)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            loss = jax.lax.pmean(loss, axis)
            grads = collectives.bucketed_all_reduce(
                grads, axis, collectives.ReduceOp.MEAN,
                bucket_bytes=bucket_bytes)
            # Cross-replica state mean (sync-BatchNorm-like semantics for
            # stateful layers); a pure model's empty state tree is free.
            new_state = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, axis), new_state)
            return loss, grads, logits, new_state

        in_specs = (P(), P(), P(axis), P(axis), P())
        out_specs = (P(), P(), P(axis), P())
        sharded = jax.shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)

        def step(params, state, opt_state, metric_states, loss_acc, x, y,
                 rng):
            if device_transform is not None:
                x = device_transform(x)
            loss, grads, logits, new_state = sharded(params, state, x, y, rng)
            with profiler.layer_scope("optimizer"):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
            new_metrics = tuple(
                m.update(ms, logits, y)
                for m, ms in zip(metrics, metric_states))
            new_acc = (loss_acc[0] + loss, loss_acc[1] + 1.0)
            from tpu_dist.training.integrity import health_summary

            with profiler.layer_scope("optimizer"):
                health = health_summary(loss, grads, params, new_params)
            return (loss, new_params, new_state, new_opt, new_metrics,
                    new_acc, health)

        return step

    def _pure_train_step(self):
        """The schedule the compiled steps build from: bucketed when the
        model compiled with ``gradient_bucket_bytes > 0``, else fused."""
        if self._bucket_bytes > 0:
            return self._scoped(self._pure_step_bucketed(self._bucket_bytes))
        return self._scoped(self._pure_step())

    def _sync_step_knobs(self) -> None:
        """Adopt the model's gradient-schedule knob; a changed bucket size
        is a trace-time property, so the compiled steps rebuild."""
        bb = int(getattr(self.model, "gradient_bucket_bytes", 0) or 0)
        if bb != self._bucket_bytes:
            self._bucket_bytes = bb
            self._train_step = None
            self._multi_step = None

    def _out_shardings(self):
        rep = self.strategy.param_sharding()

        def rep_like(tree):
            return jax.tree_util.tree_map(lambda _: rep, tree)

        v = self.variables
        acc = self._init_loss_acc()
        p_sh = self.strategy.variable_shardings(v["params"], v["params"])
        o_sh = self.strategy.variable_shardings(v["params"], v["opt"])
        return (None, p_sh, rep_like(v["state"]),
                o_sh, rep_like(v["metrics"]), rep_like(acc), rep)

    def _acquire_program(self, kind: str, builder, *variant):
        """Build — or acquire — one compiled program. Solo runs call the
        builder directly: the exact pre-jobs path. Under an active job
        scope the program lives in the pool's
        :class:`~tpu_dist.jobs.runtime.MeshRuntime` cache instead, keyed
        by job, model identity, and every trace-time dimension the
        invalidation logic tracks (policy, device transform, class
        weights) — so the pool owns its compiled-program population and
        a dimension that thrashes back becomes a cache hit, not a
        recompile."""
        if self._job is None:
            return builder()
        # The serial (not id(), which the allocator reuses) keys programs
        # to THIS trainer generation: a model recompile makes a new
        # Trainer — and its steps bake in the new optimizer/loss, so they
        # must never alias the old generation's cache entries.
        key = self._job.program_key(self.model.name, self._serial,
                                    kind, *variant)
        return self._job.runtime.cached(key, builder)

    def _train_variant(self) -> tuple:
        cw = self._class_weight
        return (self._built_policy,
                self._transform_key(self._device_transform),
                None if cw is None else tuple(sorted(cw.items())),
                self._bucket_bytes)

    def _eval_variant(self) -> tuple:
        return (self._built_policy,
                self._transform_key(self._eval_transform))

    def _build_train_step(self):
        return jax.jit(
            self._pure_train_step(),
            out_shardings=self._out_shardings(),
            donate_argnums=(0, 1, 2, 3, 4),
        )

    def _build_multi_step(self):
        """``lax.scan`` over K train steps inside ONE compiled dispatch —
        the Keras ``steps_per_execution`` knob, and the cure for
        dispatch-bound tiny steps (SURVEY.md hard-part #5): host dispatch
        cost is paid once per K steps instead of per step.

        Batches and rng keys for the K steps arrive stacked on a leading
        axis (K is a trace-time constant from the stack shape); the scan
        carries (params, state, opt, metrics, loss_acc) and the mean of the
        K losses is returned as the execution's loss.
        """
        step = self._pure_train_step()

        def one(carry, xs):
            x, y, rng = xs
            loss, *new_carry, health = step(*carry, x, y, rng)
            return tuple(new_carry), (loss, health)

        def multi(params, state, opt_state, metric_states, loss_acc,
                  xs_stack, ys_stack, rngs):
            from tpu_dist.training.integrity import reduce_window_health

            carry, (losses, healths) = jax.lax.scan(
                one, (params, state, opt_state, metric_states, loss_acc),
                (xs_stack, ys_stack, rngs))
            params, state, opt_state, metric_states, loss_acc = carry
            return (losses.mean(), params, state, opt_state, metric_states,
                    loss_acc, reduce_window_health(healths))

        return jax.jit(
            multi,
            out_shardings=self._out_shardings(),
            donate_argnums=(0, 1, 2, 3, 4),
        )

    def make_train_function(self, steps_per_execution: Optional[int] = None):
        """The compiled train step — public surface for benchmarks and custom
        loops (the Keras-2 ``make_train_function`` analog, SURVEY.md D15).

        With ``steps_per_execution`` (default: the model's compiled value) of
        1, returns the jitted single step::

            fn(params, state, opt, metrics, loss_acc, x, y, rng)
              -> (loss, params, state, opt, metrics, loss_acc, health)

        ``health`` is the in-step integrity vector (``f32[3]``, see
        :func:`tpu_dist.training.integrity.health_summary`) — custom loops
        thread ``out[1:6]`` as the next call's state and may ignore it.
        With K > 1, returns the scanned multi-step, whose ``x``/``y``/``rng``
        carry a leading K axis (stack K batches; see ``jnp_stack_keys``) and
        whose loss is the K-mean. Both donate their variable arguments —
        callers must thread the returned state into the next call.

        Always the UNWEIGHTED loss: if a prior ``fit(class_weight=...)``
        baked weights into the cached step, the step is rebuilt without
        them (weighted training is a fit-loop feature; a benchmark or
        custom loop asking for "the train step" must not inherit it
        silently).
        """
        self.ensure_variables()
        self._maybe_invalidate_for_policy()
        self._sync_step_knobs()
        if self._class_weight is not None:
            self._class_weight = None
            self._train_step = None
            self._multi_step = None
        if self._device_transform is not None:
            # Same rule as class_weight: a prior fit's dataset-specific
            # input transform (e.g. the u8 wire-dtype scale) must not leak
            # into the public step — callers feed already-prepared batches.
            self._device_transform = None
            self._train_step = None
            self._multi_step = None
        k = (steps_per_execution if steps_per_execution is not None
             else max(1, int(getattr(self.model, "steps_per_execution", 1))))
        if k > 1:
            if self._multi_step is None:
                self._multi_step = self._acquire_program(
                    "multi_step", self._build_multi_step,
                    *self._train_variant())
            return self._multi_step
        if self._train_step is None:
            self._train_step = self._acquire_program(
                "train_step", self._build_train_step, *self._train_variant())
        return self._train_step

    def train_state(self) -> tuple:
        """A fresh ``(params, state, opt, metrics, loss_acc)`` tuple, in the
        positional order the ``make_train_function`` callable consumes."""
        self.ensure_variables()
        v = self.variables
        return (v["params"], v["state"], v["opt"], v["metrics"],
                self._init_loss_acc())

    def _build_eval_step(self):
        model, loss_obj = self.model, self.model.loss
        metrics = tuple(model.metrics)
        device_transform = self._eval_transform

        def step(params, state, metric_states, loss_acc, x, y):
            if device_transform is not None:
                x = device_transform(x)
            logits, _ = model.apply(params, state, x, training=False)
            loss = loss_obj(logits, y)
            new_metrics = tuple(
                m.update(ms, logits, y) for m, ms in zip(metrics, metric_states))
            new_loss_acc = (loss_acc[0] + loss, loss_acc[1] + 1.0)
            return new_metrics, new_loss_acc

        return jax.jit(self._scoped(step), donate_argnums=(2, 3))

    # -- data plumbing (D14/D15 auto-wrap) ------------------------------------

    def _distribute(self, x):
        from tpu_dist.data.device import DeviceDataset

        if isinstance(x, DeviceDataset):
            # Pin the dataset to the training mesh (it may have been built
            # outside strategy.scope()).
            return x.bind_strategy(self.strategy)
        if isinstance(x, DistributedDataset):
            return x
        if isinstance(x, Dataset):
            # Device-residency promotion first (data/vectorize.py): an
            # HBM-sized reference-shaped chain uploads once and streams only
            # index vectors — the TPU-idiomatic delivery. Falls through to
            # the Keras-trainer auto-wrap (keras:src/backend/tensorflow/
            # trainer.py:750-755), which honors the auto-shard options.
            from tpu_dist.data import vectorize

            promoted = vectorize.try_promote_to_device(x)
            if promoted is not None:
                return promoted.bind_strategy(self.strategy)
            # allow_device_transform: the trainer applies dataset device
            # transforms inside its compiled steps (_sync_device_transform),
            # so the u8-wire split is safe here — unlike user-iterated wraps.
            return DistributedDataset(x, self.strategy,
                                      allow_device_transform=True)
        if isinstance(x, (tuple, list)) and len(x) == 2:
            ds = Dataset.from_tensor_slices(tuple(np.asarray(a) for a in x))
            return DistributedDataset(ds.batch(32), self.strategy)
        raise TypeError(
            f"fit/evaluate expects a Dataset, DistributedDataset, "
            f"DeviceDataset or (x, y) arrays; got {type(x).__name__}")

    @staticmethod
    def _cardinality_of(dist) -> Optional[int]:
        from tpu_dist.data.device import DeviceDataset

        if isinstance(dist, DeviceDataset):
            return dist.cardinality()
        return dist._local.cardinality()

    def _next_batch(self, dist: DistributedDataset, *, host: bool = False):
        """Persistent-iterator semantics across epochs (Keras 2): re-create on
        exhaustion — a fresh pass implies a fresh (re)shuffle. ``host=True``
        yields the pre-placement numpy batch (multi-step stacking path).
        With ``prefetch_to_device > 0`` compiled on the model, the device
        path routes through a :class:`~tpu_dist.data.pipeline.
        DevicePrefetcher` — batch k+1's device placement runs on a
        background thread while step k executes."""
        if not host and int(getattr(self.model, "prefetch_to_device", 0)
                            or 0) > 0:
            return self._next_prefetched(
                dist, int(self.model.prefetch_to_device))
        kind = "host" if host else "device"
        if (self._iterator is None or self._iterator_source is not dist
                or self._iterator_kind != kind):
            self._close_prefetcher()
            self._iterator = dist.iter_local() if host else iter(dist)
            self._iterator_source = dist
            self._iterator_kind = kind
        try:
            return next(self._iterator)
        except StopIteration:
            self._iterator = dist.iter_local() if host else iter(dist)
            batch = next(self._iterator, None)
            if batch is None:
                raise RuntimeError("dataset yielded no batches")
            return batch

    def _next_prefetched(self, dist: DistributedDataset, depth: int):
        """Double-buffered device fetch: same persistent-iterator semantics
        as :meth:`_next_batch`'s device path, with the iteration (and its
        ``device_put``) pushed onto the prefetcher's producer thread."""
        from tpu_dist.data.pipeline import DevicePrefetcher

        if (self._prefetcher is None or self._iterator_source is not dist
                or self._iterator_kind != "prefetch"):
            self._close_prefetcher()
            self._iterator = None
            self._prefetcher = DevicePrefetcher(iter(dist), depth=depth)
            self._iterator_source = dist
            self._iterator_kind = "prefetch"
        try:
            return next(self._prefetcher)
        except StopIteration:
            self._close_prefetcher()
            self._prefetcher = DevicePrefetcher(iter(dist), depth=depth)
            self._iterator_source = dist
            self._iterator_kind = "prefetch"
            try:
                return next(self._prefetcher)
            except StopIteration:
                self._close_prefetcher()
                raise RuntimeError("dataset yielded no batches") from None

    def _close_prefetcher(self) -> None:
        """Tear down the device prefetcher (epoch-loop exit, StopTraining,
        preemption drain, rollback): stops the producer, drains in-flight
        batches, joins the thread."""
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
            if self._iterator_kind == "prefetch":
                self._iterator_source = None

    # -- fit / evaluate / predict ---------------------------------------------

    def fit(self, x, *, epochs: int, steps_per_epoch: Optional[int],
            verbose: int, callbacks: Sequence, initial_epoch: int,
            seed: int, profile_dir: Optional[str] = None,
            validation_data=None, validation_steps: Optional[int] = None,
            checkpoint_dir: Optional[str] = None,
            class_weight: Optional[dict] = None) -> History:
        self.ensure_variables(seed)
        self._maybe_invalidate_for_policy()
        self._sync_step_knobs()
        from tpu_dist.parallel.ps_strategy import ParameterServerStrategy

        if isinstance(self.strategy, ParameterServerStrategy):
            # The async second execution model: no gang-synchronous step, no
            # collective in the hot loop — pull → local step → push against
            # the PS transport instead of the epoch machinery below.
            if not self.strategy.is_worker:
                raise ValueError(
                    "fit() under ParameterServerStrategy runs on worker "
                    "ranks; the server rank runs PSServer.run() "
                    "(tpu_dist.parallel.ps_strategy)")
            if class_weight:
                raise ValueError(
                    "class_weight is not supported under "
                    "ParameterServerStrategy")
            return self._fit_ps(x, epochs=epochs,
                                steps_per_epoch=steps_per_epoch,
                                verbose=verbose, callbacks=callbacks,
                                initial_epoch=initial_epoch, seed=seed)
        if class_weight is not None:
            class_weight = {int(c): float(w) for c, w in class_weight.items()}
            if any(c < 0 for c in class_weight):
                raise ValueError(f"negative class index in {class_weight}")
            if not class_weight:  # {} means no weighting, like None
                class_weight = None
        if class_weight != self._class_weight:
            # The weight table is baked into the compiled step; a different
            # weighting needs a rebuild (weights carry over untouched).
            self._class_weight = class_weight
            self._train_step = None
            self._multi_step = None
        # Distribute BEFORE building steps: the dataset may carry a device
        # transform that is a trace-time constant of the compiled step.
        dist = self._distribute(x)
        self._sync_device_transform(dist, role="train")
        if self._train_step is None:
            self._train_step = self._acquire_program(
                "train_step", self._build_train_step, *self._train_variant())
        if (getattr(self.model, "steps_per_execution", 1) > 1
                and self._multi_step is None):
            self._multi_step = self._acquire_program(
                "multi_step", self._build_multi_step, *self._train_variant())
        if steps_per_epoch is None:
            steps_per_epoch = self._cardinality_of(dist)
            if steps_per_epoch is None:
                raise ValueError(
                    "steps_per_epoch is required for datasets of unknown "
                    "cardinality (e.g. repeated/generator datasets)")

        callbacks = list(callbacks)
        # Code-edit-free chaos wiring (tpu_dist.resilience): a fault plan in
        # $TPU_DIST_FAULT_PLAN — set by the resilience CLI / Supervisor —
        # rides this fit as one more callback. None in production runs.
        from tpu_dist.resilience.injector import (maybe_injector_from_env,
                                                  maybe_preemption_drain,
                                                  maybe_rejoin_gate)

        fault_injector = maybe_injector_from_env(
            steps_per_epoch=steps_per_epoch)
        if fault_injector is not None:
            callbacks.append(fault_injector)
        # Graceful-preemption drain: armed only when the SIGTERM seam is
        # installed (run_entry workers), so a notebook fit pays nothing.
        # Appended AFTER the injector so an injected `preempt` fault is
        # observed by the drain in the same step-boundary callback round.
        drain = maybe_preemption_drain()
        if drain is not None:
            callbacks.append(drain)
        # Elastic epoch-boundary rejoin: $TPU_DIST_REJOIN_DIR (set by the
        # operator / chaos CLI) holds every worker at each epoch start
        # until the whole gang — including a relaunched member — arrives.
        rejoin = maybe_rejoin_gate()
        if rejoin is not None:
            callbacks.append(rejoin)
        # Mid-epoch gang reform: $TPU_DIST_GANG_DIR (set by the Supervisor
        # in step-rejoin mode) arms the step-boundary reform gate — on a
        # detected peer loss survivors drain here, reform the collective
        # clique under a fresh generation, and meet the relaunched rank at
        # a step-granular rendezvous instead of paying a gang restart.
        from tpu_dist.resilience import rejoin as rejoin_lib

        gang_gate = rejoin_lib.maybe_step_rejoin_gate(
            steps_per_epoch=steps_per_epoch)
        if gang_gate is not None:
            callbacks.append(gang_gate)
        # Same env-armed pattern for telemetry (tpu_dist.observe): an
        # observe dir in $TPU_DIST_OBSERVE_DIR — set by the Supervisor for
        # chaos workers, or by a shell — attaches the Telemetry callback.
        # Skipped when the caller already passed one (theirs wins).
        from tpu_dist.observe.telemetry import (Telemetry,
                                                maybe_telemetry_from_env)

        if not any(isinstance(cb, Telemetry) for cb in callbacks):
            telemetry = maybe_telemetry_from_env()
            if telemetry is not None:
                callbacks.append(telemetry)
        if checkpoint_dir is not None:
            # SURVEY.md §5.4: fit(checkpoint_dir=) = chief-writes-per-epoch +
            # resume-from-latest. A restored step N means epoch N finished.
            from tpu_dist.training import checkpoint as ckpt_lib
            from tpu_dist.training.callbacks import ModelCheckpoint

            import os as _os

            # A worker relaunched into a reformed gang restores the
            # CONSENSUS step the supervisor stamped ("none" = scratch),
            # not its own directory's latest — its dead predecessor's dir
            # may be ahead of or behind the survivors'.
            forced = _os.environ.get("TPU_DIST_RESTORE_STEP")
            try:
                if forced is None or forced == "":
                    restored = ckpt_lib.restore_model(
                        checkpoint_dir, self.model, trainer=self)
                elif forced == "none":
                    restored = None
                else:
                    restored = ckpt_lib.restore_model(
                        checkpoint_dir, self.model, step=int(forced),
                        trainer=self)
                if restored is not None:
                    initial_epoch = max(initial_epoch, restored + 1)
                    logger.info("resumed from checkpoint step %d; starting "
                                "at epoch %d", restored, initial_epoch)
                    from tpu_dist.resilience import events

                    events.maybe_log("checkpoint_resume", step=restored,
                                     initial_epoch=initial_epoch)
            except FileNotFoundError:
                pass
            # Don't double up save+barrier work if the caller already passed
            # a ModelCheckpoint for this same directory (str/Path agnostic).
            import os as _os

            def _same_dir(cb):
                d = getattr(cb, "directory", None)
                return (d is not None
                        and _os.fspath(d) == _os.fspath(checkpoint_dir))

            if not any(isinstance(cb, ModelCheckpoint) and _same_dir(cb)
                       for cb in callbacks):
                callbacks.append(ModelCheckpoint(checkpoint_dir))

        val_dist = val_steps = None
        if validation_data is not None:
            val_dist = self._distribute(validation_data)
            val_steps = validation_steps
            if val_steps is None:
                val_steps = self._cardinality_of(val_dist)
                if val_steps is None:
                    raise ValueError(
                        "validation_steps is required for validation datasets "
                        "of unknown cardinality")

        # Env-armed training-integrity guard (tpu_dist.training.integrity):
        # in-step anomaly detection + periodic cross-replica SDC audit +
        # rollback-and-replay, riding the hot loop directly (NOT a callback
        # — a batch-hook callback would force per-step blocking loss reads).
        from tpu_dist.training import integrity as integrity_lib

        guard = integrity_lib.maybe_guard_from_env()
        if guard is not None:
            guard.bind(self.strategy, checkpoint_dir=checkpoint_dir)

        history = History()
        cbs = CallbackList([history, *callbacks], model=self.model)
        chief = bootstrap.is_chief()
        show = verbose and chief
        root_key = jax.random.PRNGKey(seed ^ 0x5EED)

        cbs.on_train_begin()
        # Chief-only TensorBoard-compatible trace around the whole fit span
        # (SURVEY.md §5.1; README.md:51 chief duty).
        import contextlib

        ctx = (profiler.trace(profile_dir) if profile_dir
               else contextlib.nullcontext())
        try:
            with ctx:
                start_epoch = initial_epoch
                while True:
                    try:
                        self._run_epochs(dist, cbs, start_epoch, epochs,
                                         steps_per_epoch, show, root_key,
                                         val_dist=val_dist,
                                         val_steps=val_steps, guard=guard)
                        break
                    except integrity_lib.RollbackAndReplay as rb:
                        # Confirmed anomaly: restore the last published
                        # checkpoint and replay from that epoch boundary.
                        # Budget enforcement lives in the guard — it raises
                        # IntegrityAbort (escapes fit) when replay is not
                        # converging.
                        start_epoch = self._integrity_rollback(
                            rb, guard, checkpoint_dir, seed)
                    except rejoin_lib.GangReform as gr:
                        # A peer died mid-epoch: run the survivor side of
                        # the reform protocol (publish in-flight checkpoint,
                        # ack, re-init the clique at generation g+1, restore,
                        # meet the relaunched rank) and re-enter the loop —
                        # same rollback-and-replay RNG discipline, so losses
                        # stay exact.
                        start_epoch = self._gang_reform(
                            gr, gang_gate, cbs, checkpoint_dir, seed,
                            steps_per_epoch)
        except StopTraining as e:
            logger.info("training stopped early: %s", e)
        finally:
            # Tear down the device prefetcher FIRST — StopTraining and a
            # preemption drain land here with a producer thread possibly
            # mid-device_put, and callbacks (checkpoint publish) must see a
            # quiesced pipeline.
            self._close_prefetcher()
            # Runs even on the failure path (e.g. PeerUnavailableError) so
            # callbacks finalize — a JSONLogger's file matters most there.
            cbs.on_train_end()
        return history

    # -- parameter-server worker path ----------------------------------------

    def _build_ps_worker_step(self):
        """The PS worker's compiled local step: ``(params, state, x, y, rng)
        -> (loss, grads, state)`` — forward/backward ONLY. No optimizer
        update (the server owns optimizer state) and no collective (the
        strategy's mesh is one local device), which is the property
        shardcheck pins for the ``ps_worker_step`` entry point."""
        model, loss_obj = self.model, self.model.loss
        device_transform = self._device_transform

        def step(params, state, x, y, rng):
            if device_transform is not None:
                x = device_transform(x)

            def loss_fn(p):
                logits, new_state = model.apply(p, state, x, training=True,
                                                rng=rng)
                return loss_obj(logits, y) + _aux_loss_total(new_state), \
                    new_state

            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss, grads, new_state

        return jax.jit(self._scoped(step))

    def _fit_ps(self, x, *, epochs: int, steps_per_epoch: Optional[int],
                verbose: int, callbacks: Sequence, initial_epoch: int,
                seed: int) -> History:
        """The worker side of async PS training: pull params (bounded
        staleness enforced there), run ONE local step, push grads, repeat
        until the server orders STOP or the local step budget runs out.

        Epochless by nature — "epoch" here is local-step bookkeeping
        (``local_step // steps_per_epoch``) so History/callbacks keep their
        shape. The RNG stream is step-derived per (rank, local step)
        (:func:`~tpu_dist.parallel.ps_strategy.worker_step_key`), NOT
        epoch-derived: reproducibility is per-packet given the server's
        apply-order log, not per-epoch. Checkpointing/validation stay
        server-side; ``fit(checkpoint_dir=)`` is ignored here by design.
        """
        from tpu_dist.parallel import collectives
        from tpu_dist.parallel.ps_strategy import worker_step_key
        from tpu_dist.resilience.injector import (maybe_injector_from_env,
                                                  maybe_preemption_drain)

        strategy = self.strategy
        dist = self._distribute(x)
        self._sync_device_transform(dist, role="train")
        if steps_per_epoch is None:
            steps_per_epoch = self._cardinality_of(dist)
            if steps_per_epoch is None:
                raise ValueError(
                    "steps_per_epoch is required for datasets of unknown "
                    "cardinality (e.g. repeated/generator datasets)")
        ps_step = self._acquire_program("ps_worker_step",
                                        self._build_ps_worker_step,
                                        self._transform_key(
                                            self._device_transform))

        callbacks = list(callbacks)
        fault_injector = maybe_injector_from_env(
            steps_per_epoch=steps_per_epoch)
        if fault_injector is not None:
            callbacks.append(fault_injector)
        drain = maybe_preemption_drain()
        if drain is not None:
            callbacks.append(drain)
        from tpu_dist.observe.telemetry import (Telemetry,
                                                maybe_telemetry_from_env)

        if not any(isinstance(cb, Telemetry) for cb in callbacks):
            telemetry = maybe_telemetry_from_env()
            if telemetry is not None:
                callbacks.append(telemetry)

        history = History()
        cbs = CallbackList([history, *callbacks], model=self.model)
        show = bool(verbose)
        root_key = jax.random.PRNGKey(seed ^ 0x5EED)  # shardcheck: disable=SC604 -- deliberately mirrors fit()'s root-key derivation so the PS sync control is stream-identical to the sync trainer
        params_template = self.variables["params"]
        state = self.variables["state"]
        rank = strategy.rank
        # A worker caps at the GLOBAL step budget, not its 1/world share:
        # under a straggler the fast workers must be free to cover the
        # applies the slow one doesn't produce — the server's STOP (at its
        # apply budget) is the real terminator.
        max_local = (epochs - initial_epoch) * steps_per_epoch \
            * max(1, strategy.num_workers)
        local_step = 0
        stopped = False
        logger.info("PS worker %d: staleness=%d, steps_per_epoch=%d, "
                    "local cap=%d", rank, strategy.staleness,
                    steps_per_epoch, max_local)
        cbs.on_train_begin()
        try:
            for epoch in range(initial_epoch, epochs * max(
                    1, strategy.num_workers)):
                cbs.on_epoch_begin(epoch)
                if show:
                    print(f"Epoch {epoch + 1}/{epochs} (PS worker {rank})")
                bar = ProgressBar(steps_per_epoch, enabled=show)
                loss_sum = 0.0
                steps_this_epoch = 0
                t_epoch = time.perf_counter()
                for si in range(steps_per_epoch):
                    pulled = strategy.pull(params_template)
                    if pulled is None:  # server ordered STOP
                        stopped = True
                        break
                    params, _version = pulled
                    xb, yb = self._next_batch(dist)
                    rng = worker_step_key(root_key, rank=rank,
                                          local_step=local_step)
                    loss, grads, state = ps_step(params, state, xb, yb, rng)
                    # The straggler seam: a `delay@step*:rankN:always` plan
                    # sleeps HERE, between compute and push — exactly where
                    # a slow worker loses time. Same hook the sync stack's
                    # collectives fire, so one fault grammar serves both
                    # execution models.
                    collectives.fire_fault_hook("ps_step")
                    loss_val = float(loss)
                    strategy.push(grads, loss=loss_val)
                    strategy.heartbeat(step=local_step)
                    local_step += 1
                    steps_this_epoch += 1
                    loss_sum += loss_val
                    bar.update(si + 1, loss=loss_sum / steps_this_epoch)
                    cbs.on_batch_end(si, {"loss": loss_val})
                    if local_step >= max_local:
                        stopped = True
                        break
                if steps_this_epoch:
                    logs = {"loss": loss_sum / steps_this_epoch,
                            "epoch_time": time.perf_counter() - t_epoch}
                    bar.finish(logs)
                    cbs.on_epoch_end(epoch, logs)
                if stopped:
                    break
        except StopTraining as e:
            logger.info("PS worker %d stopped early: %s", rank, e)
        finally:
            self._close_prefetcher()
            strategy.mark_done(steps=local_step)
            cbs.on_train_end()
        logger.info("PS worker %d done: %d local steps, %d pushes",
                    rank, local_step, strategy.pushed)
        return history

    def _integrity_rollback(self, rb, guard, checkpoint_dir, seed) -> int:
        """Rollback-and-replay: restore the newest published checkpoint
        (strictly older than the last restore when replay re-hit the same
        anomaly), reset the data iterator to the epoch boundary, and return
        the epoch to re-enter the loop at. With no published checkpoint the
        run re-initializes from the seed and replays from epoch 0 — exact
        for the epoch-keyed RNG + per-epoch-pass datasets of the demo
        paths."""
        from tpu_dist.resilience import events
        from tpu_dist.training import checkpoint as ckpt_lib

        restored = None
        if checkpoint_dir is not None:
            step = ckpt_lib.latest_complete_step(
                checkpoint_dir, before=guard.rollback_plan(rb))
            if step is not None:
                restored = ckpt_lib.restore_model(checkpoint_dir, self.model,
                                                  step=step, trainer=self)
        if restored is None:
            self.variables = None
            self.ensure_variables(seed)
            next_epoch = 0
        else:
            next_epoch = restored + 1
        # Fresh iterator: replay re-reads the epoch's batches from the top —
        # identical to what a gang-restarted attempt would see (persistent
        # iterators are recreated per pass when cardinality matches).
        self._iterator = None
        self._close_prefetcher()
        guard.note_rollback(rb, restored)
        events.maybe_log("integrity_rollback", kind=rb.kind, step=rb.gstep,
                         restored_step=restored, next_epoch=next_epoch,
                         attempt=events.current_attempt())
        logger.warning(
            "integrity rollback: anomaly %r at global step %d; restored "
            "checkpoint step %s, replaying from epoch %d",
            rb.kind, rb.gstep, restored, next_epoch)
        return next_epoch

    def _gang_reform(self, gr, gate, cbs, checkpoint_dir, seed,
                     steps_per_epoch) -> int:
        """Survivor side of a mid-epoch gang reform.

        Phase order matters: (1) quiesce the input pipeline; (2) make the
        latest epoch checkpoint durable and ACK — the supervisor relaunches
        the lost rank only after every survivor has acked, so the rejoiner's
        restore is guaranteed to see the published state; (3) re-initialize
        the collective clique under the new generation; (4) restore the last
        complete checkpoint (every rank converges on the same step, hence
        the same rendezvous coordinate); (5) meet the reformed gang at the
        step-granular barrier. Each phase's wall time is recorded — the
        recovery breakdown the chaos report prints.
        """
        import time as _time

        from tpu_dist.cluster import bootstrap as bootstrap_lib
        from tpu_dist.observe import metrics as metrics_lib
        from tpu_dist.resilience import events
        from tpu_dist.training import checkpoint as ckpt_lib
        from tpu_dist.training.callbacks import ModelCheckpoint

        # -- drain: quiesce + publish in-flight checkpoints ----------------
        self._iterator = None
        self._close_prefetcher()
        for cb in cbs.callbacks:
            if isinstance(cb, ModelCheckpoint):
                cb.publish_in_flight()
        available = (ckpt_lib.latest_complete_step(checkpoint_dir)
                     if checkpoint_dir is not None else None)
        drain_s = _time.monotonic() - gr.seen_at
        bootstrap_lib.ack_reform(gate.directory, generation=gr.generation,
                                 rank=gate.rank, available_step=available)

        # -- reform: new clique under generation g+1 -----------------------
        t_reform = _time.monotonic()
        bootstrap_lib.reinitialize(generation=gr.generation)
        gate.generation = gr.generation

        # -- restore: converge every rank on the CONSENSUS step ------------
        # Per-rank checkpoint dirs can disagree by an epoch or two (ranks
        # are only loosely coupled between barriers; the dead rank's async
        # save may never have published). Restoring each rank's own latest
        # would put the gang at different epochs and deadlock the reformed
        # rendezvous — so the supervisor collects every ack's available
        # step, takes the gang-wide minimum, and publishes it for all.
        t_restore = _time.monotonic()
        deadline = _time.monotonic() + gate.timeout_s
        while True:
            published, step = bootstrap_lib.read_restore_step(
                gate.directory, generation=gr.generation)
            if published:
                break
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"gang reform: no consensus restore step for generation "
                    f"{gr.generation} within {gate.timeout_s:.1f}s")
            _time.sleep(0.05)
        restored = None
        if step is not None and checkpoint_dir is not None:
            restored = ckpt_lib.restore_model(checkpoint_dir, self.model,
                                              step=step, trainer=self)
        if restored is None:
            self.variables = None
            self.ensure_variables(seed)
            next_epoch = 0
        else:
            next_epoch = restored + 1
        restore_s = _time.monotonic() - t_restore

        # -- rendezvous: meet the relaunched rank mid-run ------------------
        gate.rendezvous(step=next_epoch * steps_per_epoch, epoch=next_epoch)
        reform_s = _time.monotonic() - t_reform

        metrics_lib.observe_value("elastic.drain_s", drain_s)
        metrics_lib.observe_value("elastic.reform_s", reform_s)
        metrics_lib.observe_value("elastic.restore_s", restore_s)
        events.maybe_log(
            "gang_reform", generation=gr.generation,
            lost_ranks=gr.lost_ranks, rank=gate.rank,
            detect_s=gr.request.get("detect_s"),
            drain_s=round(drain_s, 6), reform_s=round(reform_s, 6),
            restore_s=round(restore_s, 6), restored_step=restored,
            next_epoch=next_epoch, attempt=events.current_attempt())
        logger.warning(
            "gang reform: lost rank(s) %s; reformed at generation %d, "
            "restored checkpoint step %s, replaying from epoch %d "
            "(drain %.3fs reform %.3fs restore %.3fs)",
            gr.lost_ranks, gr.generation, restored, next_epoch,
            drain_s, reform_s, restore_s)
        return next_epoch

    def _run_epochs(self, dist, cbs, initial_epoch, epochs, steps_per_epoch,
                    show, root_key, val_dist=None, val_steps=None,
                    guard=None):
        from tpu_dist.data.device import DeviceDataset
        from tpu_dist.observe.telemetry import active_step_timer
        from tpu_dist.training.integrity import fire_batch_hook

        device_ds = isinstance(dist, DeviceDataset)
        monitor = getattr(self.strategy, "liveness_monitor", None)
        # Installed by a Telemetry callback's on_train_begin (which has
        # already run); None on uninstrumented fits — the hot loop then
        # pays one is-None check and three null spans per execution.
        timer = active_step_timer()
        for epoch in range(initial_epoch, epochs):
            if monitor is not None:
                # Surface a dead peer as a restartable error instead of letting
                # the next collective hang (SURVEY.md §5.3 failure semantics).
                monitor.raise_if_failed()
            cbs.on_epoch_begin(epoch)
            if show:
                print(f"Epoch {epoch + 1}/{epochs}")
            bar = ProgressBar(steps_per_epoch, enabled=bool(show))
            v = self.variables
            v["metrics"] = self._init_metric_states()  # reset per epoch
            loss_acc = self._init_loss_acc()
            # Per-step host sync (float(loss)) is only paid when something
            # consumes it — otherwise steps stay fully async on device and the
            # host runs ahead filling the dispatch pipeline (BASELINE.md
            # hard-part #5: tiny MNIST steps are dispatch-bound).
            eager_loss = bool(show) or cbs.has_batch_hooks
            bounded = self._bounded_dispatch()
            loss_running = 0.0
            t_epoch = time.perf_counter()
            k = max(1, int(getattr(self.model, "steps_per_execution", 1)))
            # All of this epoch's step keys in ONE device op, then pre-sliced
            # into per-execution chunks BEFORE the hot loop: eager device ops
            # interleaved with compiled executions stall the dispatch
            # pipeline, while a burst of consecutive slices up front does
            # not. Values are identical to fold_in(root_key,
            # epoch*100003 + step_i).
            epoch_keys = jnp_stack_keys(
                root_key, epoch * 100003, steps_per_epoch)
            key_chunks = []
            _i = 0
            while _i < steps_per_epoch:
                _kk = min(k, steps_per_epoch - _i)
                key_chunks.append(epoch_keys[_i] if _kk == 1
                                  else epoch_keys[_i:_i + _kk])
                _i += _kk
            step_i = 0
            executions = 0
            while step_i < steps_per_epoch:
                kk = min(k, steps_per_epoch - step_i)
                gstep0 = epoch * steps_per_epoch + step_i
                if guard is not None and guard.should_skip(gstep0, kk):
                    # Quarantined window (integrity guard, opt-in): pull the
                    # batches so the iterator stays aligned, but skip the
                    # dispatch — replaying a data-poisoned window would just
                    # re-trigger the same rollback.
                    if device_ds:
                        dist.next_batch() if kk == 1 else dist.next_stack(kk)
                    elif k > 1:
                        for _ in range(kk):
                            self._next_batch(dist, host=True)
                    else:
                        self._next_batch(dist)
                    from tpu_dist.resilience import events as _events

                    _events.maybe_log("integrity_quarantine_skip",
                                      step=gstep0, window=kk)
                    step_i += kk
                    executions += 1
                    continue
                # Two spans an execution (utils.profiler.span; null objects
                # unless something records): the fetch is the host input
                # pipeline, the dispatch the compiled call until it returns.
                with profiler.step_annotation(gstep0):
                    if kk == 1:
                        with profiler.span("train.exec.fetch",
                                           gstep0) as fetch:
                            if device_ds:
                                xb, yb = dist.next_batch()
                            elif k > 1:
                                # Tail step of a multi-step run: stay on the
                                # HOST iterator — switching kinds would
                                # recreate the iterator mid-epoch and replay
                                # batches.
                                hb = self._next_batch(dist, host=True)
                                xb, yb = self.strategy.distribute_batch(hb)
                            else:
                                xb, yb = self._next_batch(dist)
                            xb, yb = fire_batch_hook(gstep0, 1, xb, yb)
                            rng = key_chunks[executions]
                        with profiler.span("train.exec.dispatch",
                                           gstep0) as dispatch:
                            (loss, v["params"], v["state"], v["opt"],
                             v["metrics"], loss_acc,
                             health) = self._train_step(
                                v["params"], v["state"], v["opt"],
                                v["metrics"], loss_acc, xb, yb, rng)
                    elif device_ds:
                        # Device-resident path: batches gathered ON device
                        # (index transfer only), one scanned dispatch.
                        with profiler.span("train.exec.fetch",
                                           gstep0) as fetch:
                            xb, yb = dist.next_stack(kk)
                            xb, yb = fire_batch_hook(gstep0, kk, xb, yb)
                        with profiler.span("train.exec.dispatch",
                                           gstep0) as dispatch:
                            (loss, v["params"], v["state"], v["opt"],
                             v["metrics"], loss_acc,
                             health) = self._multi_step(
                                v["params"], v["state"], v["opt"],
                                v["metrics"], loss_acc, xb, yb,
                                key_chunks[executions])
                    else:
                        # steps_per_execution: stack kk host batches, ONE
                        # dispatch runs the scanned step (SURVEY.md
                        # hard-part #5). loss comes back as the kk-mean.
                        # Host-iterator pulls are the fetch; the stack and
                        # the placement are charged to the dispatch.
                        with profiler.span("train.exec.fetch",
                                           gstep0) as fetch:
                            batches = [self._next_batch(dist, host=True)
                                       for _ in range(kk)]
                        with profiler.span("train.exec.dispatch",
                                           gstep0) as dispatch:
                            if len({b[0].shape for b in batches}) == 1:
                                xs = np.stack([b[0] for b in batches])
                                ys = np.stack([b[1] for b in batches])
                                xb, yb = (
                                    self.strategy.distribute_batch_stack(
                                        (xs, ys)))
                                xb, yb = fire_batch_hook(gstep0, kk, xb, yb)
                                (loss, v["params"], v["state"], v["opt"],
                                 v["metrics"], loss_acc,
                                 health) = self._multi_step(
                                    v["params"], v["state"], v["opt"],
                                    v["metrics"], loss_acc, xb, yb,
                                    key_chunks[executions])
                            else:
                                # Ragged batch in the window
                                # (drop_remainder=False tail): un-stackable
                                # — run the collected batches per-step
                                # instead of crashing.
                                for j, hb in enumerate(batches):
                                    xb, yb = self.strategy.distribute_batch(
                                        hb)
                                    xb, yb = fire_batch_hook(gstep0 + j, 1,
                                                             xb, yb)
                                    (loss, v["params"], v["state"],
                                     v["opt"], v["metrics"], loss_acc,
                                     health) = self._train_step(
                                        v["params"], v["state"], v["opt"],
                                        v["metrics"], loss_acc, xb, yb,
                                        key_chunks[executions][j])
                step_i += kk
                executions += 1
                if guard is not None:
                    # One-behind health judgement + periodic SDC audit: the
                    # new vector's host copy starts now (non-blocking), the
                    # previous execution's — already in flight — is judged.
                    guard.on_execution(gstep0, kk, health, v["params"])
                waited_s = 0.0
                if bounded:
                    t_wait = time.perf_counter()
                    jax.block_until_ready(loss)
                    waited_s = time.perf_counter() - t_wait
                if timer is not None:
                    # Telemetry adds no wait of its own: the device time it
                    # books is what the host waited anyway (here, and at
                    # the epoch's end for the loss).
                    timer.record_execution(
                        steps=kk, data_wait_s=fetch.seconds,
                        dispatch_s=dispatch.seconds,
                        device_block_s=waited_s)
                if eager_loss:
                    loss_val = float(loss)
                    loss_running += loss_val
                    bar.update(step_i, loss=loss_running / executions)
                    # Keras steps_per_execution semantics: batch hooks fire
                    # once per execution, logs carry the execution's loss.
                    cbs.on_batch_end(step_i - 1, {"loss": loss_val})
            if guard is not None:
                # Judge the final in-flight health vector BEFORE epoch-end
                # callbacks run: a poisoned last step must trigger rollback
                # here, not after ModelCheckpoint has published the epoch.
                guard.flush()
            # ZERO host syncs on the epoch boundary: the loss mean and each
            # metric result are queued as device ops right behind the last
            # step's dispatch, a single batched non-blocking device→host
            # transfer is issued (LazyLogs), and the actual wait happens only
            # if/when a consumer reads a value — the progress bar when
            # verbose, a monitor callback, or History at `.history` access
            # after fit. An eager device_get here would be a full
            # round-trip per epoch; a verbose=0 fit with no log-reading
            # callbacks skips the fetch entirely. The scalars below are all
            # fresh (never-donated) outputs, so deferred reads stay valid.
            import jax.numpy as jnp

            device_logs = {"loss": loss_acc[0] / jnp.maximum(loss_acc[1], 1.0)}
            for metric, mstate in zip(self.model.metrics, v["metrics"]):
                device_logs[metric.name] = metric.result(mstate)
            logs = LazyLogs({"epoch_time": time.perf_counter() - t_epoch},
                            device_logs)
            if val_dist is not None:
                # Keras validation semantics: full validation pass at each
                # epoch end, reported as val_-prefixed logs (feeds
                # EarlyStopping/ModelCheckpoint monitors); absorbed without
                # forcing a fetch — the val scalars stay lazy too.
                val_logs = self._evaluate_on(val_dist, steps=val_steps)
                logs.absorb(val_logs, prefix="val_")
            bar.finish(logs)
            with profiler.span("train.epoch.end", epoch):
                cbs.on_epoch_end(epoch, logs)

    def evaluate(self, x, *, steps: Optional[int], verbose: int) -> dict:
        self.ensure_variables()
        self._maybe_invalidate_for_policy()
        logs = self._evaluate_on(self._distribute(x), steps=steps)
        if verbose and bootstrap.is_chief():
            print(" - ".join(f"{k}: {v_:.4f}" for k, v_ in logs.items()))
        return logs

    def _evaluate_on(self, dist: DistributedDataset,
                     steps: Optional[int]) -> dict:
        """One evaluation pass over ``dist``; shared by evaluate() and the
        per-epoch validation hook of fit()."""
        self._sync_device_transform(dist, role="eval")
        if self._eval_step is None:
            self._eval_step = self._acquire_program(
                "eval_step", self._build_eval_step, *self._eval_variant())
        v = self.variables
        metric_states = self._init_metric_states()
        loss_acc = self._init_loss_acc()
        count = 0
        # islice stops BEFORE pulling batch steps+1 — a plain for-loop with a
        # break-on-count would do one extra batch of host pipeline work per
        # bounded pass only to discard it.
        import itertools

        bounded_dispatch = self._bounded_dispatch()
        bounded = dist if steps is None else itertools.islice(iter(dist), steps)
        for xb, yb in bounded:
            metric_states, loss_acc = self._eval_step(
                v["params"], v["state"], metric_states, loss_acc, xb, yb)
            if bounded_dispatch:
                jax.block_until_ready(loss_acc)
            count += 1
        if count == 0:
            raise RuntimeError("evaluate: dataset yielded no batches")
        # Same zero-sync pattern as the epoch end: queue the scalar ops on
        # device, start one batched non-blocking transfer, and let the
        # caller's first read await it (LazyLogs is a dict, so evaluate()'s
        # public contract is unchanged).
        import jax.numpy as jnp

        device_logs = {"loss": loss_acc[0] / jnp.maximum(loss_acc[1], 1.0)}
        for metric, mstate in zip(self.model.metrics, metric_states):
            device_logs[metric.name] = metric.result(mstate)
        return LazyLogs(device_logs=device_logs)

    def predict(self, x):
        self.ensure_variables()
        self._maybe_invalidate_for_policy()
        model = self.model
        is_array = isinstance(x, np.ndarray) or hasattr(x, "__array__")
        t = None if is_array else getattr(
            x, "device_transform", getattr(x, "_device_transform", None))
        if self._transform_key(t) != self._transform_key(
                self._eval_transform):
            self._eval_transform = t
            self._eval_step = None
            self._predict_fn = None
        if self._predict_fn is None:
            dt = self._eval_transform

            def fwd(p, s, xb):
                if dt is not None:
                    xb = dt(xb)
                return model.apply(p, s, xb, training=False)[0]

            self._predict_fn = self._acquire_program(
                "predict", lambda: jax.jit(self._scoped(fwd)),
                *self._eval_variant())
        if is_array:
            batches = [np.asarray(x)]
        else:
            batches = [b[0] if isinstance(b, tuple) else b for b in x]
        v = self.variables
        n_dev = len(self.strategy.mesh.local_devices)
        # One compiled program for the whole pass: every batch pads up to
        # the largest batch size rounded to a device multiple (a ragged
        # final batch or mixed sizes would otherwise retrace per distinct
        # length — the no-retrace discipline tpu_dist.serve buckets by).
        sizes = [int(np.asarray(b).shape[0]) for b in batches]
        if not sizes:
            return np.concatenate([], axis=0)
        target = max(sizes)
        target += (-target) % n_dev
        outs = []
        for xb in batches:
            xb = np.asarray(xb)
            n = xb.shape[0]
            pad = target - n
            if pad:
                xb = np.concatenate([xb, np.repeat(xb[-1:], pad, axis=0)])
            placed = self.strategy.distribute_batch(xb)
            out = np.asarray(self._predict_fn(v["params"], v["state"], placed))
            outs.append(out[:n])
        return np.concatenate(outs, axis=0)
