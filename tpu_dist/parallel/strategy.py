"""Distribution-strategy front-ends: the reference's user-facing API, TPU-native.

Reproduces the strategy surface the reference exercises (SURVEY.md §2.1 R2,
§2.3):

* :class:`MirroredStrategy` — synchronous data parallelism across the devices
  of one host (README.md:15-19; tf_dist_example.py:13).
* :class:`MultiWorkerMirroredStrategy` — the same, across every process in the
  cluster (README.md:21-29; tf_dist_example.py:12), with the reference's
  degradation rule: no cluster / one worker behaves like MirroredStrategy
  (README.md:34).
* :class:`ParameterServerStrategy` — async bounded-staleness PS training,
  the one model the reference names but never runs (README.md:5-7, 13;
  SURVEY.md D19). Long a raising stub here; now a real second execution model
  in :mod:`tpu_dist.parallel.ps_strategy` (re-exported from this module):
  server ranks own params + optimizer state, workers pull/push asynchronously
  over host-side file transport with no collective in the hot loop.

Architecture shift (the heart of the TPU-native design): a TF strategy is an
*object* that intercepts variable creation, owns cross-device ops and launches
collectives at runtime. Here a strategy is a thin factory for a named
``jax.sharding.Mesh`` plus sharding policy — "mirrored variables" are arrays
with replicated sharding, and the gradient all-reduce is compiled into the
train step by XLA's SPMD partitioner (SURVEY.md §5.8). ``scope()`` survives as
ergonomics: it pins the active strategy so ``compile``/``fit`` pick it up,
letting the reference script port line-for-line (tf_dist_example.py:56-59).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional, Sequence

from tpu_dist.cluster import bootstrap
from tpu_dist.parallel import mesh as mesh_lib
from tpu_dist.parallel.collectives import CollectiveCommunication, ReduceOp
from tpu_dist.utils import profiler

logger = logging.getLogger("tpu_dist.strategy")

_LOCAL = threading.local()


def _strategy_stack() -> list:
    if not hasattr(_LOCAL, "stack"):
        _LOCAL.stack = []
    return _LOCAL.stack


class InputContext:
    """Per-process input-pipeline context handed to ``dataset_fn`` by
    :meth:`Strategy.distribute_datasets_from_function` — the analog of
    ``tf.distribute.InputContext`` (SURVEY.md D14): which input pipeline this
    process is (``input_pipeline_id`` of ``num_input_pipelines``) and how to
    derive a per-replica batch from a global one."""

    def __init__(self, num_input_pipelines: int, input_pipeline_id: int,
                 num_replicas_in_sync: int):
        self.num_input_pipelines = num_input_pipelines
        self.input_pipeline_id = input_pipeline_id
        self.num_replicas_in_sync = num_replicas_in_sync

    def get_per_replica_batch_size(self, global_batch_size: int) -> int:
        if global_batch_size % self.num_replicas_in_sync:
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"{self.num_replicas_in_sync} replicas")
        return global_batch_size // self.num_replicas_in_sync

    def __repr__(self) -> str:
        return (f"InputContext(pipeline {self.input_pipeline_id}/"
                f"{self.num_input_pipelines}, "
                f"replicas={self.num_replicas_in_sync})")


class _Scope:
    def __init__(self, strategy: "Strategy"):
        self._strategy = strategy

    def __enter__(self):
        _strategy_stack().append(self._strategy)
        return self._strategy

    def __exit__(self, *exc):
        popped = _strategy_stack().pop()
        assert popped is self._strategy, "unbalanced strategy scopes"
        return False


class Strategy:
    """Base: a named device mesh + pure-data-parallel sharding policy.

    ``axis_shapes`` opens extra mesh axes next to ``data`` (e.g.
    ``{"data": 2, "seq": 4}`` for combined data x sequence parallelism —
    batches shard over ``data`` exactly as before, and the extra axes are
    available to ``ring_attention``/``shard_map`` inside the model)."""

    def __init__(self, devices: Sequence | None = None, *,
                 local: bool = False,
                 axis_shapes: Optional[dict] = None):
        if axis_shapes is not None and mesh_lib.DATA_AXIS not in axis_shapes:
            raise ValueError(
                f"axis_shapes must include the {mesh_lib.DATA_AXIS!r} axis "
                f"(batches shard over it), got {axis_shapes}")
        self._mesh = mesh_lib.make_mesh(axis_shapes, devices=devices,
                                        local=local)

    # -- core surface --------------------------------------------------------

    @property
    def mesh(self):
        return self._mesh

    @property
    def data_axis(self) -> str:
        return mesh_lib.DATA_AXIS

    @property
    def num_replicas_in_sync(self) -> int:
        """Data-parallel replica count — TF's ``strategy.num_replicas_in_sync``
        (verified == 2 in the reference's 2-worker run, SURVEY.md §3.5).
        With extra mesh axes (axis_shapes) this is the ``data`` axis size,
        not the device count: a data(2) x seq(4) mesh runs 2 replicas."""
        return self._mesh.shape.get(mesh_lib.DATA_AXIS,
                                    self._mesh.devices.size)

    def scope(self) -> _Scope:
        """Context manager pinning this strategy as current
        (tf_dist_example.py:56-57 ergonomics)."""
        return _Scope(self)

    # -- sharding policy -----------------------------------------------------

    def param_sharding(self):
        """Replicated — MirroredVariable semantics (SURVEY.md D4)."""
        return mesh_lib.replicated(self._mesh)

    @property
    def model_parallel(self) -> bool:
        """True when the mesh carries a ``'model'`` axis of size > 1 —
        variables then shard Megatron-style instead of mirroring
        (parallel/tensor.py)."""
        from tpu_dist.parallel import tensor

        return self._mesh.shape.get(tensor.MODEL_AXIS, 1) > 1

    @property
    def pipeline_parallel(self) -> bool:
        """True when the mesh carries a ``'pipe'`` axis of size > 1 —
        PipelinedBlocks stage stacks then shard one-stage-per-device
        (parallel/pipeline_parallel.py)."""
        from tpu_dist.parallel.pipeline_parallel import PIPE_AXIS

        return self._mesh.shape.get(PIPE_AXIS, 1) > 1

    @property
    def expert_parallel(self) -> bool:
        """True when the mesh carries an ``'expert'`` axis of size > 1 —
        MixtureOfExperts stacks then shard experts-per-device
        (parallel/expert.py)."""
        from tpu_dist.parallel.expert import EXPERT_AXIS

        return self._mesh.shape.get(EXPERT_AXIS, 1) > 1

    def param_spec_tree(self, params):
        """PartitionSpec tree for a params tree: tensor-parallel /
        pipeline rules when the mesh has a ``'model'`` / ``'pipe'`` axis,
        else replicated everywhere (prune_indivisible later drops any
        spec naming an axis this mesh lacks)."""
        from jax.sharding import PartitionSpec
        from tpu_dist.parallel import tensor

        if (self.model_parallel or self.pipeline_parallel
                or self.expert_parallel):
            return tensor.tensor_parallel_specs(params)
        import jax

        return jax.tree_util.tree_map(lambda _: PartitionSpec(), params)

    def variable_shardings(self, params, tree):
        """NamedSharding tree for ANY variables tree (params themselves,
        optimizer moments, ...) — leaves inherit the matching param's spec
        by path suffix; unmatched leaves replicate (parallel/tensor.py)."""
        from tpu_dist.parallel import tensor

        specs = tensor.specs_like_params(tree, self.param_spec_tree(params))
        specs = tensor.prune_indivisible(specs, tree, self._mesh)
        return tensor.shardings_from_specs(specs, self._mesh)

    @profiler.spanned("strategy.place_variables")
    def place_variables(self, params, tree, *, broadcast: bool | None = None):
        """Place a variables tree with per-leaf shardings derived from the
        params rules; the TP-aware generalization of :meth:`replicate`.
        Every leaf makes a round trip through the host (a span of its
        own: it is most of a large model's set-up)."""
        import jax

        if broadcast is None:
            broadcast = jax.process_count() > 1
        return mesh_lib.place_with_shardings(
            tree, self.variable_shardings(params, tree), broadcast=broadcast)

    def batch_sharding(self):
        """Leading dim split across the data axis (SURVEY.md D14)."""
        return mesh_lib.batch_sharded(self._mesh, self.data_axis)

    def input_shard_info(self) -> tuple[int, int]:
        """``(num_input_shards, shard_id)`` for the host input pipeline.

        Input must shard over the mesh's DATA-axis process structure, not
        the raw process count: on a ``{data: 1, pipe: 2}`` (or model-only)
        multi-process mesh, every process sits at the same data coordinate
        and must feed the IDENTICAL replicated batch — striding the stream
        by process_index there hands each process different samples for
        the same global array (silent divergence, r4). Processes sharing a
        data-coordinate set share a shard id; a process spanning the whole
        axis (single-process meshes) is the one-and-only pipeline."""
        import numpy as _np

        mesh = self._mesh
        axis = list(mesh.axis_names).index(self.data_axis)
        proc_coords: dict[int, set] = {}
        for idx in _np.ndindex(mesh.devices.shape):
            d = mesh.devices[idx]
            proc_coords.setdefault(d.process_index, set()).add(idx[axis])
        distinct = sorted({tuple(sorted(s)) for s in proc_coords.values()})
        import jax

        mine = tuple(sorted(proc_coords.get(jax.process_index(), {0})))
        return len(distinct), distinct.index(mine)

    def replicate(self, tree, *, broadcast: bool | None = None):
        """Place params replicated on the mesh; in multi-process jobs,
        broadcast process 0's values first (D4 init broadcast)."""
        import jax

        if broadcast is None:
            broadcast = jax.process_count() > 1
        return mesh_lib.replicate(tree, self._mesh, broadcast=broadcast)

    def distribute_batch(self, batch):
        """Host batch pytree -> global device array, batch-dim sharded."""
        return mesh_lib.shard_batch(batch, self._mesh, self.data_axis)

    def distribute_batch_stack(self, stack):
        """K-stacked host batches -> device array (K replicated, batch dim
        sharded) for multi-step executions (steps_per_execution)."""
        return mesh_lib.shard_batch_stack(stack, self._mesh, self.data_axis)

    def experimental_distribute_dataset(self, dataset, policy=None):
        """Wrap a ``tpu_dist.data.Dataset`` for per-replica delivery — the
        analog of the commented alternative at tf_dist_example.py:36. The
        dataset should be batched to the global batch size; each process keeps
        its shard per the dataset's auto-shard policy (SURVEY.md D14)."""
        from tpu_dist.data.distribute import DistributedDataset

        return DistributedDataset(dataset, self, policy=policy)

    def distribute_datasets_from_function(self, dataset_fn, options=None):
        """Per-worker dataset construction — the analog of TF's
        ``strategy.distribute_datasets_from_function`` (SURVEY.md D14):
        ``dataset_fn(InputContext)`` builds THIS process's stream, batched to
        the PER-REPLICA size (TF's contract — use
        ``ctx.get_per_replica_batch_size(global)``). Per training step, one
        element is drawn for each of this process's replicas and the
        elements are stacked into the process's contribution to the global
        sharded batch, so the effective global batch is
        ``per_replica_batch x num_replicas_in_sync`` — identical consumption
        to TF's wrapper. Because the fn already did any cross-worker
        sharding (it knows its ``input_pipeline_id``), no autoshard rewrite
        is applied."""
        import jax

        from tpu_dist.data.distribute import DistributedDataset
        from tpu_dist.data.pipeline import AutoShardPolicy, Dataset

        # Pipelines follow the data-axis process structure (see
        # input_shard_info): same-data-coordinate processes share an id so
        # they build identical streams — dividing by raw process_count
        # would reject or mis-size exactly the pipe/model-spanning meshes
        # (r4): on {data:1, pipe:2} there is ONE pipeline feeding one
        # replica, however many processes carry it.
        num_pipelines, pipeline_id = self.input_shard_info()
        if self.num_replicas_in_sync % num_pipelines:
            # ADVICE r2: flooring the division would mis-size the global
            # batch (some replicas starve) with no error — reject instead,
            # BEFORE user code runs against the doomed InputContext.
            raise ValueError(
                f"num_replicas_in_sync ({self.num_replicas_in_sync}) must "
                f"be divisible by the input-pipeline count "
                f"({num_pipelines}); uneven replicas-per-pipeline is not "
                "supported")
        ctx = InputContext(
            num_input_pipelines=num_pipelines,
            input_pipeline_id=pipeline_id,
            num_replicas_in_sync=self.num_replicas_in_sync)
        dataset = dataset_fn(ctx)
        local_replicas = self.num_replicas_in_sync // num_pipelines

        if local_replicas > 1:
            # ADVICE r4: when several processes share an input_pipeline_id
            # (pipe/model-spanning meshes) the fn they each ran must have
            # built identical streams; reject a detected unseeded shuffle,
            # warn otherwise. Checked HERE only when the rebatch wrapper
            # below is about to hide the combinator chain — otherwise the
            # DistributedDataset OFF branch walks the same chain itself.
            from tpu_dist.data.distribute import require_replicated_determinism

            require_replicated_determinism(
                dataset, num_pipelines, jax.process_count(),
                "distribute_datasets_from_function")
            from tpu_dist.data.pipeline import _concat_structure

            inner = dataset  # capture BEFORE rebinding the name below

            def rebatch_factory():
                it = iter(inner)
                while True:
                    group = []
                    try:
                        for _ in range(local_replicas):
                            group.append(next(it))
                    except StopIteration:
                        return
                    yield _concat_structure(group)

            card = dataset.cardinality()
            dataset = Dataset(
                rebatch_factory,
                cardinality=(card // local_replicas if card and card > 0
                             else card))
        return DistributedDataset(dataset, self, policy=AutoShardPolicy.OFF)

    # TF shipped the same API under an experimental_ prefix first; accept both.
    experimental_distribute_datasets_from_function = \
        distribute_datasets_from_function

    def run(self, fn, args=(), kwargs=None):
        """Run ``fn`` once per replica — TF's ``strategy.run``, the custom-
        training-loop surface (the reference's fit path calls it inside Keras,
        keras:src/backend/tensorflow/trainer.py:134; SURVEY.md D15/L4).

        TPU-native semantics: the call IS one compiled program — a cached
        ``jax.jit`` around a ``shard_map`` over the mesh (do NOT wrap it in
        another ``jax.jit``; under an outer trace the arguments' shardings
        are invisible, so ``run`` raises instead of silently mis-sharding).
        Arguments that are global arrays sharded over the data axis
        (``distribute_batch`` / distributed-dataset output) arrive in ``fn``
        as this replica's local shard; everything else is replicated. Inside
        ``fn``, cross-replica collectives are available as
        ``jax.lax.psum/pmean(..., strategy.data_axis)``. Returns per-replica
        outputs stacked on a leading replica axis — feed to
        :meth:`reduce` (``strategy.reduce("mean", result)``), which is
        exactly TF's run-then-reduce idiom.

        The compiled program is cached per ``(fn, argument structure,
        sharding layout)``; repeated calls in a training loop hit the cache,
        so write loops exactly like TF's ``strategy.run(step, (batch,))``.

        Gradient semantics (SPMD, differs from TF's per-replica tapes in a
        convenient way): differentiating w.r.t. a REPLICATED argument (model
        params) implicitly psums the cotangents across replicas — scale the
        per-replica loss by ``1/num_replicas_in_sync`` (TF's own custom-loop
        guidance) and the returned gradient is already the fully all-reduced
        global gradient on every replica, no explicit collective needed.
        """
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        kwargs = kwargs or {}
        flat, treedef = jax.tree.flatten((args, kwargs))
        if any(isinstance(x, jax.core.Tracer) for x in flat):
            raise ValueError(
                "strategy.run was called under a jax transformation (jit/"
                "grad/vmap trace). run() already compiles its own SPMD "
                "program and must see concrete arrays to read their "
                "shardings — call it outside jit, or use shard_map "
                "directly for custom composition.")

        def spec_for(x):
            sh = getattr(x, "sharding", None)
            if (isinstance(sh, NamedSharding) and sh.mesh == self._mesh
                    and any(ax == self.data_axis
                            for ax in jax.tree.leaves(tuple(sh.spec)))):
                return P(*sh.spec)
            return P()

        in_specs = tuple(spec_for(x) for x in flat)
        key = (self._run_fn_key(fn), treedef, in_specs)
        cache = getattr(self, "_run_cache", None)
        if cache is None:
            cache = self._run_cache = {}
        compiled = cache.get(key)
        if compiled is None:
            compiled = cache[key] = self._build_run_program(
                fn, treedef, in_specs)
        return compiled(*flat)

    @staticmethod
    def _run_fn_key(fn):
        """Cache key for a step function that tolerates the natural TF-port
        pattern of an inline lambda recreated every call: key on the code
        object plus the closure VALUES (when hashable), so
        ``strategy.run(lambda b: step(b), ...)`` in a loop hits the cache
        instead of recompiling per step. Unhashable closure contents fall
        back to object identity (each distinct closure compiles once)."""
        code = getattr(fn, "__code__", None)
        if code is None:  # callable object — identity
            return fn
        cells = getattr(fn, "__closure__", None) or ()
        # Bound methods delegate __code__/__closure__ to the function with
        # `self` in neither. Key the receiver by its attribute VALUES (same
        # semantics as closure cells: changed values recompile, equal values
        # hit the cache); receivers with unhashable attrs key by identity —
        # there, like tf.function, attribute mutation does NOT retrace.
        receiver = getattr(fn, "__self__", None)
        if receiver is not None:
            try:
                rkey = (type(receiver),
                        tuple(sorted(vars(receiver).items())))
                hash(rkey)
            except (TypeError, ValueError):
                rkey = ("id", id(receiver))
        else:
            rkey = None
        try:
            key = (code, tuple(c.cell_contents for c in cells),
                   getattr(fn, "__defaults__", None), rkey)
            hash(key)  # unhashable closure contents -> identity fallback
            return key
        except (TypeError, ValueError):  # unhashable / empty cell
            return fn

    def _build_run_program(self, fn, treedef, in_specs):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def body(*leaves):
            a, k = jax.tree.unflatten(treedef, leaves)
            out = fn(*a, **k)
            # Leading replica axis: each replica contributes [1, ...]; the
            # out_spec concatenates them to [num_replicas, ...] — the
            # PerReplica-stack convention reduce() consumes.
            return jax.tree.map(lambda t: jnp.asarray(t)[None], out)

        return jax.jit(jax.shard_map(body, mesh=self._mesh,
                                     in_specs=in_specs,
                                     out_specs=P(self.data_axis)))

    def reduce(self, op: ReduceOp | str, value):
        """Host-side reduction of per-replica values to single results,
        applied leaf-wise over pytrees (dict/tuple outputs of :meth:`run`
        reduce per leaf, like TF's ``strategy.reduce``)."""
        import jax
        import jax.numpy as jnp

        op = ReduceOp(op) if not isinstance(op, ReduceOp) else op
        if op not in (ReduceOp.SUM, ReduceOp.MEAN):
            raise ValueError(
                f"host-side reduce supports SUM/MEAN, got {op}")

        def red(leaf):
            v = jnp.asarray(leaf)
            if not v.ndim:
                return v
            return v.sum(axis=0) if op is ReduceOp.SUM else v.mean(axis=0)

        return jax.tree.map(red, value)

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(replicas={self.num_replicas_in_sync}, "
                f"mesh={tuple(self._mesh.shape.items())})")


class DefaultStrategy(Strategy):
    """No distribution: one device, the implicit strategy when none is scoped.

    Matches the baseline "strategy off" configuration (BASELINE.md config 1)
    and TF's default-strategy fallback."""

    def __init__(self):
        import jax

        super().__init__(devices=[jax.local_devices()[0]])


class MirroredStrategy(Strategy):
    """Sync data parallelism over one host's devices (README.md:15-19).

    Every variable is mirrored on each local device; gradients are all-reduced
    each batch. ``devices=None`` uses all local devices — the reference's
    "no GPUs -> CPU" degradation (README.md:34) falls out naturally because the
    mesh is built from whatever devices exist.
    """

    def __init__(self, devices: Sequence | None = None,
                 axis_shapes: Optional[dict] = None):
        super().__init__(devices=devices, local=devices is None,
                         axis_shapes=axis_shapes)
        logger.info("MirroredStrategy: %d replica(s) on mesh %s: %s",
                    self.num_replicas_in_sync, dict(self._mesh.shape),
                    [str(d) for d in self._mesh.devices.flat])


class MultiWorkerMirroredStrategy(Strategy):
    """Sync data parallelism across all cluster processes (README.md:21-29).

    Construction performs cluster bring-up exactly where the reference does it
    (strategy __init__ starts servers and blocks for peers, SURVEY.md §3.1):

    1. ``bootstrap.initialize()`` — TF_CONFIG (or TPU-pod autodetect) ->
       ``jax.distributed.initialize``; blocks until all processes join.
    2. Mesh over every global device (ICI within a slice, DCN across slices —
       XLA routes collectives; there is no RING/NCCL choice to make,
       ``communication`` is accepted for compatibility, README.md:23).
    3. Startup barrier, the analog of the reference's dummy-all-reduce barrier
       (tf:...collective_all_reduce_strategy.py:1043-1066).

    With one process and no cluster config this degrades to MirroredStrategy
    behavior (README.md:34): the mesh is just the local devices.
    """

    def __init__(self,
                 communication: CollectiveCommunication | str | None = None,
                 cluster_config=None,
                 axis_shapes: Optional[dict] = None):
        import jax

        self.communication = CollectiveCommunication.resolve(communication)
        bootstrap.initialize(config=cluster_config)
        # axis_shapes carves the GLOBAL device set into extra mesh axes
        # (seq/model/...) exactly as on MirroredStrategy — e.g.
        # {'data': n_processes, 'model': local_devices} keeps the model
        # axis intra-host (ICI-speed all-reduces) with data across hosts:
        # make_mesh orders devices process-contiguously, so inner axes
        # land within a process when the sizes align.
        super().__init__(axis_shapes=axis_shapes)  # all global devices
        bootstrap.barrier("MultiWorkerMirroredStrategy_init")
        # Peer-health monitoring starts only after the startup barrier, so it
        # can't fire during bring-up (tf:...collective_all_reduce_strategy.py:
        # 1043-1066 ordering; SURVEY.md D12). No-op for single-process jobs;
        # a per-process singleton so repeated constructions don't leak threads.
        from tpu_dist.cluster.liveness import shared_monitor

        self.liveness_monitor = shared_monitor().start()
        # Bring-up log, the analog of TF's "MultiWorkerMirroredStrategy with
        # cluster_spec = {...}, num_workers = N" line (SURVEY.md §3.5).
        cfg = bootstrap.cluster_config()
        logger.info(
            "MultiWorkerMirroredStrategy up: num_workers = %d, "
            "num_replicas_in_sync = %d, communication = %s, cluster_spec = %s",
            jax.process_count(), self.num_replicas_in_sync,
            self.communication.name,
            dict(cfg.cluster.jobs) if cfg else "<auto>")

    @property
    def is_chief(self) -> bool:
        return bootstrap.is_chief()


def __getattr__(name: str):
    # PEP 562 lazy re-export: ParameterServerStrategy lives in ps_strategy
    # (which imports Strategy from here), so a top-level import would be
    # circular. Resolved on first attribute access instead.
    if name == "ParameterServerStrategy":
        from tpu_dist.parallel.ps_strategy import ParameterServerStrategy

        return ParameterServerStrategy
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


_default_strategy: Optional[DefaultStrategy] = None


def get_strategy() -> Strategy:
    """Innermost scoped strategy, or the (cached) DefaultStrategy — identity-
    stable like ``tf.distribute.get_strategy()``."""
    stack = _strategy_stack()
    if stack:
        return stack[-1]
    global _default_strategy
    if _default_strategy is None:
        _default_strategy = DefaultStrategy()
    return _default_strategy


def has_strategy() -> bool:
    return bool(_strategy_stack())
