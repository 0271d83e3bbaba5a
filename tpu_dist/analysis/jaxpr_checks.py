"""shardcheck jaxpr-level checks — collective consistency under trace.

The AST pass sees spelling; this pass sees the program XLA will actually
partition. Representative entry points (the trainer step, both pipeline
schedules, the TP/SP/MoE parallel families, the resilience and observe
demo steps) are traced on CPU with ``jax.make_jaxpr`` — tracing compiles
nothing and needs no TPU — and the resulting jaxprs are walked
interprocedurally (descending into ``pjit``/``scan``/``while``/``cond``/
``remat``/``custom_vjp`` sub-jaxprs) for the deadlock classes the
reference's TF runtime ordered away:

**SC201 — collective-order divergence.** In an SPMD program every device
runs the same instruction stream, so collectives pair up by construction —
EXCEPT inside ``lax.cond``/``lax.switch``, where a device-varying predicate
(``axis_index``-derived, the usual reason SPMD code branches at all) sends
different devices down different branches. If those branches issue
different collective sequences, the mismatched launches rendezvous with
each other and the program deadlocks. This is why
``pipeline_1f1b.one_f_one_b`` keeps its ``ppermute``s OUTSIDE the
forward/backward/idle switch; the check pins that invariant for every
entry point and every user program that registers one.

**SC202 — data-dependent collective trip count.** A collective inside a
``lax.while_loop`` body launches once per iteration, and a while's trip
count is data-dependent by construction — ranks whose predicates diverge
launch different counts and the rendezvous mismatches. (A static-length
``lax.scan`` is fine: every rank runs exactly L iterations.)

**SC203 — collective payload mismatch.** Launches that pair up by order
but not by payload: cond/switch branches issuing the same collective
sequence over different payload shapes/dtypes (rank A psums f32[2,4]
against rank B's f32[4,4] — hang or garbage), and ``ppermute``
permutations invalid for the axis in effect (out-of-range index,
duplicate source, duplicate destination — all trace fine today).

Note on ``pvary``/``pcast``: jax's check_vma rewriter inserts these
replication-type casts into traced bodies,
*including asymmetrically into cond branches whose values differ in
replication only*. They move no bytes and launch nothing, so they are NOT
collectives for any rule here — treating them as real traffic made SC201
false-positive on ring attention's causal skip branch.

User programs opt in by defining a module-level ``shardcheck_entry()``
returning ``(fn, example_args)`` — or ``(fn, example_args,
donate_argnums)`` to tell SC303 which arguments the production caller
donates; the CLI traces it and applies the same checks (see cli.py).
"""

from __future__ import annotations

import logging
from typing import Callable, Iterable, Optional

from tpu_dist.analysis.rules import Finding

logger = logging.getLogger("tpu_dist.analysis")

#: Primitive-name fragments that identify cross-device collectives in a
#: jaxpr. Substring match keeps this robust across jax renames
#: (psum/psum2/psum_invariant all count). pbroadcast/pvary are absent by
#: design — see the module docstring.
_COLLECTIVE_FRAGMENTS = ("psum", "pmax", "pmin", "ppermute", "all_gather",
                         "all_to_all", "reduce_scatter", "pgather",
                         "pshuffle")


def _is_collective(prim_name: str) -> bool:
    return any(f in prim_name for f in _COLLECTIVE_FRAGMENTS)


def _cause(e: BaseException, limit: int = 160) -> str:
    """``ExceptionType: first line of the message`` — jax trace errors run
    to pages, and a multi-line info finding buries the tier-1 log line
    that explains WHY an entry point degraded."""
    first = (str(e).splitlines() or [""])[0].strip()
    if len(first) > limit:
        first = first[:limit - 1] + "…"
    return f"{type(e).__name__}: {first}" if first else type(e).__name__


def _inner_jaxprs(params: dict):
    """Sub-jaxprs of one eqn's params (branches, scan/while bodies,
    shard_map/pjit bodies, custom_vjp closures, ...)."""
    for value in params.values():
        for item in (value if isinstance(value, (tuple, list)) else (value,)):
            jaxpr = getattr(item, "jaxpr", item)
            if hasattr(jaxpr, "eqns"):
                yield jaxpr


#: Primitive-name fragments that identify RNG consumption in a jaxpr
#: (SC610). Substring match for the same rename-robustness reason as
#: _COLLECTIVE_FRAGMENTS: threefry2x32 / threefry_2x32 / random_seed /
#: random_bits / random_fold_in / rng_bit_generator all count.
_RNG_FRAGMENTS = ("threefry", "random_seed", "random_bits", "random_fold",
                  "random_gamma", "random_wrap", "random_unwrap",
                  "rng_bit_generator", "rng_uniform")


def rng_primitives(jaxpr) -> list[str]:
    """Sorted, de-duplicated RNG primitive names a jaxpr consumes,
    descending into every sub-jaxpr. An empty list is a CONTRACT for the
    RNG-free entry points (serve decode/prefill, audit checksums, the PS
    server apply): their whole exactness story assumes no stream is
    consumed inside the step."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: set = set()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(f in name for f in _RNG_FRAGMENTS):
            out.add(name)
        for sub in _inner_jaxprs(eqn.params):
            out.update(rng_primitives(sub))
    return sorted(out)


def check_rng_baseline(rng_now: dict, rng_baseline: dict,
                       path: str) -> list:
    """SC610: a traced entry point whose committed baseline records ZERO
    RNG primitives now consumes one — the exactness contract for that
    step just silently broke. Drift in already-RNG-consuming entries
    (new primitive name, jax rename) degrades to SC900 info with the
    re-baseline hint, never an error: intended randomness is re-baselined,
    contractually-absent randomness is a gate."""
    findings: list[Finding] = []
    for name in sorted(rng_now):
        if name not in rng_baseline:
            continue  # new entries are covered at --update-baseline time
        before, after = list(rng_baseline[name]), list(rng_now[name])
        if before == after:
            continue
        if not before and after:
            findings.append(Finding(
                "SC610", path, 1, 0,
                f"{name}: baseline records this step as RNG-FREE, but it "
                f"now consumes {', '.join(after)}; a contractually "
                f"deterministic step (replay/verify compares its bits) "
                f"grew a random stream — remove it, or re-run cost "
                f"--update-baseline only if the contract itself changed"))
        else:
            findings.append(Finding(
                "SC900", path, 1, 0,
                f"{name}: RNG primitive set drifted from baseline "
                f"({', '.join(before) or 'none'} -> "
                f"{', '.join(after) or 'none'}); if intended, re-run "
                f"cost --update-baseline and commit the diff"))
    return findings


def _collective_uses(jaxpr) -> list:
    """Depth-first ``(name, axes, shape, dtype)`` tuples for every
    collective launch a jaxpr issues (program launch order for
    straight-line code; branch bodies contribute in branch order)."""
    from tpu_dist.analysis.costmodel import _axis_names

    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if _is_collective(name):
            aval = eqn.invars[0].aval if eqn.invars else None
            out.append((name, _axis_names(eqn.params),
                        tuple(getattr(aval, "shape", ()) or ()),
                        str(getattr(aval, "dtype", ""))))
        for sub in _inner_jaxprs(eqn.params):
            out.extend(_collective_uses(sub))
    return out


def collective_sequence(jaxpr) -> list[str]:
    """Depth-first sequence of collective primitive names issued by a
    jaxpr, descending into every sub-jaxpr."""
    out = []
    for name, axes, _, _ in _collective_uses(jaxpr):
        out.append(f"{name}[{axes}]" if axes else name)
    return out


def check_branch_collectives(jaxpr, *, label: str,
                             path: str = "<trace>") -> list[Finding]:
    """SC201/SC203a over every ``cond``/``switch`` anywhere in the jaxpr:
    branches must issue the same collective sequence (SC201), over the
    same payload shapes/dtypes (SC203)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    findings: list[Finding] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            uses = [_collective_uses(b)
                    for b in eqn.params.get("branches", ())]
            orders = [tuple((n, a) for n, a, _, _ in u) for u in uses]
            if len(set(orders)) > 1:
                desc = ", ".join(
                    f"branch {i}: "
                    f"{[f'{n}[{a}]' for n, a in o] or ['<none>']}"
                    for i, o in enumerate(orders))
                findings.append(Finding(
                    "SC201", path, 1, 0,
                    f"{label}: cond/switch branches issue different "
                    f"collective sequences ({desc}); devices taking "
                    "different branches will deadlock — hoist the "
                    "collective out of the branch"))
            elif len({tuple(u) for u in uses}) > 1:
                desc = ", ".join(
                    f"branch {i}: "
                    f"{[f'{n}[{a}] {d}{list(s)}' for n, a, s, d in u]}"
                    for i, u in enumerate(uses))
                findings.append(Finding(
                    "SC203", path, 1, 0,
                    f"{label}: cond/switch branches issue the same "
                    f"collective sequence over DIFFERENT payloads "
                    f"({desc}); ranks taking different branches "
                    "rendezvous with mismatched shapes/dtypes — hang or "
                    "garbage on real hardware"))
        for sub in _inner_jaxprs(eqn.params):
            findings.extend(check_branch_collectives(
                sub, label=label, path=path))
    return findings


def check_while_collectives(jaxpr, *, label: str,
                            path: str = "<trace>") -> list[Finding]:
    """SC202: any collective reachable from a ``while`` eqn's body or
    predicate, anywhere in the jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    findings: list[Finding] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            for part in ("cond_jaxpr", "body_jaxpr"):
                sub = eqn.params.get(part)
                if sub is None:
                    continue
                uses = _collective_uses(sub)
                if uses:
                    ops = sorted({f"{n}[{a}]" for n, a, _, _ in uses})
                    where = ("predicate" if part == "cond_jaxpr"
                             else "body")
                    findings.append(Finding(
                        "SC202", path, 1, 0,
                        f"{label}: {', '.join(ops)} inside a while-loop "
                        f"{where}; the trip count is data-dependent, so "
                        "ranks whose predicates diverge launch different "
                        "collective counts and deadlock — use a "
                        "static-length scan, or hoist the collective "
                        "out of the loop"))
        else:
            for sub in _inner_jaxprs(eqn.params):
                findings.extend(check_while_collectives(
                    sub, label=label, path=path))
    return findings


def check_permutes(jaxpr, *, label: str, path: str = "<trace>",
                   mesh_env: Optional[dict] = None,
                   model_mesh: Optional[dict] = None) -> list[Finding]:
    """SC203b: every ``ppermute`` permutation must be valid for the mesh
    axis in effect — indices in ``[0, P)``, no duplicate source, no
    duplicate destination. jax traces all three violations without
    complaint; on the machine a duplicate destination is two sends
    racing one receive and an out-of-range index is a hang."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    mesh_env = dict(mesh_env or {})
    model_mesh = dict(model_mesh or {})
    findings: list[Finding] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if "ppermute" in name:
            from tpu_dist.analysis.costmodel import _axis_names

            axes = _axis_names(eqn.params)
            size = 1
            for a in axes:
                size *= int(model_mesh.get(a, mesh_env.get(a, 0)) or 0)
            perm = tuple(eqn.params.get("perm", ()))
            problems = []
            if size > 0:
                bad = [p for p in perm
                       if not (0 <= p[0] < size and 0 <= p[1] < size)]
                if bad:
                    problems.append(
                        f"indices {sorted(set(bad))} outside the axis "
                        f"size {size}")
            srcs = [s for s, _ in perm]
            dsts = [d for _, d in perm]
            if len(set(srcs)) != len(srcs):
                problems.append("duplicate sources")
            if len(set(dsts)) != len(dsts):
                problems.append("duplicate destinations (two sends "
                                "racing one receive)")
            if problems:
                findings.append(Finding(
                    "SC203", path, 1, 0,
                    f"{label}: ppermute over axis {axes} has an invalid "
                    f"permutation — {'; '.join(problems)} — perm={perm}"))
        inner_env = mesh_env
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            if mesh is not None and hasattr(mesh, "shape"):
                inner_env = dict(mesh_env)
                inner_env.update(
                    {str(k): int(v) for k, v in dict(mesh.shape).items()})
        for sub in _inner_jaxprs(eqn.params):
            findings.extend(check_permutes(
                sub, label=label, path=path, mesh_env=inner_env,
                model_mesh=model_mesh))
    return findings


def check_jaxpr(closed, *, label: str, path: str = "<trace>",
                donated: Iterable[int] = ()) -> list[Finding]:
    """Every jaxpr-level rule over one traced entry point: SC201/SC203a
    (branch divergence), SC202 (while collectives), SC203b (permutation
    validity), SC303 (undonated dead arguments)."""
    from tpu_dist.analysis import costmodel

    findings = check_branch_collectives(closed, label=label, path=path)
    findings.extend(check_while_collectives(closed, label=label, path=path))
    findings.extend(check_permutes(closed, label=label, path=path))
    report = costmodel.analyze_jaxpr(closed, entry=label)
    findings.extend(costmodel.sc303_findings(
        report, path=path, donated=donated))
    return findings


def check_callable(fn: Callable, args: tuple, *, label: str,
                   path: str = "<trace>",
                   donated: Iterable[int] = ()) -> list[Finding]:
    """Trace ``fn(*args)`` and run every jaxpr-level rule on the result."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return check_jaxpr(closed, label=label, path=path, donated=donated)


# -- built-in entry points ----------------------------------------------------

def _pipe_mesh_or_none():
    import jax

    from tpu_dist.parallel import mesh as mesh_lib
    from tpu_dist.parallel.axes import PIPE_AXIS

    devices = jax.devices()
    if len(devices) < 2:
        return None
    return mesh_lib.make_mesh({PIPE_AXIS: 2}, devices=devices[:2])


def _shard_mapped(body, mesh, in_specs, out_specs):
    import jax

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _trace_gpipe():
    """GPipe schedule over a 2-stage pipe mesh (parallel/pipeline_parallel)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_dist.parallel.axes import PIPE_AXIS
    from tpu_dist.parallel.pipeline_parallel import gpipe_schedule

    mesh = _pipe_mesh_or_none()
    if mesh is None:
        raise RuntimeError("needs >= 2 devices for a pipe mesh")
    params = jnp.ones(())

    def stage_apply(p, x, key):
        return x * p

    def body(x_mb):
        return gpipe_schedule(stage_apply, params, x_mb, num_stages=2,
                              axis_name=PIPE_AXIS)

    mapped = _shard_mapped(body, mesh, (P(),), P())
    return jax.make_jaxpr(mapped)(jnp.zeros((4, 2, 3)))


def _trace_1f1b():
    """1F1B schedule over a 2-stage pipe mesh (parallel/pipeline_1f1b)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_dist.parallel.pipeline_1f1b import one_f_one_b

    mesh = _pipe_mesh_or_none()
    if mesh is None:
        raise RuntimeError("needs >= 2 devices for a pipe mesh")
    stage_p = jnp.ones(())
    pre_p = jnp.ones(())
    post_p = jnp.ones(())

    def stage_apply(p, a):
        return a * p

    def pre_apply(p, x):
        return x * p

    def post_loss(p, a, y):
        return ((a * p - y) ** 2).mean()

    def body(x_mb, y_mb):
        return one_f_one_b(stage_apply, pre_apply, post_loss, stage_p,
                           pre_p, post_p, x_mb, y_mb, num_stages=2)

    mapped = _shard_mapped(body, mesh, (P(), P()), (P(), P(), P(), P()))
    x = jnp.zeros((4, 2))
    return jax.make_jaxpr(mapped)(x, x)


def _trace_train_step():
    """The trainer's SPMD step on a tiny Dense model (training/trainer.py)."""
    import jax
    import numpy as np

    from tpu_dist.models import Dense, Sequential
    from tpu_dist.training.trainer import Trainer

    model = Sequential([Dense(4)], input_shape=(4,), name="shardcheck_probe")
    model.compile(optimizer="sgd", loss="mse")
    trainer = Trainer(model)
    step = trainer._pure_step()
    trainer.ensure_variables()
    state = trainer.train_state()
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 4), np.float32)
    rng = jax.random.PRNGKey(0)
    return jax.make_jaxpr(step)(*state, x, y, rng)


def _trace_train_step_bucketed():
    """The trainer's bucketed-reduction schedule (gradient_bucket_bytes=1
    forces one bucket per leaf on the probe model, so every explicit
    per-bucket psum launch site appears in the jaxpr — the schedule the
    latency cost model prices per launch, and the program SC201 guards
    against rank-divergent bucket order)."""
    import jax
    import numpy as np

    from tpu_dist.models import Dense, Sequential
    from tpu_dist.parallel import MirroredStrategy
    from tpu_dist.training.trainer import Trainer

    model = Sequential([Dense(4)], input_shape=(4,), name="shardcheck_probe")
    model.compile(optimizer="sgd", loss="mse", gradient_bucket_bytes=1)
    model.strategy = MirroredStrategy()  # all 8 forced-CPU devices
    trainer = Trainer(model)
    trainer._sync_step_knobs()
    step = trainer._pure_train_step()
    trainer.ensure_variables()
    state = trainer.train_state()
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 4), np.float32)
    rng = jax.random.PRNGKey(0)
    return jax.make_jaxpr(step)(*state, x, y, rng)


def _trace_train_step_prefetch():
    """The trainer's step with double-buffered input enabled
    (prefetch_to_device=2). The traced program must be IDENTICAL to the
    plain train_step — prefetch lives entirely on the host side of the
    seam (a background device_put thread), so baselining this entry pins
    that turning the knob on never changes the compiled step."""
    import jax
    import numpy as np

    from tpu_dist.models import Dense, Sequential
    from tpu_dist.training.trainer import Trainer

    model = Sequential([Dense(4)], input_shape=(4,), name="shardcheck_probe")
    model.compile(optimizer="sgd", loss="mse", prefetch_to_device=2)
    trainer = Trainer(model)
    trainer._sync_step_knobs()
    step = trainer._pure_train_step()
    trainer.ensure_variables()
    state = trainer.train_state()
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 4), np.float32)
    rng = jax.random.PRNGKey(0)
    return jax.make_jaxpr(step)(*state, x, y, rng)


def _trace_resilience_demo_step():
    """The supervised/resumable trainer step as the resilience demo runs it
    (resilience/entrypoints.py: the reference CNN under fit(checkpoint_dir=),
    the program every chaos run restarts and resumes)."""
    import jax
    import numpy as np

    from tpu_dist.models.cnn import build_and_compile_cnn_model
    from tpu_dist.training.trainer import Trainer

    model = build_and_compile_cnn_model(learning_rate=0.01)
    trainer = Trainer(model)
    step = trainer._pure_step()
    trainer.ensure_variables()
    state = trainer.train_state()
    x = np.zeros((8, 28, 28, 1), np.float32)
    y = np.zeros((8,), np.int32)
    rng = jax.random.PRNGKey(0)
    return jax.make_jaxpr(step)(*state, x, y, rng)


def _trace_observe_demo_step():
    """The demo step exactly as ``python -m tpu_dist.observe demo`` runs it:
    telemetry armed — registry enabled, collective observe hook installed —
    while the program traces. Pins that observe instrumentation stays on
    the host side of the seam: hook firings at trace time must not add or
    reorder collectives in the program XLA partitions."""
    import jax
    import numpy as np

    from tpu_dist.models.cnn import build_and_compile_cnn_model
    from tpu_dist.observe.metrics import MetricsRegistry
    from tpu_dist.observe.telemetry import registry_collective_hook
    from tpu_dist.parallel import collectives
    from tpu_dist.training.trainer import Trainer

    registry = MetricsRegistry(enabled=True)
    prev = collectives.install_observe_hook(
        registry_collective_hook(registry))
    try:
        model = build_and_compile_cnn_model(learning_rate=0.01)
        trainer = Trainer(model)
        step = trainer._pure_step()
        trainer.ensure_variables()
        state = trainer.train_state()
        x = np.zeros((8, 28, 28, 1), np.float32)
        y = np.zeros((8,), np.int32)
        rng = jax.random.PRNGKey(0)
        return jax.make_jaxpr(step)(*state, x, y, rng)
    finally:
        collectives.install_observe_hook(prev)


def _trace_megatron_block():
    """The tensor-parallel MLP block's collective pattern (parallel/
    tensor.py): column-parallel up-projection, row-parallel down-
    projection, one partial-sum all-reduce back to the residual stream.
    tensor.py expresses this as GSPMD sharding ANNOTATIONS (XLA derives
    the psum at compile time, invisible to make_jaxpr), so the entry
    traces the equivalent explicit shard_map program — the communication
    contract the annotations imply, priced and rule-checked."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_dist.parallel import mesh as mesh_lib
    from tpu_dist.parallel.axes import MODEL_AXIS

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError("needs >= 4 devices for a model mesh")
    mesh = mesh_lib.make_mesh({MODEL_AXIS: 4}, devices=devices[:4])

    def block(x, w1, w2):
        h = jnp.maximum(x @ w1, 0.0)  # column-parallel: w1 [d, f/P]
        y = h @ w2                    # row-parallel:    w2 [f/P, d]
        return jax.lax.psum(y, MODEL_AXIS)

    mapped = _shard_mapped(
        block, mesh,
        (P(), P(None, MODEL_AXIS), P(MODEL_AXIS, None)), P())
    return jax.make_jaxpr(mapped)(
        jnp.zeros((16, 8)), jnp.ones((8, 32)), jnp.ones((32, 8)))


def _trace_ring_attention():
    """Causal ring attention over a 4-way seq mesh (parallel/sequence.py):
    the K/V ppermute ring inside a static-length scan, plus the causal
    skip cond — the branch that must stay collective-free for SC201."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.parallel import mesh as mesh_lib
    from tpu_dist.parallel.axes import SEQ_AXIS
    from tpu_dist.parallel.sequence import ring_attention

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError("needs >= 4 devices for a seq mesh")
    mesh = mesh_lib.make_mesh({SEQ_AXIS: 4}, devices=devices[:4])
    q = jnp.zeros((2, 2, 16, 4))

    def attend(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, causal=True)

    return jax.make_jaxpr(attend)(q, q, q)


def _trace_moe_layer():
    """MixtureOfExperts' sharded apply under a data x expert strategy
    scope (parallel/expert.py): the all_to_all dispatch/return pair plus
    the aux-loss pmeans over both axes."""
    import jax
    import jax.numpy as jnp

    import tpu_dist as td
    from tpu_dist.parallel.axes import DATA_AXIS, EXPERT_AXIS
    from tpu_dist.parallel.expert import MixtureOfExperts

    devices = jax.devices()
    if len(devices) < 8:
        raise RuntimeError("needs >= 8 devices for a data x expert mesh")
    strategy = td.MirroredStrategy(
        axis_shapes={DATA_AXIS: 2, EXPERT_AXIS: 4})
    with strategy.scope():
        layer = MixtureOfExperts(num_experts=4, ff_dim=16, top_k=2)
        params, state, _ = layer.init(jax.random.PRNGKey(0), (8, 8, 8))
        x = jnp.zeros((8, 8, 8))
        return jax.make_jaxpr(
            lambda p, xx: layer.apply(p, state, xx)[0])(params, x)


def _trace_checkpoint_snapshot():
    """The async checkpointer's on-device snapshot program
    (training/checkpoint.py: ``snapshot_copy_program``) over a compiled
    trainer's saveable state. Pins the zero-stall contract: the snapshot a
    save dispatches on the training thread must stay collective-free — any
    gather/reduce sneaking into it would put the background writer in the
    collective ordering and deadlock against the main thread's barriers —
    and its HBM cost is the transient double-buffer the pipeline budgets."""
    import jax

    from tpu_dist.models import Dense, Sequential
    from tpu_dist.training import checkpoint
    from tpu_dist.training.trainer import Trainer

    model = Sequential([Dense(4)], input_shape=(4,), name="shardcheck_probe")
    model.compile(optimizer="sgd", loss="mse")
    trainer = Trainer(model)
    trainer.ensure_variables()
    saveable = checkpoint._saveable(trainer.variables)
    return jax.make_jaxpr(checkpoint.snapshot_copy_program)(saveable)


def _serve_probe():
    """Tiny servable LM + plan shared by the two serve tracers."""
    import jax

    from tpu_dist.models.transformer import build_transformer_lm
    from tpu_dist.serve import kv_cache

    model = build_transformer_lm(32, 16, d_model=16, depth=1, num_heads=2)
    params = model.init(0)["params"]
    plan = kv_cache.build_plan(model)
    cache = kv_cache.init_cache(plan, max_batch=4, max_len=16)
    return plan, params, cache


def _trace_serve_prefill():
    """``serve.kv_cache.prefill`` — the full causal pass over one padded
    prompt that seeds a KV-cache slot. Pins that prefill stays
    collective-free on the default strategy (request-level parallelism
    only; a collective here would serialize admissions behind the decode
    stream) and baselines the cache-write HBM cost."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, cache = _serve_probe()
    tokens = jnp.zeros((8,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, t: kv_cache.prefill(plan, p, c, t, jnp.int32(5),
                                         jnp.int32(0)))(
        params, cache, tokens)


def _trace_serve_decode():
    """``serve.kv_cache.decode_step`` — one generated token per active
    slot against the cached K/V. The steady-state serving hot loop: pins
    it collective-free and baselines its comm/HBM so a regression (an
    accidental all-gather of the cache, a cache-sized temporary) gates CI
    exactly like a training-step regression. The serve-resilience layer
    (request journal, shedding, stall watchdog) is host-side by design —
    it must add zero collectives and zero comm bytes here, which this
    unchanged baseline enforces."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, cache = _serve_probe()
    tokens = jnp.zeros((4,), jnp.int32)
    lengths = jnp.ones((4,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, t, ln: kv_cache.decode_step(plan, p, c, t, ln,
                                                 bucket=4))(
        params, cache, tokens, lengths)


def _trace_serve_paged_prefill():
    """``serve.kv_cache.paged_prefill`` — the suffix prefill that writes
    K/V through a page table onto the paged pool (serve/paging.py). One
    program serves cold prompts (start=0) and prefix-cache hits alike.
    Pins it collective-free like the contiguous prefill, and baselines
    the page-gather HBM cost so an accidental pool-sized temporary (e.g.
    gathering every pool page instead of the slot's table row) gates
    CI."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, _ = _serve_probe()
    pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=4)
    page_row = jnp.zeros((4,), jnp.int32)
    tokens = jnp.zeros((8,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, r, t: kv_cache.paged_prefill(
            plan, p, c, r, t, jnp.int32(5), jnp.int32(0)))(
        params, pool, page_row, tokens)


def _trace_serve_paged_decode():
    """``serve.kv_cache.paged_decode_step`` — the paged serving hot loop:
    tail-page scatter append + attention over gathered pages. Pins it
    collective-free and baselines comm/HBM alongside the contiguous
    ``serve.decode_step``, so the paged subsystem's device cost is
    budgeted exactly like the path it replaces (the host-side allocator,
    prefix cache, and copy-on-write bookkeeping must add nothing
    here)."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, _ = _serve_probe()
    pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=4)
    tables = jnp.zeros((4, 4), jnp.int32)
    tokens = jnp.zeros((4,), jnp.int32)
    lengths = jnp.ones((4,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, tb, t, ln: kv_cache.paged_decode_step(
            plan, p, c, tb, t, ln, bucket=4))(
        params, pool, tables, tokens, lengths)


def _trace_serve_prefill_chunk():
    """``serve.kv_cache.prefill_chunk_step`` — one mid-prompt chunk of
    the interleaved prefill: writes the chunk's K/V at a traced start
    offset and attends over everything cached so far. Runs between
    decode steps, so it inherits the decode-loop contract: pinned
    collective-free, and its HBM baseline catches an accidental
    whole-cache temporary (the chunk should touch one slot's rows
    plus the shared weights, nothing cache-sized)."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, cache = _serve_probe()
    tokens = jnp.zeros((8,), jnp.int32)
    # A non-degenerate mid-prompt chunk: start=8, valid through 12, pad
    # to 16 == max_len (the caller-enforced bound).
    return jax.make_jaxpr(
        lambda p, c, t: kv_cache.prefill_chunk_step(
            plan, p, c, t, jnp.int32(12), jnp.int32(0), jnp.int32(8)))(
        params, cache, tokens)


def _trace_serve_paged_prefill_chunk():
    """``serve.paged_prefill_chunk`` — the paged chunked-prefill step.
    Deliberately the SAME program as ``serve.paged_prefill`` called at a
    mid-prompt (start > 0, length < prompt end) window: chunking on the
    paged path reuses the traced-start seam instead of adding a kernel.
    Pinned separately so a future 'optimization' that forks the chunked
    call into its own program (doubling the compiled surface) or adds a
    collective to it shows up as a baseline diff."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, _ = _serve_probe()
    pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=4)
    page_row = jnp.zeros((4,), jnp.int32)
    tokens = jnp.zeros((4,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, r, t: kv_cache.paged_prefill(
            plan, p, c, r, t, jnp.int32(8), jnp.int32(4)))(
        params, pool, page_row, tokens)


def _trace_serve_paged_decode_ragged():
    """``serve.kv_cache.paged_decode_ragged`` — the single full-capacity
    decode program that replaces the pow2-bucket family: per-slot active
    masking routes inactive rows' tail writes to the scratch page and
    attention masks by length. Pinned separately from the bucketed step
    so the retrace-surface collapse stays honest: ONE program, the same
    collective-free/RNG-free contract, and an HBM baseline that catches
    an accidental pool-sized temporary exactly like the bucketed pin."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, _ = _serve_probe()
    pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=4)
    tables = jnp.zeros((4, 4), jnp.int32)
    tokens = jnp.zeros((4,), jnp.int32)
    lengths = jnp.ones((4,), jnp.int32)
    active = jnp.ones((4,), bool)
    return jax.make_jaxpr(
        lambda p, c, tb, t, ln, a: kv_cache.paged_decode_ragged(
            plan, p, c, tb, t, ln, a))(
        params, pool, tables, tokens, lengths, active)


def _trace_serve_paged_prefill_int8():
    """``serve.kv_cache.paged_prefill`` over an int8 pool — quantize-on-
    write (per-position amax scales into the fp32 scale rows) with
    dequant fused into the page gather, plus the max-abs quant-error
    reduction the engine reads back host-side. Pinned separately from
    the float pin so the quantized path carries its own collective-free
    / RNG-free contract and HBM budget (the int8 payload plus scale rows
    must price BELOW the float pool, and the error reduction must not
    smuggle in a host callback)."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, _ = _serve_probe()
    pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=4,
                                   dtype=jnp.int8)
    page_row = jnp.zeros((4,), jnp.int32)
    tokens = jnp.zeros((8,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, r, t: kv_cache.paged_prefill(
            plan, p, c, r, t, jnp.int32(5), jnp.int32(0)))(
        params, pool, page_row, tokens)


def _trace_serve_paged_decode_int8():
    """``serve.kv_cache.paged_decode_step`` over an int8 pool — the
    quantized serving hot loop: int8 tail-page scatter + scale-row write,
    dequantizing gather, fp32 softmax. Same collective-free contract as
    the float pin; the separate HBM baseline is the capacity claim made
    auditable (the gathered working set shrinks with the payload)."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, _ = _serve_probe()
    pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=4,
                                   dtype=jnp.int8)
    tables = jnp.zeros((4, 4), jnp.int32)
    tokens = jnp.zeros((4,), jnp.int32)
    lengths = jnp.ones((4,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c, tb, t, ln: kv_cache.paged_decode_step(
            plan, p, c, tb, t, ln, bucket=4))(
        params, pool, tables, tokens, lengths)


def _trace_serve_paged_decode_ragged_int8(walk=None):
    """``serve.kv_cache.paged_decode_ragged`` over an int8 pool — the
    two tentpole optimizations composed: one full-capacity masked decode
    program over quantized pages. The production configuration for
    capacity-bound serving, so it gets its own pin rather than trusting
    the features to compose silently."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.serve import kv_cache

    plan, params, _ = _serve_probe()
    pool = kv_cache.init_page_pool(plan, num_pages=8, page_size=4,
                                   dtype=jnp.int8)
    tables = jnp.zeros((4, 4), jnp.int32)
    tokens = jnp.zeros((4,), jnp.int32)
    lengths = jnp.ones((4,), jnp.int32)
    active = jnp.ones((4,), bool)
    return jax.make_jaxpr(
        lambda p, c, tb, t, ln, a: kv_cache.paged_decode_ragged(
            plan, p, c, tb, t, ln, a, walk=walk))(
        params, pool, tables, tokens, lengths, active)


def _trace_serve_paged_decode_ragged_walked():
    """The same program with the attention the TPU runs: the
    page-walking kernel (``ops/paged_attention.py``), traced with
    ``walk=True`` so the ``pallas_call`` is in the jaxpr on any backend.
    Its modeled peak HBM is what separates it from
    ``serve.paged_decode_ragged_int8``: no gathered, dequantized
    ``[slots, heads, max_len, key_dim]`` copy of the cache."""
    return _trace_serve_paged_decode_ragged_int8(walk=True)


def _trace_integrity_health_step():
    """The trainer step WITH the in-step health vector — same program the
    plain train_step entry traces (health_summary is always folded in), but
    pinned separately so the integrity contract is explicit: arming the
    guard must add zero collectives and zero comm bytes to the hot loop
    (all three health scalars reduce values the step already computed)."""
    import jax
    import numpy as np

    from tpu_dist.models import Dense, Sequential
    from tpu_dist.training.trainer import Trainer

    model = Sequential([Dense(4)], input_shape=(4,), name="shardcheck_probe")
    model.compile(optimizer="sgd", loss="mse")
    trainer = Trainer(model)
    step = trainer._pure_step()
    trainer.ensure_variables()
    state = trainer.train_state()
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 4), np.float32)
    rng = jax.random.PRNGKey(0)

    def health_only(*args):
        return step(*args)[-1]

    return jax.make_jaxpr(health_only)(*state, x, y, rng)


def _trace_integrity_audit_checksum():
    """The SDC audit's per-replica checksum program
    (training/integrity.py: ``build_audit_checksum``). Pins that the audit
    is collective-FREE — each device checksums its own replica copy and the
    comparison happens on host through the collectives seam — so its
    baselined comm payload is exactly 0 bytes and it can never deadlock
    against the training step's collectives."""
    import jax
    import numpy as np

    from tpu_dist.models import Dense, Sequential
    from tpu_dist.parallel.strategy import MirroredStrategy
    from tpu_dist.training.integrity import build_audit_checksum
    from tpu_dist.training.trainer import Trainer

    strategy = MirroredStrategy()
    with strategy.scope():
        model = Sequential([Dense(4)], input_shape=(4,),
                           name="shardcheck_probe")
        model.compile(optimizer="sgd", loss="mse")
        trainer = Trainer(model)
        trainer.ensure_variables()
        leaves = jax.tree_util.tree_leaves(trainer.variables["params"])
        key = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        fn = build_audit_checksum(strategy.mesh, key)
        return jax.make_jaxpr(fn)(*leaves)


def _trace_integrity_audit_checksum_sharded():
    """The SHARD-AWARE audit program on a TP mesh (``{data: 4, model: 2}``):
    sharded leaves are checksummed shard-locally (``in_specs`` taken from
    the live ``NamedSharding``s — column-parallel kernel, sharded bias,
    row-parallel kernel, replicated bias, the Megatron layout) and the
    shard-group comparison happens on host. Pins that shard-awareness
    added NO collective: the sharded table build is as comm-free as the
    replicated one — exactly 0 baselined bytes."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dist.parallel.strategy import MirroredStrategy
    from tpu_dist.training.integrity import build_audit_checksum

    if jax.device_count() < 8:
        raise RuntimeError("needs >= 8 devices for a data x model mesh")
    strategy = MirroredStrategy(axis_shapes={"data": 4, "model": 2})
    mesh = strategy.mesh
    leaves = [
        jax.device_put(np.zeros(8, np.float32),
                       NamedSharding(mesh, P("model"))),
        jax.device_put(np.zeros((4, 8), np.float32),
                       NamedSharding(mesh, P(None, "model"))),
        jax.device_put(np.zeros(4, np.float32), NamedSharding(mesh, P())),
        jax.device_put(np.zeros((8, 4), np.float32),
                       NamedSharding(mesh, P("model", None))),
    ]
    specs = tuple(P(*l.sharding.spec) for l in leaves)
    key = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
    fn = build_audit_checksum(mesh, key, specs)
    return jax.make_jaxpr(fn)(*leaves)


def _trace_ps_worker_step():
    """The async PS worker's local step exactly as ``_fit_ps`` compiles
    it (training/trainer.py): forward/backward ONLY — no optimizer update
    (the server owns opt state) and NO collective anywhere, which is the
    load-bearing property of the execution model: a worker's hot loop
    must never block on a peer, so a straggler or a dead rank cannot
    stall it. The baseline pins that collective count at zero."""
    import tempfile

    import jax
    import numpy as np

    from tpu_dist.models.cnn import build_and_compile_cnn_model
    from tpu_dist.parallel.ps_strategy import ParameterServerStrategy
    from tpu_dist.training.trainer import Trainer

    strategy = ParameterServerStrategy(
        tempfile.mkdtemp(prefix="psa-"), role="worker", rank=0,
        num_workers=1, staleness=4, sync=False)
    with strategy.scope():
        model = build_and_compile_cnn_model(learning_rate=0.01)
    trainer = Trainer(model)
    step = trainer._build_ps_worker_step()
    trainer.ensure_variables()
    params = trainer.variables["params"]
    state = trainer.variables["state"]
    x = np.zeros((8, 28, 28, 1), np.float32)
    y = np.zeros((8,), np.int32)
    rng = jax.random.PRNGKey(0)
    return jax.make_jaxpr(step)(params, state, x, y, rng)


def _trace_ps_server_apply():
    """The PS server's apply program (parallel/ps_strategy.py PSServer):
    one pushed gradient packet folded into the authoritative params/opt
    state via ``optimizer.update``. Single-device by construction and
    collective-free — the server serializes applies in arrival order, so
    any collective here would be a bug, not a cost."""
    import tempfile

    import jax

    from tpu_dist.cluster.ps_transport import PSDir
    from tpu_dist.models.cnn import build_and_compile_cnn_model
    from tpu_dist.parallel.ps_strategy import PSServer

    model = build_and_compile_cnn_model(learning_rate=0.01)
    server = PSServer(model, PSDir(tempfile.mkdtemp(prefix="psb-")),
                      num_workers=1, budget=1)
    params = server.variables["params"]
    opt = server.variables["opt"]
    grads = jax.tree_util.tree_map(jax.numpy.zeros_like, params)
    return jax.make_jaxpr(server._apply)(params, opt, grads)


def _trace_jobs_runtime_train_step():
    """The trainer step built INSIDE a multi-tenant job scope
    (jobs/runtime.py): same probe model as ``training.trainer.train_step``
    but with the strategy and program acquisition flowing through a
    :class:`~tpu_dist.jobs.runtime.MeshRuntime` submesh lease. Pins the
    solo no-op contract from the program side: packing a job onto a
    1-slice pool must change NOTHING — same jaxpr family, zero added
    collectives, zero added comm bytes vs the solo baseline."""
    import jax
    import numpy as np

    from tpu_dist.jobs.runtime import MeshRuntime, job_scope
    from tpu_dist.jobs.spec import JobSpec
    from tpu_dist.models import Dense, Sequential
    from tpu_dist.training.trainer import Trainer

    runtime = MeshRuntime(jax.devices()[:1])
    spec = JobSpec(name="shardcheck-job", kind="train", devices=1)
    with job_scope(runtime, spec):
        model = Sequential([Dense(4)], input_shape=(4,),
                           name="shardcheck_probe")
        model.compile(optimizer="sgd", loss="mse")
        trainer = Trainer(model)
        step = trainer._pure_step()
        trainer.ensure_variables()
        state = trainer.train_state()
        x = np.zeros((8, 4), np.float32)
        y = np.zeros((8, 4), np.float32)
        rng = jax.random.PRNGKey(0)
        return jax.make_jaxpr(step)(*state, x, y, rng)


def _trace_jobs_runtime_decode_step():
    """``serve.kv_cache.decode_step`` built inside a multi-tenant job
    scope — the packed serving counterpart of ``serve.decode_step``. Pins
    that a serve job on a leased submesh slice decodes with the identical
    collective-free program a solo engine compiles."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.jobs.runtime import MeshRuntime, job_scope
    from tpu_dist.jobs.spec import JobSpec
    from tpu_dist.serve import kv_cache

    runtime = MeshRuntime(jax.devices()[:1])
    spec = JobSpec(name="shardcheck-serve-job", kind="serve", devices=1)
    with job_scope(runtime, spec):
        plan, params, cache = _serve_probe()
        tokens = jnp.zeros((4,), jnp.int32)
        lengths = jnp.ones((4,), jnp.int32)
        return jax.make_jaxpr(
            lambda p, c, t, ln: kv_cache.decode_step(plan, p, c, t, ln,
                                                     bucket=4))(
            params, cache, tokens, lengths)


ENTRY_POINTS = {
    "pipeline_parallel.gpipe_schedule": _trace_gpipe,
    "pipeline_1f1b.one_f_one_b": _trace_1f1b,
    "training.trainer.train_step": _trace_train_step,
    "training.trainer.train_step_bucketed": _trace_train_step_bucketed,
    "training.trainer.train_step_prefetch": _trace_train_step_prefetch,
    "resilience.entrypoints.demo_train_step": _trace_resilience_demo_step,
    "observe.demo_train_step": _trace_observe_demo_step,
    "parallel.tensor.megatron_block": _trace_megatron_block,
    "parallel.sequence.ring_attention": _trace_ring_attention,
    "parallel.expert.moe_layer": _trace_moe_layer,
    "training.checkpoint.snapshot_copy": _trace_checkpoint_snapshot,
    "serve.prefill_step": _trace_serve_prefill,
    "serve.decode_step": _trace_serve_decode,
    "serve.paged_prefill": _trace_serve_paged_prefill,
    "serve.paged_decode_step": _trace_serve_paged_decode,
    "serve.prefill_chunk_step": _trace_serve_prefill_chunk,
    "serve.paged_prefill_chunk": _trace_serve_paged_prefill_chunk,
    "serve.paged_decode_ragged": _trace_serve_paged_decode_ragged,
    "serve.paged_prefill_int8": _trace_serve_paged_prefill_int8,
    "serve.paged_decode_int8": _trace_serve_paged_decode_int8,
    "serve.paged_decode_ragged_int8": _trace_serve_paged_decode_ragged_int8,
    "serve.paged_decode_ragged_walked":
        _trace_serve_paged_decode_ragged_walked,
    "training.integrity.health_step": _trace_integrity_health_step,
    "training.integrity.audit_checksum": _trace_integrity_audit_checksum,
    "training.integrity.audit_checksum_sharded":
        _trace_integrity_audit_checksum_sharded,
    "jobs.runtime.train_step": _trace_jobs_runtime_train_step,
    "jobs.runtime.decode_step": _trace_jobs_runtime_decode_step,
    "parallel.ps_strategy.ps_worker_step": _trace_ps_worker_step,
    "parallel.ps_strategy.ps_server_apply": _trace_ps_server_apply,
}

#: Argument positions each entry point's production caller donates
#: (consumed by SC303). None of the built-in steps donate today; the map
#: exists so registering a donating entry point is one line.
ENTRY_DONATED: dict[str, tuple] = {}


def trace_entry_points(
        names: Optional[Iterable[str]] = None) -> tuple[dict, list]:
    """Trace every built-in entry point. Returns ``(traced, findings)``
    where ``traced`` maps name -> ClosedJaxpr and ``findings`` carries an
    SC900 info finding (exception class + one-line cause) for each entry
    that cannot trace in this environment — degrade, never crash."""
    traced: dict = {}
    findings: list[Finding] = []
    for name, tracer in ENTRY_POINTS.items():
        if names is not None and name not in names:
            continue
        try:
            traced[name] = tracer()
        except Exception as e:  # noqa: BLE001 - degrade, never crash
            logger.debug("entry point %s untraceable", name, exc_info=True)
            findings.append(Finding(
                "SC900", f"<entry:{name}>", 1, 0,
                f"entry point {name} could not be traced here "
                f"({_cause(e)}); jaxpr rules skipped for it"))
    return traced, findings


def run_entry_points(
        names: Optional[Iterable[str]] = None) -> list[Finding]:
    """Trace every built-in entry point and collect jaxpr-rule findings.
    An entry point that cannot trace in this environment (too few
    devices, a moved jax internal) degrades to an SC900 info finding,
    never a crash — the lint pass's results still stand."""
    traced, findings = trace_entry_points(names)
    for name, closed in traced.items():
        findings.extend(check_jaxpr(
            closed, label=name, path=f"<entry:{name}>",
            donated=ENTRY_DONATED.get(name, ())))
    return findings
