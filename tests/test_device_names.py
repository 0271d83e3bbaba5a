"""The device's work by name, where a trace reads it.

Every program a serving engine builds lowers as module
``jit_serve_<kind>``; every instruction of a decode plan op carries ONE
layer scope (``tpu_dist.<scope>`` in its name stack), of the kind that op
names; the training walk scopes its layers and the optimizer update; and
the program's spans are written whenever a profiler session records,
whoever started it. Names and scopes are metadata: the lowered programs
stay the parent's (``test_hybrid_lm.PARENT_DIGESTS``).
"""

import contextlib
import functools
import importlib.util
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.models.policy import policy, set_policy
from tpu_dist.models.transformer import build_transformer_lm
from tpu_dist.observe import metrics
from tpu_dist.serve import engine as engine_lib
from tpu_dist.serve import kv_cache
from tpu_dist.serve.engine import ServeEngine
from tpu_dist.utils import profiler

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCOPE = re.compile(r"tpu_dist\.[a-z_.]*[a-z_]")
OP_NAME = re.compile(r'op_name="([^"]*)"')
MODULE = re.compile(r"module @(\S+)")

#: The scope each plan op's instructions carry (the vocabulary head, the
#: plan's last op, carries ``tpu_dist.head``; a residual's start computes
#: nothing).
OP_SCOPES = {
    "attn": "tpu_dist.attn", "latent": "tpu_dist.mla",
    "state": "tpu_dist.kda", "gqa": "tpu_dist.gqa.full",
    "window": "tpu_dist.gqa.window", "moe": "tpu_dist.moe",
    "embed": "tpu_dist.embed", "pos": "tpu_dist.embed",
    "point": "tpu_dist.dense", "res_end": "tpu_dist.dense"}


def _family(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_for_names", ROOT / f"tpubench/reference/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _rehearsal(config):
    full = json.loads((ROOT / f"tpubench/configs/{config}.json").read_text())
    return {**full, **full["rehearsal"]}


def _gpt2():
    return build_transformer_lm(512, 128, d_model=64, depth=2, num_heads=4,
                                ff_dim=256)


MODELS = {
    "gpt2": (_gpt2, jnp.int8, {}),
    "ling": (lambda: _family("ling_hybrid").build_program(
        _rehearsal("ling-3.0-flash"), 1), jnp.bfloat16, {"slots": 4}),
    "exaone": (lambda: _family("exaone_moe").build_program(
        _rehearsal("k-exaone-236b-a23b"), 1), jnp.bfloat16, {"slots": 4}),
}


@pytest.fixture
def mixed_bfloat16():
    before = policy()
    set_policy("mixed_bfloat16")
    yield
    set_policy(before)


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        tree)


def _module(lowered) -> str:
    return MODULE.search(lowered.as_text()).group(1)


# -- programs by kind ---------------------------------------------------------


def test_every_program_of_a_paged_ragged_engine_is_named_by_its_kind(
        monkeypatch):
    model = build_transformer_lm(64, 64, d_model=16, depth=2, num_heads=2)
    model.init(0)
    engine = ServeEngine(model, max_batch=4, max_len=64, paged=True,
                         page_size=8, ragged=True, kv_dtype="int8",
                         prefill_chunk=8)
    built = {}
    acquire = engine._acquire_program

    def recording(kind, key, builder):
        fn = acquire(kind, key, builder)

        def call(*args):
            built.setdefault((kind, key), (fn, _shapes(args)))
            return fn(*args)
        return call

    monkeypatch.setattr(engine, "_acquire_program", recording)
    for n in (5, 13, 21):   # one chunk, two, three
        engine.submit(list(range(1, n + 1)), max_new_tokens=4)
    engine.run_until_idle()
    assert {kind for kind, _ in built} == {"paged_decode_ragged",
                                           "paged_prefill"}
    for (kind, _), (fn, args) in built.items():
        assert _module(fn.lower(*args)) == f"jit_serve_{kind}"
    pool = _shapes(engine.cache)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    row = jax.ShapeDtypeStruct((4,), jnp.int32)
    assert _module(engine._copy_fn.lower(pool, i32, i32)) \
        == "jit_serve_copy_page"
    assert _module(engine._swap_state_fn.lower(pool, i32, i32)) \
        == "jit_serve_swap_state"
    assert _module(engine_lib._next_inputs.lower(row, row, i32, i32)) \
        == "jit_serve_next_inputs"
    cache = _shapes(kv_cache.init_cache(engine.plan, max_batch=4,
                                        max_len=16))
    assert _module(engine._swap_fn.lower(cache, i32, i32)) \
        == "jit_serve_swap_slots"


# -- one scope a plan op ------------------------------------------------------


def _marked(monkeypatch):
    """Put a marker ``planop<i>`` around the scope of plan op ``i``: an
    instruction's name stack then says which op it came from."""
    scope = kv_cache._scope

    def marked(plan, op):
        i = next(k for k, o in enumerate(plan.ops) if o is op)
        stack = contextlib.ExitStack()
        stack.enter_context(jax.named_scope(f"planop{i}"))
        stack.enter_context(scope(plan, op))
        return stack

    monkeypatch.setattr(kv_cache, "_scope", marked)


def _op_scopes(text) -> list[tuple[int | None, list[str]]]:
    """(plan op, layer scopes) of every name stack of an instruction (XLA
    joins those of instructions it merged with ``;``)."""
    out = []
    for line in text.splitlines():
        m = OP_NAME.search(line)
        for stack in m.group(1).split(";") if m else ():
            if stack.startswith("jit("):
                op = re.search(r"/planop(\d+)/", stack)
                out.append((op and int(op.group(1)), SCOPE.findall(stack)))
    return out


@pytest.mark.parametrize("family", list(MODELS))
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_every_instruction_of_a_plan_op_carries_one_scope_of_its_kind(
        family, program, monkeypatch, mixed_bfloat16):
    build, dtype, pool_kw = MODELS[family]
    model = build()
    plan = kv_cache.build_plan(model)
    params = jax.eval_shape(lambda: model.init(0))["params"]
    pool = jax.eval_shape(lambda: kv_cache.init_page_pool(
        plan, num_pages=24, page_size=16, dtype=dtype, **pool_kw))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    _marked(monkeypatch)
    if program == "decode":
        fn = functools.partial(kv_cache.paged_decode_ragged, plan,
                               walk=False)
        args = (params, pool, i32(4, 8), i32(4), i32(4),
                jax.ShapeDtypeStruct((4,), jnp.bool_))
    else:
        fn = functools.partial(kv_cache.paged_prefill, plan)
        slot = (i32(),) if plan.recurrent else ()
        args = (params, pool, i32(8), i32(32), i32(), i32(), *slot)
    text = jax.jit(engine_lib._picking(fn)).lower(*args).compile().as_text()
    rows = _op_scopes(text)
    seen = set()
    for op, scopes in rows:
        if op is None:
            # Outside the walk: the head's cast and pick, or nothing.
            assert scopes in ([], ["tpu_dist.head"]), scopes
            continue
        tag = plan.ops[op][0]
        want = ("tpu_dist.head" if op == len(plan.ops) - 1
                else OP_SCOPES[tag])
        assert scopes == [want], (tag, scopes)
        seen.add(op)
    # Every op that computes left instructions behind.
    assert seen == {i for i, op in enumerate(plan.ops)
                    if op[0] != "res_start"}
    kinds = {OP_SCOPES[op[0]] for op in plan.ops[:-1] if op[0] in OP_SCOPES}
    assert {s for _, scopes in rows for s in scopes} \
        == kinds | {"tpu_dist.head"}


# -- the training walk --------------------------------------------------------


def test_the_training_step_scopes_its_layers_and_its_optimizer():
    from tpu_dist.models.layers import Dense, Dropout
    from tpu_dist.models.model import Sequential
    from tpu_dist.models.transformer import Embedding, PositionalEmbedding
    from tpu_dist.ops.optimizers import Adam
    from tpu_dist.training.trainer import Trainer

    model = Sequential([Embedding(32, 16), PositionalEmbedding(8),
                        Dense(16, activation="relu"), Dropout(0.1),
                        Dense(32)], input_shape=(8,))
    model.compile(optimizer=Adam(1e-3),
                  loss="sparse_categorical_crossentropy")
    trainer = Trainer(model)
    trainer.ensure_variables()
    step, v = trainer._pure_train_step(), trainer.variables
    x = np.zeros((4, 8), np.int32)
    args = (v["params"], v["state"], v["opt"], v["metrics"],
            (jnp.float32(0), jnp.float32(0)), jnp.asarray(x), jnp.asarray(x),
            jax.random.PRNGKey(0))
    text = jax.jit(step).lower(*args).compile().as_text()
    scopes = {s for line in text.splitlines()
              for m in [OP_NAME.search(line)] if m
              for stack in m.group(1).split(";")
              for s in SCOPE.findall(stack)}
    assert scopes == {"tpu_dist.embed", "tpu_dist.dense", "tpu_dist.head",
                      "tpu_dist.optimizer"}


def test_layers_name_their_scope_kind():
    from tpu_dist.models import hybrid
    from tpu_dist.models.layers import Block, Dense, Residual
    from tpu_dist.models.transformer import MultiHeadAttention

    assert Dense(4).scope_kind == "point"
    assert Block(()).scope_kind is None and Residual(()).scope_kind is None
    # The flash kernels are named after their name stack: unscoped.
    assert MultiHeadAttention(2, 8).scope_kind is None
    window = hybrid.GroupedQueryAttention(4, 2, 8, window=4)
    full = hybrid.GroupedQueryAttention(4, 2, 8)
    assert (window.scope_kind, full.scope_kind) == ("window", "gqa")
    assert set(profiler.LAYER_SCOPES) >= {
        window.scope_kind, full.scope_kind, "head", "optimizer"}


# -- spans under any profiler session -----------------------------------------


def test_spans_are_written_whenever_a_profiler_session_records(tmp_path):
    from jax.profiler import ProfileData

    metrics.disable()
    assert profiler.span("test.outside") is profiler.NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.span("test.inside") as inside:
            jnp.ones(4).block_until_ready()
        with profiler.step_annotation(3):
            pass
    finally:
        jax.profiler.stop_trace()
    assert inside is not profiler.NULL_SPAN and inside.seconds > 0
    assert profiler.span("test.after") is profiler.NULL_SPAN
    # The registry stayed off: nothing was observed.
    assert "span.test.inside.s" not in \
        metrics.get_registry().snapshot()["distributions"]
    path, = tmp_path.rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert "tpu_dist.test.inside" in names
    assert "tpu_dist.test.outside" not in names
