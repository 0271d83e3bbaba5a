"""The harness as the driver meets it: no TPU means no result, the files
of BENCHMARK.json are all there, and a later PR adds a model family, a
configuration, a mix, a cell and a counter-backed metric with new files
and entries only."""

import hashlib
import json
import re
import shutil
import subprocess
import sys

import pytest

from tpubench import run as bench_run
from tpubench.harness import cells, readers

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_only_files_that_exist():
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for cfg in bench["configs"]:
        assert (ROOT / cfg["file"]).is_file()
        assert NAME.match(cfg["name"]) and len(cfg["why"]) <= 200
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    names = set()
    for w in bench["workloads"]:
        cell = cells.Cell(w["name"])
        assert cell.kind in ("train", "serve")
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            spec = cell.metric_spec(m["name"])
            assert spec["reader"] in readers.READERS
            names.add(m["name"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and NAME.match(m["name"])
        assert m["name"] in names
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload",
         "train.gpt2-medium.dp1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == bench_run.EXIT_NO_CHIP
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_fewer_chips_than_the_cell_asks_for_is_no_chip():
    from tpubench.harness import device

    with pytest.raises(device.NoChip, match="asks for 64"):
        device.require_chips(64, rehearse=True)


#: A throw-away FAMILY, as a later ``model_config`` PR would bring one:
#: its own key names (``hidden_size``, ``vocab_size``...), its own leaf
#: names, its own ``build_program`` through the repo's constructor. It
#: names nothing of the family the benchmark has, and the harness is not
#: edited for it.
THROWAWAY_FAMILY = '''
import math

import jax
import jax.numpy as jnp

from tpubench.harness.reference import seed_key

MATRICES = ("q", "k", "v", "o", "up", "down")


def _glorot(key, shape):
    limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def make_params(key, cfg):
    d, f, v = cfg["hidden_size"], cfg["mlp_size"], cfg["vocab_size"]
    n = cfg["num_layers"]
    ks = iter(jax.random.split(key, 16))
    p = {"tok": 0.02 * jax.random.normal(next(ks), (v, d)),
         "pos": 0.02 * jax.random.normal(next(ks), (cfg["max_positions"], d)),
         "out": _glorot(next(ks), (d, v))}
    shapes = {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
              "up": (d, f), "down": (f, d)}
    for name in MATRICES:
        p["h." + name] = _glorot(next(ks), (n, *shapes[name]))
    return p


def _norm(x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5)


def forward(params, tokens, cfg, *, quant=None):
    if quant is not None:
        raise NotImplementedError("the throw-away family has no control")
    b, ln = tokens.shape
    heads = cfg["heads"]
    x = params["tok"][tokens] + params["pos"][:ln]
    mask = jnp.tril(jnp.ones((ln, ln), bool))
    for i in range(cfg["num_layers"]):
        w = {m: params["h." + m][i] for m in MATRICES}
        h = _norm(x)
        split = lambda y: y.reshape(b, ln, heads, -1).transpose(0, 2, 1, 3)
        q, k, v = split(h @ w["q"]), split(h @ w["k"]), split(h @ w["v"])
        s = q @ k.transpose(0, 1, 3, 2) / math.sqrt(q.shape[-1])
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ v
        x = x + a.transpose(0, 2, 1, 3).reshape(b, ln, -1) @ w["o"]
        u = _norm(x) @ w["up"]
        u = 0.5 * u * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                    * (u + 0.044715 * u ** 3)))
        x = x + u @ w["down"]
    return _norm(x) @ params["out"]


def build_program(cfg, seed):
    from tpu_dist.models.transformer import build_transformer_lm

    d, f, v = cfg["hidden_size"], cfg["mlp_size"], cfg["vocab_size"]
    model = build_transformer_lm(
        v, cfg["max_positions"], d_model=d, depth=cfg["num_layers"],
        num_heads=cfg["heads"], ff_dim=f)
    shapes = jax.eval_shape(lambda: model.init(0))["params"]

    def tree(key):
        p = make_params(key, cfg)
        ln = lambda: {"gamma": jnp.ones((d,)), "beta": jnp.zeros((d,))}
        t = {"embedding": {"table": p["tok"]},
             "positionalembedding": {"table": p["pos"]},
             "layernormalization": ln(),
             "dense": {"kernel": p["out"], "bias": jnp.zeros((v,))}}
        for i in range(cfg["num_layers"]):
            w = {m: p["h." + m][i] for m in MATRICES}
            z = jnp.zeros((d,))
            t["block" if i == 0 else f"block_{i}"] = {
                "residual": {"main": {
                    "layernormalization": ln(),
                    "multiheadattention": {
                        "wq": w["q"], "wk": w["k"], "wv": w["v"],
                        "wo": w["o"], "bq": z, "bk": z, "bv": z, "bo": z}}},
                "residual_1": {"main": {
                    "layernormalization": ln(),
                    "dense": {"kernel": w["up"], "bias": jnp.zeros((f,))},
                    "dense_1": {"kernel": w["down"], "bias": z}}}}
        return t

    make = jax.jit(tree)
    ours = jax.eval_shape(make, seed_key(seed))
    if (jax.tree_util.tree_map(lambda s: s.shape, ours)
            != jax.tree_util.tree_map(lambda s: s.shape, shapes)):
        raise RuntimeError("not the tree this family lays weights into")
    model.init = lambda _seed=0, input_shape=None: {
        "params": make(seed_key(seed)), "state": {}}
    return model


def sizes(cfg):
    return {"n_vocab": cfg["vocab_size"], "n_ctx": cfg["max_positions"]}


def _weights(cfg):
    d, f = cfg["hidden_size"], cfg["mlp_size"]
    return cfg["num_layers"] * (4 * d * d + 2 * d * f) + d * cfg["vocab_size"]


def decode_step_flops(cfg, contexts):
    return (len(contexts) * 2 * _weights(cfg)
            + 4 * cfg["hidden_size"] * cfg["num_layers"] * sum(contexts))


def decode_step_bytes(cfg, live_tokens, kv_dtype):
    return 2 * _weights(cfg) + live_tokens * 2 * cfg["num_layers"] * (
        cfg["hidden_size"] + 4 * cfg["heads"])
'''


def _digest_of_tree(root) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture()
def copy_with_additions(tmp_path):
    """A temporary checkout: BENCHMARK.json and tpubench/ copied, then a
    throw-away family, configuration, mix, cell and counter-backed metric
    ADDED as new files and new entries; nothing that was there is edited,
    in the copy or in the real tree."""
    real_before = (_digest_of_tree(ROOT / "tpubench"),
                   (ROOT / "BENCHMARK.json").read_bytes())
    shutil.copytree(ROOT / "tpubench", tmp_path / "tpubench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    copied = _digest_of_tree(tmp_path / "tpubench")
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    (tmp_path / "tpubench/reference/throwaway.py").write_text(
        THROWAWAY_FAMILY)
    (tmp_path / "tpubench/configs/throwaway.json").write_text(json.dumps({
        "hidden_size": 1536, "vocab_size": 32000, "num_layers": 12,
        "heads": 12, "mlp_size": 6144, "max_positions": 2048,
        "reference": "tpubench/reference/throwaway.py",
        "rehearsal": {"hidden_size": 64, "vocab_size": 384,
                      "num_layers": 2, "heads": 4, "mlp_size": 128,
                      "max_positions": 128}}))
    mix = cells.load_json(ROOT / "tpubench/traffic/chat.json")
    mix["rehearsal"] = {**mix["rehearsal"], "rate_rps": 12.0}
    mix["repeat_share"] = 0.2
    (tmp_path / "tpubench/traffic/rush.json").write_text(json.dumps(mix))
    (tmp_path / "tpubench/layer_metrics/decode_steps_per_token.json"
     ).write_text(json.dumps({
         "reader": "counter_ratio", "numerator": "serve.decode.steps",
         "denominator": "serve.tokens.generated"}))
    bench["configs"].append({
        "name": "throwaway", "source": "https://example.org/throwaway",
        "file": "tpubench/configs/throwaway.json", "reduced": [],
        "why": "a test's configuration of a test's family"})
    bench["workloads"].append({
        "name": "serve.throwaway.rush", "config": "throwaway",
        "traffic": "rush", "chips": 1, "why": "a test's cell"})
    bench["per_layer"].append({
        "name": "decode_steps_per_token", "unit": "steps/token",
        "better": "lower", "source": "program_counter", "layer": "server",
        "moves": "serve_tokens_per_s",
        "workloads": ["serve.throwaway.rush"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve.gpt2-large.chat" in m.get("workloads", []) \
                and m["name"] != "decode_steps_per_token":
            m["workloads"] = m["workloads"] + ["serve.throwaway.rush"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    yield tmp_path
    after = _digest_of_tree(tmp_path / "tpubench")
    assert {k: after[k] for k in copied} == copied
    assert real_before == (_digest_of_tree(ROOT / "tpubench"),
                           (ROOT / "BENCHMARK.json").read_bytes())


def test_a_family_and_its_cell_are_added_by_files_and_entries_alone(
        copy_with_additions):
    root = copy_with_additions
    args = bench_run.parse([
        "--workload", "serve.throwaway.rush", "--seed", str(2 ** 31 + 9),
        "--seconds", "3", "--trace", "1", "--rehearse", "1",
        "--root", str(root)])
    cell = bench_run.load_cell(args)
    assert cell.family.__file__ == str(
        root / "tpubench/reference/throwaway.py")
    assert cell.sizes == {"n_vocab": 384, "n_ctx": 128}
    assert cell.config["num_layers"] == 2 and cell.mix["rate_rps"] == 12.0
    result = bench_run.run_cell(cell, args)
    line = bench_run.result_line(cell, args, result)
    # A rehearsal is never a result: not correct, and no device metric.
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert result["checks_ok"], result["rows"]
    assert result["host"]["checked_tokens"] > 20
    layers = result["per_layer"]
    assert 0 < layers["decode_steps_per_token"]["value"] <= 1.0
    assert layers["serve_prefix_hit_share"]["value"] > 0
    # With no trace of a device here every device reader stays silent
    # rather than print a 0.
    assert "decode_step_mfu" not in layers
    assert "device_idle_share.serve" not in layers
    assert result["host"]["sent"] >= 30 and result["failed"] == 0


def test_the_harness_names_no_family():
    """Whatever belongs to a model family lives in its module under
    ``reference/``: no file of the harness, nor ``run.py``, holds a
    family's name, a configuration's name or one of a family's keys."""
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    words = {p.stem for p in (ROOT / "tpubench/reference").glob("*.py")
             if p.stem != "__init__"}
    assert words, "the benchmark has no family module"
    for cfg in bench["configs"]:
        config = cells.load_json(ROOT / cfg["file"])
        words |= {k for k, v in config.items() if isinstance(v, int)}
        words.add(cfg["name"])
    # What the interface fixes by name (``sizes``) a family may share.
    words -= {"n_vocab", "n_ctx"}
    files = sorted((ROOT / "tpubench/harness").glob("*.py")) + [
        ROOT / "tpubench/run.py"]
    assert len(files) > 10
    for path in files:
        text = path.read_text()
        for word in sorted(words):
            assert not re.search(rf"(?<![A-Za-z0-9]){re.escape(word)}"
                                 rf"(?![A-Za-z0-9])", text), (path, word)


def test_every_configuration_names_a_family_that_loads():
    for cfg in cells.load_json(ROOT / "BENCHMARK.json")["configs"]:
        config = cells.load_json(ROOT / cfg["file"])
        family = cells.load_family(ROOT / config["reference"])
        for name in ("make_params", "forward", "build_program", "sizes"):
            assert callable(getattr(family, name)), (cfg["name"], name)
        assert {"n_vocab", "n_ctx"} <= set(family.sizes(config))
