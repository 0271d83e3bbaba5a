"""The ``bailing_hybrid`` family as the benchmark holds it: the
configuration against the catalog row it was cut from, the arithmetic the
cut was sized by, the reference computed a layer at a time, and the new
cell through ``run.py``'s own code path at rehearsal sizes."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import run as bench_run
from tpubench.harness import cells
from tpubench.harness.reference import seed_key

ROOT = cells.ROOT
CELL = "serve.ling-3.0-flash.longgen"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: Never a width: the depth, the experts held, the vocabulary slice, MTP.
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"}


@pytest.fixture(scope="module")
def config():
    return cells.load_json(ROOT / "tpubench/configs/ling-3.0-flash.json")


@pytest.fixture(scope="module")
def family():
    return cells.load_family(ROOT / "tpubench/reference/ling_hybrid.py")


def test_every_published_key_is_kept_and_only_the_cut_differs(config):
    if not CATALOG.is_file():
        pytest.skip("the catalog of public architectures is not here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Ling-3.0-flash")
    entry = next(c for c in cells.load_json(ROOT / "BENCHMARK.json")["configs"]
                 if c["name"] == "ling-3.0-flash")
    assert entry["source"] == row["source_url"]
    assert set(entry["reduced"]) == REDUCED
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == REDUCED
    assert config["published"] == {k: row["config"][k] for k in REDUCED}


def test_the_cut_is_sized_as_the_issue_sized_it(config, family):
    parts, layers = family.part_params(config), family.layer_counts(config)
    assert layers == {"mla": 1, "kda": 7, "dense": 2, "moe": 6}
    assert [family.layer_kind(config, i)[0] for i in range(8)].index(
        "mla") == 5
    assert parts["kda"] == 52_592_640 and parts["mla"] == 31_965_184
    assert parts["expert"] == parts["shared"] == 5_898_240
    assert family.non_expert_matmul_params(config) == 638_337_024
    assert family.param_count(config) == 5_268_783_104     # 10.54 GB bf16
    assert family.kv_bytes_per_token(config, "bf16") == 1152
    assert family.state_bytes_per_slot(config) == 7 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 4)
    assert family.sizes(config) == {"n_vocab": 39296, "n_ctx": 2048}
    # 64 slots at 800 tokens: the bytes are a lower bound (no experts, no
    # state), the FLOPs whole.
    low = family.decode_step_bytes(config, 64 * 800, "bf16")
    assert low == 2 * 638_337_024 + 64 * 800 * 1152
    flops = family.decode_step_flops(config, [800] * 64)
    assert 64 * 2 * 638_337_024 < flops < 64 * 2 * 800e6


def test_the_program_tree_is_the_references_weights(config, family):
    from tpu_dist.models.policy import policy, set_policy

    cfg = {**config, **config["rehearsal"]}
    before = policy()
    set_policy("mixed_bfloat16")
    try:
        tree = family.build_program(cfg, 5).init()["params"]
    finally:
        set_policy(before)
    params = jax.jit(lambda k: family.make_params(k, cfg))(seed_key(5))
    assert set(params) == {"wte", "lnf", "head_w", "layer_keys"}
    w = family.layer_weights(params["layer_keys"][2],
                             family.layer_kind(cfg, 2), cfg)
    block = tree["block_2"]
    mla = block["residual"]["main"]["latentattention"]
    moe = block["residual_1"]["main"]["routedexperts"]
    # Matrices rounded once to bf16; the router and the norms float32.
    assert mla["wkvb"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(mla["wkvb"].astype(jnp.float32)),
                          np.asarray(w["wkvb"].astype(jnp.bfloat16)
                                     .astype(jnp.float32)))
    assert moe["router"].dtype == jnp.float32
    assert np.array_equal(np.asarray(moe["router"]), np.asarray(w["router"]))
    assert moe["wg"].shape == (8, 64, 32)          # the experts held: 8 of 16


def test_the_fp8_control_reads_lower_than_the_reference(config, family):
    cfg = {**config, **config["rehearsal"]}
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: family.make_params(k, cfg))(seed_key(3))
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, 512, size=(1, 48)), jnp.int32)
        ref = family.forward(params, tokens, cfg)
        low = family.forward(params, tokens, cfg, quant="fp8")
    assert ref.shape == (1, 48, 512) and bool(jnp.all(jnp.isfinite(low)))
    assert float(jnp.abs(ref - low).max()) > 0.05 * float(ref.std())


def test_the_new_cell_runs_through_the_harness_at_rehearsal_sizes():
    args = bench_run.parse([
        "--workload", CELL, "--seed", str(2 ** 31 + 28), "--seconds", "3",
        "--trace", "1", "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    assert cell.chips == 1 and cell.sizes == {"n_vocab": 512, "n_ctx": 128}
    full = cells.Cell(CELL).mix
    assert full["engine"] == {
        "max_batch": 64, "max_len": 2048, "paged": True, "ragged": True,
        "kv_dtype": "bf16", "page_size": 16, "num_pages": 8192,
        "prefill_chunk": 512}
    assert abs(full["rate_rps"] - 0.8 * full["knee_rps"]) < 1e-9
    result = bench_run.run_cell(cell, args)
    line = bench_run.result_line(cell, args, result)
    assert line["correct"] is False and line["metrics"] == {}
    assert result["checks_ok"], result["rows"]
    assert result["failed"] == 0 and result["host"]["checked_tokens"] > 20
    layers = result["per_layer"]
    # 8 of 16 experts held at rehearsal sizes; no prefix is ever hit.
    assert 35 < layers["moe_held_share"]["value"] < 65
    assert layers["moe_tokens_per_touched_expert"]["value"] >= 1.0
    assert layers["serve_prefill_chunk_ms"]["value"] > 0
    assert layers["serve_decode_wait_ms"]["value"] > 0
    assert layers["decode_pages_read_share"]["value"] == 100.0
    assert "serve_prefix_hit_share" not in layers
    assert "decode_step_mfu" not in layers      # no device trace here
    # Judged on both tails, as the issue says; the three quantities that
    # move the first token's time are read beside it.
    names = {m["name"] for m in cell.end_to_end}
    assert names == {"serve_tokens_per_s", "itl_p95_ms", "ttft_p95_ms",
                     "setup_s"}
    for name in ("generator_lateness_p95_ms", "serve_queue_wait_mean_ms",
                 "serve_prefill_mean_ms"):
        assert layers[name]["value"] >= 0
    moves = {m["name"]: m["moves"] for m in cell.per_layer}
    assert moves["serve_prefill_chunk_ms"] == "ttft_p95_ms"


# -- planted faults: what the cell's comparison must refuse --------------------


def _leave_the_state_behind_in_a_swap(run):
    """Compaction moves a request to another slot and its pages with it,
    but not its recurrent state: it decodes on over another's."""
    run.engine._swap_state_fn = lambda cache, i, j: cache
    run._undo = lambda: None


def _drop_the_routed_experts(run):
    """The expert layer answers with its shared expert alone."""
    from tpu_dist.parallel.routed_experts import RoutedExperts

    forward = RoutedExperts.forward

    def shared_only(self, params, x, valid=None):
        silent = {**params, "wd": params["wd"] * 0}
        return forward(self, silent, x, valid)

    RoutedExperts.forward = shared_only
    run._undo = lambda: setattr(RoutedExperts, "forward", forward)


def _another_experts_down_projection(run):
    """Every held expert multiplies by its neighbour's ``W_d``: rows
    sorted into the wrong group on the way out."""
    from tpu_dist.parallel.routed_experts import RoutedExperts

    forward = RoutedExperts.forward

    def shifted(self, params, x, valid=None):
        wrong = {**params, "wd": jnp.roll(params["wd"], 1, axis=0)}
        return forward(self, wrong, x, valid)

    RoutedExperts.forward = shifted
    run._undo = lambda: setattr(RoutedExperts, "forward", forward)


@pytest.mark.parametrize("fault", [_leave_the_state_behind_in_a_swap,
                                   _drop_the_routed_experts,
                                   _another_experts_down_projection],
                         ids=["state_left_behind_in_a_swap",
                              "routed_experts_left_out",
                              "another_experts_down_projection"])
def test_a_planted_fault_in_a_new_mechanism_is_not_correct(fault):
    args = bench_run.parse([
        "--workload", CELL, "--seed", str(2 ** 31 + 29), "--seconds", "3",
        "--trace", "0", "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    undo = []

    def sabotage(run):
        fault(run)
        undo.append(run._undo)

    try:
        result = bench_run.run_cell(cell, args, sabotage=sabotage)
    finally:
        for fn in undo:
            fn()
    assert not result["checks_ok"]
    assert [r["name"] for r in result["rows"] if not r["ok"]] == [
        "served_logit_gap"]
