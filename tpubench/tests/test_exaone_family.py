"""The ``exaone_moe`` family as the benchmark holds it: the configuration
against the catalog row it was cut from, the arithmetic the cut was sized
by, the reference computed a layer at a time, the new cell through
``run.py``'s own code path at rehearsal sizes, and what its comparison must
refuse: two faults planted in the program and the fp8 control."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import run as bench_run
from tpubench.harness import cells, serve_cell
from tpubench.harness.reference import seed_key

ROOT = cells.ROOT
CELL = "serve.k-exaone-236b-a23b.mixedlen"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: Never a width: the depth, the experts held, the vocabulary slice, MTP.
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"}


@pytest.fixture(scope="module")
def config():
    return cells.load_json(ROOT / "tpubench/configs/k-exaone-236b-a23b.json")


@pytest.fixture(scope="module")
def family():
    return cells.load_family(ROOT / "tpubench/reference/exaone_moe.py")


def test_every_published_key_is_kept_and_only_the_cut_differs(config):
    if not CATALOG.is_file():
        pytest.skip("the catalog of public architectures is not here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "K-EXAONE-236B-A23B")
    entry = next(c for c in cells.load_json(ROOT / "BENCHMARK.json")["configs"]
                 if c["name"] == "k-exaone-236b-a23b")
    assert entry["source"] == row["source_url"]
    assert set(entry["reduced"]) == REDUCED
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == REDUCED
    assert config["published"] == {k: row["config"][k] for k in REDUCED}
    # Every published width, unchanged.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) == (
                6144, 64, 8, 128)
    assert (config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["num_experts_published"],
            config["sliding_window"], config["routed_scaling_factor"]) == (
                18432, 2048, 8, 128, 128, 2.5)
    assert config["rope_parameters"]["rope_theta"] == 1_000_000
    assert set(config["assumed"]) >= {"norms", "rope", "router_bias"}


def test_the_cut_is_sized_as_the_issue_sized_it(config, family):
    parts, layers = family.part_params(config), family.layer_counts(config)
    assert layers == {"window": 6, "full": 2, "dense": 1, "moe": 7}
    assert [family.layer_kind(config, i)[0] for i in range(8)] == [
        "window", "window", "window", "full"] * 2
    assert parts["attention"] == 113_246_208           # 50.33+6.29+6.29+50.33 M
    assert parts["dense"] == 339_738_624
    assert parts["expert"] == parts["shared"] == 37_748_736
    assert parts["router"] == 786_432
    assert parts["head"] == parts["embedding"] == 117_964_800
    # Layer 0 453.0 M, a MoE layer 755.8 M (16 experts held of 128).
    assert parts["attention"] + parts["dense"] == 452_984_832
    assert (parts["attention"] + 17 * parts["expert"]
            + parts["router"]) == 755_761_152
    assert family.param_count(config) == 5_979_242_496      # 11.96 GB bf16
    assert family.non_expert_matmul_params(config) == 1_633_419_264
    assert family.kv_bytes_per_token(config, "bf16") == 8192   # 2 full layers
    assert family.window_bytes_per_slot(config, "bf16") == 6 * 128 * 4096
    assert family.sizes(config) == {"n_vocab": 19200, "n_ctx": 8192}
    # 64 slots at 1100 tokens: the bytes are a lower bound (no experts, no
    # rings), the FLOPs whole; a window layer counts 128 keys at most.
    low = family.decode_step_bytes(config, 64 * 1100, "bf16")
    assert low == 2 * 1_633_419_264 + 64 * 1100 * 8192
    flops = family.decode_step_flops(config, [1100] * 64)
    keys = 64 * (2 * 1100 + 6 * 128)
    assert flops == 64 * 2 * (1_633_419_264 + 7 * 37_748_736) + (
        2 * 2 * 64 * 128 * keys)
    short = family.decode_step_flops(config, [50])
    assert short == 2 * (1_633_419_264 + 7 * 37_748_736) + (
        2 * 2 * 64 * 128 * 8 * 50)


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
    gqa = block["residual"]["main"]["groupedqueryattention"]
    moe = block["residual_1"]["main"]["routedexperts"]
    # Matrices rounded once to bf16; the router and the norms float32.
    assert gqa["wk"].dtype == jnp.bfloat16 and gqa["wk"].shape == (64, 32)
    assert np.array_equal(np.asarray(gqa["wq"].astype(jnp.float32)),
                          np.asarray(w["wq"].astype(jnp.bfloat16)
                                     .astype(jnp.float32)))
    assert moe["router"].dtype == jnp.float32
    assert np.array_equal(np.asarray(moe["router"]), np.asarray(w["router"]))
    assert moe["wg"].shape == (8, 64, 32)          # the experts held: 8 of 16
    # The norms sit behind the sublayers, at the draw's scale.
    gamma = block["residual"]["main"]["rmsnorm"]["gamma"]
    assert gamma.dtype == jnp.float32
    assert np.allclose(np.asarray(gamma), family.OUTPUT_NORM_GAMMA)
    assert "gatedmlp" in tree["block"]["residual_1"]["main"]


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


def test_the_reference_multiplies_a_routed_row_by_its_own_expert(config,
                                                                 family):
    """Sorted rows through ``ragged_dot`` against every token through
    every held expert, masked: the same sum."""
    cfg = {**config, **config["rehearsal"]}
    w = family.layer_weights(jax.random.PRNGKey(1), ("window", "moe"), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    with jax.default_matmul_precision("highest"):
        got = family._routed(x, w, cfg, None)
        chosen, weights = family.route(x, w["router"], w["bias"], cfg)
        want = jnp.zeros_like(x)
        for e in range(8):
            y = family._swiglu(x, w["ewg"][e], w["ewu"][e], w["ewd"][e], None)
            want += y * jnp.sum(jnp.where(chosen == e, weights, 0.0),
                                axis=1, keepdims=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want).max()) > 1e-3


def test_the_new_cell_runs_through_the_harness_at_rehearsal_sizes():
    args = bench_run.parse([
        "--workload", CELL, "--seed", str(2 ** 31 + 33), "--seconds", "3",
        "--trace", "1", "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    assert cell.chips == 1 and cell.sizes == {"n_vocab": 512, "n_ctx": 128}
    full = cells.Cell(CELL).mix
    assert full["engine"] == {
        "max_batch": 64, "max_len": 8192, "paged": True, "ragged": True,
        "kv_dtype": "bf16", "page_size": 16, "num_pages": 8192,
        "prefill_chunk": 512}
    assert abs(full["rate_rps"] - 0.8 * full["knee_rps"]) < 1e-9
    assert full["prompt_len"] == {"median": 1024, "sigma": 1.0, "min": 64,
                                  "max": 7168}
    assert full["output_len"] == {"median": 128, "sigma": 0.7, "min": 16,
                                  "max": 512}
    assert full["check"]["requests"] == 8
    result = bench_run.run_cell(cell, args)
    line = bench_run.result_line(cell, args, result)
    assert line["correct"] is False and line["metrics"] == {}
    assert result["checks_ok"], result["rows"]
    assert result["failed"] == 0 and result["host"]["checked_tokens"] > 20
    layers = result["per_layer"]
    # 8 of 16 experts held at rehearsal sizes; no prefix is ever hit.
    assert 35 < layers["moe_held_share"]["value"] < 65
    assert layers["moe_tokens_per_touched_expert"]["value"] >= 1.0
    # Six window layers of at most 16 keys beside two full ones.
    assert 20 < layers["decode_window_keys_share"]["value"] < 75
    assert 0 < layers["prefill_keys_visited_share"]["value"] <= 100
    assert layers["serve_prefill_chunk_ms"]["value"] > 0
    assert layers["decode_pages_read_share"]["value"] == 100.0
    assert "serve_prefix_hit_share" not in layers
    assert "decode_step_mfu" not in layers      # no device trace here
    names = {m["name"] for m in cell.end_to_end}
    assert names == {"serve_tokens_per_s", "itl_p95_ms", "ttft_p95_ms",
                     "setup_s"}
    moves = {m["name"]: m["moves"] for m in cell.per_layer}
    assert moves["decode_window_keys_share"] == "itl_p95_ms"
    assert moves["prefill_keys_visited_share"] == "ttft_p95_ms"


# -- planted faults: what the cell's comparison must refuse --------------------


def cut_the_full_layers_to_the_window(run):
    """The full layers drop the keys behind ``sliding_window``: a chunk's
    queries see the band a window layer sees, and a decode step's page walk
    starts at the page that holds the window's first key."""
    from tpu_dist.models.hybrid import GroupedQueryAttention
    from tpu_dist.serve import kv_cache

    window = run.cfg["sliding_window"]
    sees, walked = GroupedQueryAttention.sees, kv_cache._walked_attention

    def banded(self, q_pos, k_pos):
        ok = sees(self, q_pos, k_pos)
        return ok & (q_pos[..., :, None] - k_pos[..., None, :] < window)

    def from_the_window_on(pool, layer, tables, q, n_keys):
        ps, width = pool["k"].shape[2], tables.shape[1]
        first = jnp.maximum(n_keys - window, 0) // ps       # pages skipped
        cols = jnp.minimum(first[:, None] + jnp.arange(width), width - 1)
        return walked(pool, layer, jnp.take_along_axis(tables, cols, axis=1),
                      q, n_keys - first * ps)

    GroupedQueryAttention.sees = banded
    kv_cache._walked_attention = from_the_window_on
    run._undo = lambda: (setattr(GroupedQueryAttention, "sees", sees),
                         setattr(kv_cache, "_walked_attention", walked))


def zero_the_held_experts_down_projection(run):
    """The expert layer answers with its shared expert alone."""
    from tpu_dist.parallel.routed_experts import RoutedExperts

    forward = RoutedExperts.forward

    def shared_only(self, params, x, valid=None):
        silent = {**params, "wd": params["wd"] * 0}
        return forward(self, silent, x, valid)

    RoutedExperts.forward = shared_only
    run._undo = lambda: setattr(RoutedExperts, "forward", forward)


FAULTS = {"full_layers_cut_to_the_window": cut_the_full_layers_to_the_window,
          "held_experts_wd_zeroed": zero_the_held_experts_down_projection}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_in_a_new_mechanism_is_not_correct(fault):
    args = bench_run.parse([
        "--workload", CELL, "--seed", str(2 ** 31 + 34), "--seconds", "3",
        "--trace", "0", "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    undo = []

    def sabotage(run):
        FAULTS[fault](run)
        undo.append(run._undo)

    try:
        result = bench_run.run_cell(cell, args, sabotage=sabotage)
    finally:
        for fn in undo:
            fn()
    assert not result["checks_ok"]
    assert [r["name"] for r in result["rows"] if not r["ok"]] == [
        "served_logit_gap"]


def test_the_serving_control_is_not_correct():
    args = bench_run.parse(["--workload", CELL, "--rehearse", "1"])
    cell = bench_run.load_cell(args)
    run = serve_cell.ServeRun(cell, 2 ** 31 + 35)
    run.build(3.0)
    run.warm_up()
    run.window(3.0)
    run.free_program()
    program = run.reference_numbers()
    control = run.reference_numbers(quant="fp8")
    limit = cell.mix["limits"]["served_logit_gap"]
    assert program["served_logit_gap"] <= limit
    assert control["served_logit_gap"] > limit
