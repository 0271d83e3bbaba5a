"""The harness as the driver meets it: no TPU means no result, the files
of BENCHMARK.json are all there, and a later PR adds a configuration, a
mix, a cell and a counter-backed metric with new files and entries only."""

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


@pytest.fixture()
def copy_with_additions(tmp_path):
    """A temporary checkout: BENCHMARK.json and tpubench/ copied, then a
    throw-away configuration, mix, cell and counter-backed metric ADDED as
    new files and new entries; nothing that was there is edited."""
    shutil.copytree(ROOT / "tpubench", tmp_path / "tpubench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    before = json.dumps(bench, sort_keys=True)
    tiny = cells.load_json(ROOT / "tpubench/configs/gpt2-medium.json")
    tiny["rehearsal"] = {**tiny["rehearsal"], "n_layer": 3}
    (tmp_path / "tpubench/configs/throwaway.json").write_text(
        json.dumps(tiny))
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
        "why": "a test's configuration"})
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
    assert json.dumps(cells.load_json(ROOT / "BENCHMARK.json"),
                      sort_keys=True) == before
    return tmp_path


def test_a_cell_is_added_by_files_and_entries_alone(copy_with_additions):
    root = copy_with_additions
    args = bench_run.parse([
        "--workload", "serve.throwaway.rush", "--seed", str(2 ** 31 + 9),
        "--seconds", "3", "--trace", "1", "--rehearse", "1",
        "--root", str(root)])
    cell = bench_run.load_cell(args)
    assert cell.config["n_layer"] == 3 and cell.mix["rate_rps"] == 12.0
    result = bench_run.run_cell(cell, args)
    line = bench_run.result_line(cell, args, result)
    # A rehearsal is never a result: not correct, and no device metric.
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert result["checks_ok"], result["rows"]
    layers = result["per_layer"]
    assert 0 < layers["decode_steps_per_token"]["value"] <= 1.0
    assert layers["serve_prefix_hit_share"]["value"] > 0
    # No trace of a device here: every device reader stays silent
    # rather than print a 0.
    assert "decode_step_mfu" not in layers
    assert "device_idle_share.serve" not in layers
    assert result["host"]["sent"] >= 30 and result["failed"] == 0
