"""shardcheck (tpu_dist.analysis) tests: every advertised rule over the
known-bad/known-good fixture programs, CLI exit-code contract, suppression
syntax, and the dogfooded self-check over the repo itself.

Assertions are on rule IDs, never message text — messages may be reworded
freely without breaking these tests.
"""

import json
import pathlib
import subprocess
import sys

import pytest

from tpu_dist.analysis import RULES, lint_file
from tpu_dist.analysis.cli import cost_main, main as shardcheck_main
from tpu_dist.analysis.report import exit_code
from tpu_dist.analysis.rules import Severity

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "shardcheck"
BAD = FIXTURES / "bad"
GOOD = FIXTURES / "good"
COST = FIXTURES / "cost"
BASELINES = FIXTURES / "baselines"
PKG = pathlib.Path(__file__).resolve().parents[1] / "tpu_dist"
REPO = PKG.parent

#: cost_main argv prefix that prices ONLY the hand-computable cost fixture
#: (skipping the eight built-in entry-point traces).
COST_FIXTURE_ARGS = [str(COST), "--entries", "module:cost_entry"]

#: AST-pass fixtures: file -> exactly the rule IDs it must trip.
BAD_AST = {
    "wrong_axis_name.py": {"SC101"},
    "rank_mismatch_spec.py": {"SC102"},
    "side_effect_in_jit.py": {"SC103"},
    "metrics_in_jit.py": {"SC103"},
    "donated_reuse.py": {"SC104"},
    "swallowed_liveness.py": {"SC105"},
}
GOOD_AST = ["declared_axis.py", "matching_spec.py", "pure_jit.py",
            "metrics_in_callback.py", "donate_rebind.py",
            "reraised_liveness.py"]

#: Concurrency/liveness fixtures (``--concurrency`` mode): file -> exactly
#: the rule IDs it must trip. Per-rule assertions live in
#: test_shardcheck_concurrency.py; this map feeds the advertised-rule
#: coverage sweep below.
BAD_CONCURRENCY = {
    "thread_unlocked_write.py": {"SC401"},
    "blocking_join_under_lock.py": {"SC402"},
    "collective_on_thread.py": {"SC403"},
    "exit_under_lock.py": {"SC404"},
    "rank_divergent_barrier.py": {"SC501"},
    "unbounded_wait.py": {"SC502"},
    "torn_protocol_write.py": {"SC503"},
    "stale_suppression.py": {"SC901"},
}

#: Determinism fixtures (``--determinism`` mode): file -> exactly the
#: rule IDs it must trip. Per-rule assertions live in
#: test_shardcheck_determinism.py; this map feeds the advertised-rule
#: coverage sweep below. SC610 is jaxpr-level and flags from the cost
#: fixture vs baselines/rng_free.json instead.
BAD_DETERMINISM = {
    "nondet_seed_taint.py": {"SC601"},
    "rng_key_reuse.py": {"SC602"},
    "unsorted_scan_order.py": {"SC603"},
    "fold_constant_collision.py": {"SC604"},
    "unordered_float_sum.py": {"SC605"},
}


def _cli_json(capsys, argv):
    """Run the CLI in-process with --json; return (exit_code, payload)."""
    rc = shardcheck_main(argv + ["--json"])
    payload = json.loads(capsys.readouterr().out)
    return rc, payload


def _rule_ids(payload):
    return {f["rule_id"] for f in payload["findings"]}


class TestAstRules:
    @pytest.mark.parametrize("name,expected", sorted(BAD_AST.items()))
    def test_bad_fixture_flags_exactly_its_rule(self, name, expected):
        findings = lint_file(str(BAD / name))
        assert {f.rule_id for f in findings} == expected

    @pytest.mark.parametrize("name", GOOD_AST)
    def test_good_fixture_is_clean(self, name):
        assert lint_file(str(GOOD / name)) == []

    def test_suppression_comment_silences_rule(self, tmp_path):
        f = tmp_path / "suppressed.py"
        f.write_text(
            "import jax\n"
            "def bad(x):\n"
            "    return jax.lax.psum(x, 'nope')"
            "  # shardcheck: disable=SC101 -- test axis, mesh built elsewhere\n")
        assert lint_file(str(f)) == []
        # Without the pragma the same program is flagged.
        g = tmp_path / "unsuppressed.py"
        g.write_text(
            "import jax\n"
            "def bad(x):\n"
            "    return jax.lax.psum(x, 'nope')\n")
        assert {x.rule_id for x in lint_file(str(g))} == {"SC101"}

    def test_unparseable_file_degrades_to_sc900(self, tmp_path):
        f = tmp_path / "broken.py"
        f.write_text("def oops(:\n")
        findings = lint_file(str(f))
        assert [x.rule_id for x in findings] == ["SC900"]
        assert findings[0].severity == Severity.INFO
        # Info findings pass the default gate but fail --fail-on info.
        assert exit_code(findings, fail_on="error") == 0
        assert exit_code(findings, fail_on="info") == 1


class TestJaxprRules:
    def test_branch_collective_fixture_flags_sc201(self, capsys,
                                                   eight_devices):
        rc, payload = _cli_json(
            capsys, [str(BAD / "branch_collective.py")])
        assert rc == 1
        assert "SC201" in _rule_ids(payload)

    def test_bucket_order_divergent_fixture_flags_sc201(self, capsys,
                                                        eight_devices):
        # Rank-dependent bucket packing = rank-dependent launch counts.
        rc, payload = _cli_json(
            capsys, [str(BAD / "bucket_order_divergent.py")])
        assert rc == 1
        assert "SC201" in _rule_ids(payload)

    def test_uniform_branches_fixture_is_clean(self, capsys, eight_devices):
        rc, payload = _cli_json(
            capsys, [str(GOOD / "uniform_branches.py")])
        assert rc == 0
        assert payload["findings"] == []

    def test_while_collective_fixture_flags_sc202(self, capsys,
                                                  eight_devices):
        rc, payload = _cli_json(capsys, [str(BAD / "while_collective.py")])
        assert rc == 1
        assert "SC202" in _rule_ids(payload)

    def test_scan_collective_fixture_is_clean(self, capsys, eight_devices):
        rc, payload = _cli_json(capsys, [str(GOOD / "scan_collective.py")])
        assert rc == 0
        assert payload["findings"] == []

    def test_branch_payload_mismatch_flags_sc203_not_sc201(
            self, capsys, eight_devices):
        rc, payload = _cli_json(
            capsys, [str(BAD / "branch_payload_mismatch.py")])
        assert rc == 1
        ids = _rule_ids(payload)
        assert "SC203" in ids
        # Same collective ORDER in both branches: SC201 must stay quiet —
        # the payload mismatch is the whole finding.
        assert "SC201" not in ids

    def test_invalid_permute_flags_sc203(self, capsys, eight_devices):
        rc, payload = _cli_json(capsys, [str(BAD / "invalid_permute.py")])
        assert rc == 1
        assert "SC203" in _rule_ids(payload)

    def test_ring_permute_fixture_is_clean(self, capsys, eight_devices):
        rc, payload = _cli_json(capsys, [str(GOOD / "ring_permute.py")])
        assert rc == 0
        assert payload["findings"] == []

    def test_undonated_large_arg_warns_sc303(self, capsys, eight_devices):
        # SC303 is a warning: reported, default gate passes, --strict fails.
        rc, payload = _cli_json(
            capsys, [str(BAD / "undonated_large_arg.py")])
        assert rc == 0
        assert "SC303" in _rule_ids(payload)
        rc = shardcheck_main(
            [str(BAD / "undonated_large_arg.py"), "--strict"])
        capsys.readouterr()
        assert rc == 1

    def test_donated_large_arg_fixture_is_clean(self, capsys,
                                                eight_devices):
        # The 3-tuple (fn, args, donate_argnums) entry protocol clears it.
        rc, payload = _cli_json(
            capsys, [str(GOOD / "donated_large_arg.py"), "--strict"])
        assert rc == 0
        assert payload["findings"] == []

    def test_untraceable_entry_names_exception_class(self, capsys,
                                                     tmp_path):
        f = tmp_path / "explodes.py"
        f.write_text(
            "def shardcheck_entry():\n"
            "    raise ValueError('boom\\nwith a second line')\n")
        rc, payload = _cli_json(capsys, [str(f)])
        assert rc == 0
        (finding,) = payload["findings"]
        assert finding["rule_id"] == "SC900"
        assert "ValueError: boom" in finding["message"]
        assert "second line" not in finding["message"]  # one-line cause


class TestCliContract:
    @pytest.mark.parametrize("name", sorted(BAD_AST))
    def test_bad_fixture_exits_nonzero(self, capsys, name):
        rc, payload = _cli_json(capsys, [str(BAD / name), "--no-trace"])
        assert rc == 1
        assert payload["exit_code"] == 1

    def test_good_dir_exits_zero_without_trace(self, capsys):
        rc, payload = _cli_json(capsys, [str(GOOD), "--no-trace"])
        assert rc == 0
        assert payload["findings"] == []

    def test_fail_on_never_reports_but_passes(self, capsys):
        rc, payload = _cli_json(
            capsys, [str(BAD / "wrong_axis_name.py"), "--no-trace",
                     "--fail-on", "never"])
        assert rc == 0
        assert "SC101" in _rule_ids(payload)

    def test_json_payload_shape(self, capsys):
        rc, payload = _cli_json(
            capsys, [str(BAD / "donated_reuse.py"), "--no-trace"])
        assert payload["tool"] == "shardcheck"
        assert set(payload["counts"]) == {"info", "warning", "error"}
        finding = payload["findings"][0]
        assert {"rule_id", "severity", "path", "line", "col",
                "message"} <= set(finding)

    def test_github_format_emits_workflow_annotations(self, capsys):
        rc = shardcheck_main(
            [str(BAD / "wrong_axis_name.py"), "--no-trace",
             "--format", "github"])
        out = capsys.readouterr().out
        assert rc == 1
        line = next(l for l in out.splitlines() if l.startswith("::"))
        assert line.startswith("::error file=")
        assert ",line=" in line and "::[SC101]" in line.split("file=")[1]

    def test_every_advertised_rule_has_flagging_and_clean_coverage(
            self, capsys, eight_devices):
        advertised = set(RULES)
        flagged = set()
        for name in BAD_AST:
            flagged |= {f.rule_id for f in lint_file(str(BAD / name))}
        for name in ("branch_collective.py", "while_collective.py",
                     "branch_payload_mismatch.py",
                     "undonated_large_arg.py"):
            _, payload = _cli_json(capsys, [str(BAD / name)])
            flagged |= _rule_ids(payload)
        # SC301/SC302 flag from the cost fixture vs the bad baselines.
        for baseline in ("cost_regressed.json", "cost_low_hbm.json"):
            rc = cost_main(COST_FIXTURE_ARGS + [
                "--baseline", str(BASELINES / baseline), "--json"])
            flagged |= _rule_ids(json.loads(capsys.readouterr().out))
        # SC4xx/SC5xx/SC901 flag from the concurrency fixture set.
        for name in BAD_CONCURRENCY:
            _, payload = _cli_json(
                capsys, [str(BAD / name), "--concurrency"])
            flagged |= _rule_ids(payload)
        # SC6xx flag from the determinism fixture set...
        for name in BAD_DETERMINISM:
            _, payload = _cli_json(
                capsys, [str(BAD / name), "--determinism"])
            flagged |= _rule_ids(payload)
        # ...except jaxpr-level SC610: the RNG-consuming cost fixture vs
        # the baseline that records it RNG-free.
        rc = cost_main([str(COST), "--entries", "module:rng_entry",
                        "--baseline", str(BASELINES / "rng_free.json"),
                        "--json"])
        flagged |= _rule_ids(json.loads(capsys.readouterr().out))
        assert rc == 1
        # SC900 is the degradation rule; its flagging fixture is synthetic
        # (test_unparseable_file_degrades_to_sc900) to keep bad/ all-error.
        assert advertised - {"SC900"} <= flagged
        # Every good fixture is clean of every rule, trace pass included
        # (--strict so warnings would fail too).
        rc, payload = _cli_json(capsys, [str(GOOD), "--strict"])
        assert rc == 0
        assert payload["findings"] == []
        rc, payload = _cli_json(capsys, [str(GOOD), "--concurrency",
                                         "--strict"])
        assert rc == 0
        assert payload["findings"] == []
        rc, payload = _cli_json(capsys, [str(GOOD), "--determinism",
                                         "--strict"])
        assert rc == 0
        assert payload["findings"] == []
        rc = cost_main(COST_FIXTURE_ARGS + [
            "--baseline", str(BASELINES / "cost_good.json"), "--strict"])
        capsys.readouterr()
        assert rc == 0
        # The rng_recorded baseline matches the fixture's actual RNG set.
        rc = cost_main([str(COST), "--entries", "module:rng_entry",
                        "--baseline", str(BASELINES / "rng_recorded.json"),
                        "--strict"])
        capsys.readouterr()
        assert rc == 0


class TestCostModel:
    """Exact byte counts on hand-computable toy jaxprs. Mesh data=4, the
    f32[8, 4] input sharded over data -> per-shard payload f32[2, 4] =
    32 B; the ring formulas give psum 2*(3/4)*32 = 48, all_gather
    (4-1)*32 = 96, ppermute 32."""

    def _toy_jaxpr(self, body, n_in=1):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(jax.devices()[:4], ("data",))
        kw = dict(mesh=mesh, in_specs=(P("data"),) * n_in,
                  out_specs=P("data"))
        mapped = jax.shard_map(body, check_vma=False, **kw)
        return jax.make_jaxpr(mapped)(
            *(jnp.ones((8, 4)) for _ in range(n_in)))

    def test_ring_formulas(self):
        from tpu_dist.analysis import comm_bytes

        assert comm_bytes("psum", 32, 4) == 48       # 2*(P-1)/P
        assert comm_bytes("all_gather", 32, 4) == 96  # (P-1) per shard
        assert comm_bytes("all_to_all", 32, 4) == 24  # (P-1)/P
        assert comm_bytes("reduce_scatter", 32, 4) == 24
        assert comm_bytes("ppermute", 32, 4) == 32    # one neighbor send
        assert comm_bytes("psum", 32, 1) == 0         # P=1: nothing moves
        # Replication-type casts are not communication.
        assert comm_bytes("pbroadcast", 32, 4) == 0
        assert comm_bytes("pvary", 32, 4) == 0

    def test_collective_bytes_exact(self, eight_devices):
        import jax

        from tpu_dist.analysis import analyze_jaxpr

        def body(x):
            s = jax.lax.psum(x, "data")
            g = jax.lax.all_gather(x, "data")
            p = jax.lax.ppermute(
                x, "data", [(i, (i + 1) % 4) for i in range(4)])
            return s + g.sum(axis=0) + p

        report = analyze_jaxpr(self._toy_jaxpr(body), entry="toy")
        by_op = {c.op.split("_invariant")[0]: c.bytes
                 for c in report.collectives}
        assert by_op["psum"] == 48
        assert by_op["all_gather"] == 96
        assert by_op["ppermute"] == 32
        assert report.total_comm_bytes == 176

    def test_model_mesh_overrides_participant_count(self, eight_devices):
        import jax

        from tpu_dist.analysis import analyze_jaxpr

        def body(x):
            s = jax.lax.psum(x, "data")
            g = jax.lax.all_gather(x, "data")
            p = jax.lax.ppermute(
                x, "data", [(i, (i + 1) % 4) for i in range(4)])
            return s + g.sum(axis=0) + p

        # Same trace repriced at data=8: payload shapes stay as traced
        # (32 B shards), only P in the ring arithmetic changes.
        report = analyze_jaxpr(self._toy_jaxpr(body), entry="toy",
                               model_mesh={"data": 8})
        assert report.total_comm_bytes == 56 + 224 + 32  # 312

    def test_scan_multiplies_launch_count(self, eight_devices):
        import jax

        from tpu_dist.analysis import analyze_jaxpr

        ring = [(i, (i + 1) % 4) for i in range(4)]

        def body(x):
            def step(c, _):
                return jax.lax.ppermute(c, "data", ring), None

            y, _ = jax.lax.scan(step, x, None, length=3)
            return y

        report = analyze_jaxpr(self._toy_jaxpr(body), entry="toy")
        (perm,) = report.collectives
        assert perm.multiplier == 3
        assert perm.bytes == 3 * 32
        assert report.total_comm_bytes == 96

    def test_peak_live_bytes_linear_chain(self):
        import jax
        import jax.numpy as jnp

        from tpu_dist.analysis import peak_live_bytes

        def f(x):
            y = x * 2.0
            z = y + 1.0
            return z

        # f32[1024] = 4096 B; x dies as y is born, y dies as z is born:
        # at most two 4096 B values live at once.
        closed = jax.make_jaxpr(f)(jnp.ones((1024,), jnp.float32))
        assert peak_live_bytes(closed) == 8192

    def test_parse_mesh(self):
        from tpu_dist.analysis import parse_mesh

        assert parse_mesh("data=8,model=4") == {"data": 8, "model": 4}
        with pytest.raises(ValueError):
            parse_mesh("data")
        with pytest.raises(ValueError):
            parse_mesh("data=0")


class TestCostCli:
    def test_cost_json_payload_shape_and_fixture_bytes(self, capsys,
                                                       eight_devices):
        rc = cost_main(COST_FIXTURE_ARGS + ["--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["tool"] == "shardcheck-cost"
        entry = payload["entries"]["module:cost_entry"]
        # The hand-computed number the committed baselines encode.
        assert entry["total_comm_bytes"] == 32
        assert entry["peak_hbm_bytes"] > 0
        (coll,) = entry["collectives"]
        assert {"op", "axes", "axis_size", "payload_bytes", "multiplier",
                "bytes", "shape", "dtype"} <= set(coll)

    def test_baseline_regression_fails_with_sc301(self, capsys,
                                                  eight_devices):
        rc = cost_main(COST_FIXTURE_ARGS + [
            "--baseline", str(BASELINES / "cost_regressed.json"), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert "SC301" in _rule_ids(payload)

    def test_hbm_over_budget_warns_sc302(self, capsys, eight_devices):
        rc = cost_main(COST_FIXTURE_ARGS + [
            "--baseline", str(BASELINES / "cost_low_hbm.json"), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0  # warning: reported, default gate passes
        assert "SC302" in _rule_ids(payload)
        rc = cost_main(COST_FIXTURE_ARGS + [
            "--baseline", str(BASELINES / "cost_low_hbm.json"),
            "--strict"])
        capsys.readouterr()
        assert rc == 1

    def test_update_baseline_then_injected_regression_fails(
            self, capsys, tmp_path, eight_devices):
        base = tmp_path / "baseline.json"
        rc = cost_main(COST_FIXTURE_ARGS + [
            "--update-baseline", "--baseline", str(base)])
        capsys.readouterr()
        assert rc == 0 and base.exists()
        # Freshly committed baseline gates clean...
        rc = cost_main(COST_FIXTURE_ARGS + ["--baseline", str(base)])
        capsys.readouterr()
        assert rc == 0
        # ...then a 2x comm regression (baseline halved, same program)
        # fails the same invocation check.sh runs.
        data = json.loads(base.read_text())
        data["entries"]["module:cost_entry"]["total_comm_bytes"] //= 2
        base.write_text(json.dumps(data))
        rc = cost_main(COST_FIXTURE_ARGS + [
            "--baseline", str(base), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert "SC301" in _rule_ids(payload)

    def test_tolerance_flag_overrides_baseline(self, capsys,
                                               eight_devices):
        # 32 vs baseline 10 is a 220% jump: passes at --tolerance 250.
        rc = cost_main(COST_FIXTURE_ARGS + [
            "--baseline", str(BASELINES / "cost_regressed.json"),
            "--tolerance", "250"])
        capsys.readouterr()
        assert rc == 0

    def test_unknown_entry_name_is_an_error(self, capsys):
        with pytest.raises(SystemExit):
            cost_main(["--entries", "no.such.entry"])
        capsys.readouterr()


class TestDogfood:
    def test_repo_lints_clean(self):
        findings = [f for p in (PKG,)
                    for f in lint_file(str(p))] if PKG.is_file() else None
        # Directory lint via the public API, error severity must be absent.
        from tpu_dist.analysis import lint_paths

        findings = lint_paths([str(PKG)])
        errors = [f for f in findings if f.severity >= Severity.ERROR]
        assert errors == [], [f.render() for f in errors]

    # ~12s of fresh-interpreter entry-point tracing; check.sh's shardcheck
    # stage runs the identical CLI over tpu_dist/ + examples/, so the
    # pytest copy rides outside tier-1 (test_repo_lints_clean keeps the
    # in-process lint coverage).
    @pytest.mark.slow
    def test_cli_self_check_exits_zero(self):
        # The acceptance-criterion invocation, end to end in a fresh
        # interpreter: AST lint + built-in entry-point traces over the
        # installed package, warnings fatal.
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_dist.analysis", str(PKG),
             "--strict"],
            capture_output=True, text=True, timeout=600,
            cwd=str(PKG.parent))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_parallel_family_steps_are_registered_entry_points(self):
        # The ROADMAP satellite: TP, SP and MoE steps are traced alongside
        # the trainer/pipeline/resilience/observe entries.
        from tpu_dist.analysis.jaxpr_checks import ENTRY_POINTS

        assert {"parallel.tensor.megatron_block",
                "parallel.sequence.ring_attention",
                "parallel.expert.moe_layer",
                "pipeline_parallel.gpipe_schedule",
                "pipeline_1f1b.one_f_one_b",
                "training.trainer.train_step"} <= set(ENTRY_POINTS)

    def test_baseline_and_entry_registry_are_one_to_one(self):
        # The ROADMAP maintenance rule ("register every new traced entry
        # point and re-run cost --update-baseline"), machine-enforced:
        # jaxpr_checks.ENTRY_POINTS and ANALYSIS_BASELINE.json must agree
        # exactly, both directions, names and count — and the SC610 rng
        # section must cover the same names, so every entry point has a
        # committed RNG-consumption contract.
        from tpu_dist.analysis.jaxpr_checks import ENTRY_POINTS

        baseline = json.loads((REPO / "ANALYSIS_BASELINE.json").read_text())
        registered = set(ENTRY_POINTS)
        committed = set(baseline["entries"])
        assert registered - committed == set(), (
            "entry points missing from ANALYSIS_BASELINE.json — run "
            "`python -m tpu_dist.analysis cost --update-baseline` and "
            "commit the diff")
        assert committed - registered == set(), (
            "stale ANALYSIS_BASELINE.json entries for unregistered entry "
            "points — run `python -m tpu_dist.analysis cost "
            "--update-baseline` and commit the diff")
        assert len(ENTRY_POINTS) == len(baseline["entries"])
        rng = baseline.get("rng")
        assert rng is not None, (
            "ANALYSIS_BASELINE.json has no 'rng' section — the SC610 "
            "determinism gate has nothing to diff against")
        assert set(rng) == committed

    def test_cost_matches_committed_baseline(self, capsys, eight_devices):
        # Acceptance criterion: every registered entry point's modeled
        # cost is within tolerance of the committed ANALYSIS_BASELINE.json
        # (exactly the check.sh analysis-cost stage, in-process).
        baseline = REPO / "ANALYSIS_BASELINE.json"
        assert baseline.exists(), "commit ANALYSIS_BASELINE.json"
        rc = cost_main(["--baseline", str(baseline), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0, payload["findings"]
        errors = [f for f in payload["findings"]
                  if f["severity"] != "info"]
        assert errors == []
        assert set(payload["entries"]) == set(json.loads(
            baseline.read_text())["entries"])
