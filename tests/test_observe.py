"""tpu_dist.observe tests: percentile math, straggler logic, exporter
round-trips, Telemetry fit integration, env arming, and the CLI contract
(a vacuous series must FAIL).

Quantile assertions are exact on known inputs; everything else asserts on
structure and counters, never on wall-clock values.
"""

import json
import pathlib

import numpy as np
import pytest

import tpu_dist as td
from tpu_dist.data import Dataset
from tpu_dist.models import Dense, Sequential
from tpu_dist.observe import exporters, metrics, straggler
from tpu_dist.observe.metrics import MetricsRegistry, quantile
from tpu_dist.observe.telemetry import (OBSERVE_DIR_ENV, StepTimer,
                                        Telemetry, active_step_timer,
                                        maybe_telemetry_from_env,
                                        registry_collective_hook)
from tpu_dist.ops import SGD, SparseCategoricalCrossentropy


def _model(lr=0.2):
    m = Sequential([Dense(16, activation="relu"), Dense(4)], input_shape=(8,))
    m.compile(loss=SparseCategoricalCrossentropy(from_logits=True),
              optimizer=SGD(learning_rate=lr))
    return m


def _ds(n=64, batch=32, seed=1):
    rng = np.random.default_rng(seed)
    y = rng.integers(4, size=n)
    x = (np.eye(8)[y * 2] + rng.normal(0, 0.1, (n, 8))).astype(np.float32)
    return Dataset.from_tensor_slices((x, y.astype(np.int64))).batch(batch)


class TestQuantileMath:
    def test_known_inputs_exact(self):
        # 1..100 under numpy's linear interpolation: h = (n-1)q.
        vals = [float(v) for v in range(1, 101)]
        assert quantile(vals, 0.5) == 50.5
        assert quantile(vals, 0.95) == pytest.approx(95.05)
        assert quantile(vals, 0.99) == pytest.approx(99.01)
        assert quantile(vals, 0.0) == 1.0
        assert quantile(vals, 1.0) == 100.0
        np.testing.assert_allclose(
            [quantile(vals, q) for q in (0.25, 0.75)],
            np.percentile(vals, [25, 75]))

    def test_single_value_and_errors(self):
        assert quantile([7.0], 0.99) == 7.0
        with pytest.raises(ValueError):
            quantile([], 0.5)
        with pytest.raises(ValueError):
            quantile([1.0], 1.5)

    def test_distribution_snapshot_quantiles(self):
        r = MetricsRegistry(enabled=True)
        d = r.distribution("t")
        for v in range(1, 101):
            d.observe(float(v))
        snap = d.snapshot()
        assert snap["count"] == 100 and snap["sum"] == 5050.0
        assert snap["min"] == 1.0 and snap["max"] == 100.0
        assert snap["p50"] == 50.5
        assert snap["p95"] == pytest.approx(95.05)
        assert snap["p99"] == pytest.approx(99.01)

    def test_reservoir_bounds_memory_keeps_exact_aggregates(self):
        r = MetricsRegistry(enabled=True, reservoir_size=64)
        d = r.distribution("t")
        for v in range(10_000):
            d.observe(float(v))
        assert d.count == 10_000 and len(d._reservoir) == 64
        snap = d.snapshot()
        assert snap["sum"] == sum(range(10_000))
        # The reservoir is a uniform sample: p50 lands mid-range.
        assert 1_000 < snap["p50"] < 9_000


class TestRegistry:
    def test_disabled_is_noop(self):
        r = MetricsRegistry(enabled=False)
        r.counter("c").inc(5)
        r.gauge("g").set(1.0)
        r.distribution("d").observe(3.0)
        snap = r.snapshot()
        assert snap["counters"]["c"] == 0
        assert snap["gauges"]["g"] is None
        assert snap["distributions"]["d"]["count"] == 0
        r.enable()
        r.counter("c").inc(5)
        assert r.counter("c").value == 5

    def test_instruments_are_singletons(self):
        r = MetricsRegistry(enabled=True)
        assert r.counter("x") is r.counter("x")
        r.counter("x").inc()
        r.reset()
        assert r.counter("x").value == 0

    def test_module_helpers_hit_default_registry(self):
        reg = metrics.get_registry()
        was = reg.enabled
        reg.enable()
        try:
            reg.reset()
            metrics.inc("helper.c", 2)
            metrics.set_gauge("helper.g", 4.0)
            metrics.observe_value("helper.d", 1.0)
            snap = reg.snapshot()
            assert snap["counters"]["helper.c"] == 2
            assert snap["gauges"]["helper.g"] == 4.0
            assert snap["distributions"]["helper.d"]["count"] == 1
        finally:
            reg.reset()
            if not was:
                reg.disable()


class TestStraggler:
    def test_flags_rank_above_median_multiple(self):
        verdicts = straggler.detect_stragglers([0.1, 0.1, 0.35, 0.1])
        assert [v.rank for v in verdicts] == [2]
        v = verdicts[0]
        assert v.step_s == 0.35 and v.median_s == pytest.approx(0.1)
        assert v.ratio == pytest.approx(3.5)
        assert set(v.to_dict()) == {"rank", "step_s", "median_s", "ratio"}

    def test_uniform_gang_is_clean(self):
        assert straggler.detect_stragglers([0.1] * 8) == []

    def test_single_rank_never_flags(self):
        assert straggler.detect_stragglers([5.0]) == []

    def test_tiny_steps_below_floor_are_ignored(self):
        # Median below min_step_s: ratios over noise-floor steps are
        # meaningless, never flag.
        assert straggler.detect_stragglers([1e-6, 1e-6, 1e-5]) == []

    def test_threshold_must_exceed_one(self):
        with pytest.raises(ValueError):
            straggler.detect_stragglers([0.1, 0.2], threshold=1.0)

    def test_heartbeat_monitor_staleness(self):
        clock = [100.0]
        mon = straggler.HeartbeatMonitor(3, clock=lambda: clock[0])
        mon.beat(0)
        clock[0] = 105.0
        mon.beat(1)
        clock[0] = 109.0
        # rank 0 beat 9s ago, rank 1 4s ago, rank 2 never (9s since ctor).
        assert mon.stale_ranks(5.0) == [0, 2]
        assert mon.stale_ranks(20.0) == []


class TestExporters:
    def test_jsonl_round_trip(self, tmp_path):
        r = MetricsRegistry(enabled=True)
        r.counter("step.count").inc(3)
        path = tmp_path / "m.jsonl"
        with exporters.JsonlExporter(path) as ex:
            ex.write(r.snapshot(), kind="epoch", epoch=0)
            ex.write(r.snapshot(), kind="final")
        recs = exporters.read_series(path)
        assert len(recs) == 2
        assert all(rec["schema"] == exporters.SCHEMA for rec in recs)
        assert recs[0]["epoch"] == 0 and recs[1]["kind"] == "final"
        assert recs[1]["metrics"]["counters"]["step.count"] == 3

    def test_read_series_rejects_wrong_schema(self, tmp_path):
        path = tmp_path / "m.jsonl"
        path.write_text(json.dumps({"schema": "someone/else", "metrics": {}})
                        + "\n")
        with pytest.raises(exporters.SchemaError):
            exporters.read_series(path)

    def test_read_series_torn_line(self, tmp_path):
        path = tmp_path / "m.jsonl"
        good = json.dumps({"schema": exporters.SCHEMA, "metrics": {}})
        path.write_text(good + "\n" + good[: len(good) // 2])
        assert len(exporters.read_series(path)) == 1  # torn tail skipped
        with pytest.raises(json.JSONDecodeError):
            exporters.read_series(path, strict=True)

    def test_prometheus_textfile(self, tmp_path):
        r = MetricsRegistry(enabled=True)
        r.counter("step.count").inc(7)
        r.gauge("epoch.steps_per_s").set(12.5)
        d = r.distribution("step.total_s")
        for v in (0.1, 0.2, 0.3):
            d.observe(v)
        path = tmp_path / "m.prom"
        exporters.write_prometheus_textfile(r.snapshot(), path)
        text = path.read_text()
        assert "# TYPE tpu_dist_step_count counter" in text
        assert "tpu_dist_step_count 7" in text
        assert "tpu_dist_epoch_steps_per_s 12.5" in text
        assert 'tpu_dist_step_total_s{quantile="0.5"} 0.2' in text
        assert "tpu_dist_step_total_s_count 3" in text
        # Every registry snapshot quantile gets a summary label — derived
        # from SNAPSHOT_QUANTILES, not a second hardcoded list (and the
        # flattened pNN keys stay the JSONL schema, untouched).
        for q in metrics.SNAPSHOT_QUANTILES:
            assert f'tpu_dist_step_total_s{{quantile="{q}"}}' in text
        assert "# TYPE tpu_dist_step_total_s summary" in text
        # Atomic write: no leftover tmp file.
        assert list(tmp_path.glob("*.tmp*")) == []


class TestTelemetryCallback:
    def test_fit_records_steps_and_collectives(self, eight_devices,
                                               tmp_path):
        reg = MetricsRegistry(enabled=False)
        cb = Telemetry(jsonl_path=tmp_path / "m.jsonl",
                       prometheus_path=tmp_path / "m.prom", registry=reg)
        _model().fit(_ds(), epochs=2, verbose=0, callbacks=[cb])
        snap = reg.snapshot()
        assert snap["counters"]["step.count"] == 4  # 2 epochs x 2 batches
        assert snap["distributions"]["step.total_s"]["count"] > 0
        assert snap["distributions"]["step.data_wait_s"]["count"] > 0
        # The per-epoch cross-rank exchange guarantees collective traffic
        # even single-process.
        assert snap["counters"]["collective.host_all_gather.calls"] >= 2
        assert snap["gauges"]["rank0.step_time_s"] > 0
        assert snap["gauges"]["epoch.steps_per_s"] > 0
        # Series on disk: epoch records plus a final one, schema-valid.
        recs = exporters.read_series(tmp_path / "m.jsonl")
        assert [r["kind"] for r in recs] == ["epoch", "epoch", "final"]
        assert (tmp_path / "m.prom").exists()

    def test_fit_restores_hook_timer_and_enabled_state(self, eight_devices):
        from tpu_dist.parallel import collectives

        reg = MetricsRegistry(enabled=False)
        before_hook = collectives._OBSERVE_HOOK
        _model().fit(_ds(), epochs=1, verbose=0,
                     callbacks=[Telemetry(registry=reg)])
        assert collectives._OBSERVE_HOOK is before_hook
        assert active_step_timer() is None
        assert reg.enabled is False  # was disabled before the span

    def test_collective_hook_counts_bytes_and_phases(self):
        reg = MetricsRegistry(enabled=True)
        hook = registry_collective_hook(reg)
        hook("all_reduce", phase="trace", leaves=1, nbytes=64)
        hook("all_reduce", phase="eager", leaves=1, nbytes=64, seconds=0.01)
        snap = reg.snapshot()
        assert snap["counters"]["collective.all_reduce.calls"] == 2
        assert snap["counters"]["collective.all_reduce.trace_calls"] == 1
        assert snap["counters"]["collective.all_reduce.bytes"] == 128
        assert snap["distributions"][
            "collective.all_reduce.host_seconds"]["count"] == 1

    def test_step_timer_divides_by_steps(self):
        reg = MetricsRegistry(enabled=True)
        timer = StepTimer(reg)
        timer.record_execution(steps=4, data_wait_s=0.4, dispatch_s=0.8,
                               device_block_s=1.2)
        # The step's wall time is the epoch's over its steps: the host
        # cannot see it per execution on a free-running device.
        timer.record_epoch(wall_s=2.4, loss_wait_s=0.4)
        snap = reg.snapshot()
        assert snap["counters"]["step.count"] == 4
        assert snap["distributions"]["step.total_s"]["p50"] == pytest.approx(
            0.6)
        assert snap["distributions"]["step.data_wait_s"][
            "p50"] == pytest.approx(0.1)
        assert timer.epoch_mean_step_s() == pytest.approx(0.6)

    def test_env_armed_telemetry_and_events(self, eight_devices, tmp_path,
                                            monkeypatch):
        from tpu_dist.resilience import events

        monkeypatch.setenv(OBSERVE_DIR_ENV, str(tmp_path / "obs"))
        monkeypatch.setenv(events.EVENT_LOG_ENV,
                           str(tmp_path / "events.jsonl"))
        assert maybe_telemetry_from_env() is not None
        _model().fit(_ds(), epochs=2, verbose=0)  # no explicit callback
        recs = exporters.read_series(tmp_path / "obs" / "metrics.jsonl")
        assert recs and recs[-1]["kind"] == "final"
        timing = events.read_events(tmp_path / "events.jsonl", "step_timing")
        assert len(timing) == 2
        assert all(t["steps"] == 2 for t in timing)

    def test_env_unset_means_no_telemetry(self, monkeypatch):
        monkeypatch.delenv(OBSERVE_DIR_ENV, raising=False)
        assert maybe_telemetry_from_env() is None


class TestCli:
    def test_demo_writes_valid_series(self, eight_devices, tmp_path, capsys):
        from tpu_dist.observe.cli import main

        rc = main(["demo", "--epochs", "2", "--steps-per-epoch", "2",
                   "--batch", "8", "--out", str(tmp_path)])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["ok"] and payload["missing"] == []
        assert payload["summary"]["steps"] == 4
        assert payload["summary"]["collective_calls"]  # non-vacuous
        assert pathlib.Path(payload["metrics_path"]).exists()
        assert pathlib.Path(payload["prometheus_path"]).exists()

    def test_summarize_requires_fail_on_step_free_series(self, tmp_path,
                                                         capsys):
        from tpu_dist.observe.cli import main

        # A schema-valid series with NO step metrics: summarize succeeds
        # plain but FAILS under --require step (vacuous pass convention).
        r = MetricsRegistry(enabled=True)
        r.counter("collective.all_reduce.calls").inc()
        path = tmp_path / "m.jsonl"
        with exporters.JsonlExporter(path) as ex:
            ex.write(r.snapshot(), kind="final")
        assert main(["summarize", str(path)]) == 0
        capsys.readouterr()
        assert main(["summarize", str(path), "--require", "step"]) == 1
        assert main(["summarize", str(path), "--require", "collective"]) == 0

    def test_summarize_empty_series_fails(self, tmp_path):
        from tpu_dist.observe.cli import main

        path = tmp_path / "empty.jsonl"
        path.write_text("")
        assert main(["summarize", str(path)]) == 1

    def test_summarize_missing_file_fails(self, tmp_path):
        from tpu_dist.observe.cli import main

        assert main(["summarize", str(tmp_path / "nope.jsonl")]) == 1

    def test_diff_flags_regression(self, tmp_path, capsys):
        from tpu_dist.observe.cli import main

        def series(path, steps_per_s):
            r = MetricsRegistry(enabled=True)
            r.counter("step.count").inc(4)
            r.gauge("epoch.steps_per_s").set(steps_per_s)
            with exporters.JsonlExporter(path) as ex:
                ex.write(r.snapshot(), kind="final")

        series(tmp_path / "base.jsonl", 100.0)
        series(tmp_path / "slow.jsonl", 50.0)
        assert main(["diff", str(tmp_path / "base.jsonl"),
                     str(tmp_path / "base.jsonl")]) == 0
        capsys.readouterr()
        rc = main(["diff", str(tmp_path / "base.jsonl"),
                   str(tmp_path / "slow.jsonl"), "--max-regress-pct", "20"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["steps_per_s_regress_pct"] == pytest.approx(50.0)
        assert payload["regressions"]
