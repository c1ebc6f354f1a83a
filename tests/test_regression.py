"""Perf-regression sentinel (obs/regression.py, ISSUE 16): the offline
bench-trajectory gate (loader, direction inference, noise-aware
tolerances, CLI exit codes) and the live CUSUM sentinel (deterministic
fold, rising-edge telemetry, FleetHealth degradation, seeded chaos
acceptance)."""

import json
import os

import pytest

from mmlspark_tpu.obs.export import SpanCollector
from mmlspark_tpu.obs.fleet import FleetAggregator, FleetHealth
from mmlspark_tpu.obs.metrics import MetricsRegistry
from mmlspark_tpu.obs.regression import (CusumDetector, RegressionSentinel,
                                         SeriesWatch, compare_benches,
                                         direction, format_table,
                                         gate_verdict, history_from_files,
                                         load_bench, main)
from mmlspark_tpu.obs.timeseries import TimeSeriesStore



def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


# ------------------------------------------------------------- loader

class TestLoadBench:
    def test_flat_dict(self, tmp_path):
        p = _write(tmp_path, "b.json",
                   {"train_images_per_sec": 120.0, "p99_ms": 4.5,
                    "ok": True})
        got = load_bench(p)
        assert got == {"train_images_per_sec": 120.0, "p99_ms": 4.5}

    def test_banker_wrapper_nested_parsed(self, tmp_path):
        doc = {"n": 3, "cmd": "bench", "rc": 0, "tail": "",
               "parsed": {"metric": "train_images_per_sec",
                          "value": 120.0, "unit": "img/s",
                          "vs_baseline": 1.02,
                          "extras": {"serving_p99_ms": 4.5}}}
        got = load_bench(_write(tmp_path, "b.json", doc))
        assert got["train_images_per_sec"] == 120.0
        assert got["serving_p99_ms"] == 4.5
        assert "vs_baseline" not in got and "n" not in got

    def test_truncated_tail_regex_harvest(self, tmp_path):
        # the banked tail is the LAST 2000 chars: the metrics JSON line
        # routinely loses its opening brace, so only the regex sweep
        # still reads it
        tail = ('_per_sec": 99.0, "serving_p99_ms": 4.25, '
                '"last_measured_mfu": 0.41}')
        doc = {"n": 1, "rc": 0, "tail": tail, "parsed": None}
        got = load_bench(_write(tmp_path, "b.json", doc))
        assert got["serving_p99_ms"] == 4.25
        assert got["mfu"] == 0.41          # last_measured_ stripped

    def test_history_from_files_keeps_order(self, tmp_path):
        ps = [_write(tmp_path, f"r{i}.json", {"m_per_sec": float(v)})
              for i, v in enumerate([10, 11, 12])]
        assert history_from_files(ps)["m_per_sec"] == [10.0, 11.0, 12.0]


# ---------------------------------------------------------- direction

class TestDirection:
    def test_known_directions(self):
        assert direction("train_images_per_sec") == "higher"
        assert direction("profile_mfu") == "higher"
        assert direction("serving_p99_ms") == "lower"
        assert direction("tracing_overhead_pct") == "lower"

    def test_unknowable_is_none(self):
        assert direction("widget_count") is None
        # tokens from both camps cancel out
        assert direction("latency_per_sec") is None


# ------------------------------------------------------------ compare

class TestCompareBenches:
    def _row(self, rows, metric):
        return next(r for r in rows if r["metric"] == metric)

    def test_synthetic_20pct_throughput_drop_fails(self):
        rows = compare_benches({"train_images_per_sec": 100.0},
                               {"train_images_per_sec": 80.0})
        assert self._row(rows, "train_images_per_sec")["verdict"] == \
            "regression"
        assert gate_verdict(rows).startswith("REGRESSION")

    def test_improvement_and_ok(self):
        rows = compare_benches(
            {"train_images_per_sec": 100.0, "serving_p99_ms": 10.0},
            {"train_images_per_sec": 125.0, "serving_p99_ms": 10.5})
        assert self._row(rows, "train_images_per_sec")["verdict"] == \
            "improved"
        assert self._row(rows, "serving_p99_ms")["verdict"] == "ok"
        assert gate_verdict(rows).startswith("PASS")

    def test_abs_floor_absorbs_sub_ms_jitter(self):
        # +40% relative but only 0.2 ms absolute: loopback jitter
        rows = compare_benches({"serving_p50_ms": 0.5},
                               {"serving_p50_ms": 0.7})
        assert self._row(rows, "serving_p50_ms")["verdict"] == "ok"

    def test_mad_history_widens_tolerance(self):
        # a trajectory that historically swings +-25% prices its own
        # noise: a 20% drop is within tolerance there
        hist = {"m_per_sec": [100.0, 75.0, 125.0, 80.0, 120.0]}
        rows = compare_benches({"m_per_sec": 100.0}, {"m_per_sec": 80.0},
                               hist)
        assert self._row(rows, "m_per_sec")["verdict"] == "ok"
        assert self._row(rows, "m_per_sec")["tol_pct"] > 10.0

    def test_short_history_keeps_rel_floor(self):
        hist = {"m_per_sec": [100.0, 75.0]}   # 2 samples prove nothing
        rows = compare_benches({"m_per_sec": 100.0}, {"m_per_sec": 80.0},
                               hist)
        assert self._row(rows, "m_per_sec")["verdict"] == "regression"

    def test_failed_measurement_skipped_never_gated(self):
        rows = compare_benches({"m_per_sec": 0.0}, {"m_per_sec": 80.0})
        assert self._row(rows, "m_per_sec")["verdict"] == "skipped"
        assert gate_verdict(rows).startswith("PASS")

    def test_unknown_direction_is_info(self):
        rows = compare_benches({"widget_count": 5.0},
                               {"widget_count": 50.0})
        assert self._row(rows, "widget_count")["verdict"] == "info"
        assert gate_verdict(rows).startswith("PASS")

    def test_format_table_renders_every_row(self):
        rows = compare_benches({"a_per_sec": 1.0}, {"a_per_sec": 2.0})
        table = format_table(rows)
        assert "a_per_sec" in table and "improved" in table
        assert format_table([]) == "(no common metrics)"


# ---------------------------------------------------------------- CLI

class TestGateCLI:
    def test_real_trajectory_passes(self, tmp_path, capsys):
        """ISSUE 16 acceptance: a two-run trajectory within tolerance
        clears the gate; no files at all is "too short" (exit 2)."""
        old = _write(tmp_path, "r1.json", {"train_images_per_sec": 100.0,
                                           "serving_p99_ms": 2.0})
        new = _write(tmp_path, "r2.json", {"train_images_per_sec": 101.0,
                                           "serving_p99_ms": 1.98})
        assert main(["gate", old, new]) == 0
        assert "PASS" in capsys.readouterr().out
        assert main(["gate"]) == 2

    def test_synthetic_regression_exits_1(self, tmp_path, capsys):
        old = _write(tmp_path, "r1.json", {"train_images_per_sec": 100.0})
        new = _write(tmp_path, "r2.json", {"train_images_per_sec": 80.0})
        assert main(["compare", old, new]) == 1
        assert "REGRESSION" in capsys.readouterr().out

    def test_gate_needs_two_files(self, tmp_path, monkeypatch, capsys):
        monkeypatch.chdir(tmp_path)
        assert main(["gate"]) == 2
        assert main([]) == 2
        assert main(["compare", "only_one.json"]) == 2

    def test_compare_with_history(self, tmp_path, capsys):
        hist = [_write(tmp_path, f"h{i}.json",
                       {"m_per_sec": v})
                for i, v in enumerate([100.0, 75.0, 125.0, 80.0])]
        old = _write(tmp_path, "old.json", {"m_per_sec": 100.0})
        new = _write(tmp_path, "new.json", {"m_per_sec": 80.0})
        assert main(["compare", old, new, "--history"] + hist) == 0


# -------------------------------------------------------------- CUSUM

class TestCusumDetector:
    def test_steady_sequence_never_alarms(self):
        det = CusumDetector(warmup=4, direction="lower_bad")
        vals = [0.42, 0.421, 0.419, 0.42] + [0.42, 0.418, 0.422] * 20
        assert not any(det.update(v) for v in vals)

    def test_step_drop_alarms_lower_bad(self):
        det = CusumDetector(warmup=4, direction="lower_bad")
        for v in [0.42] * 4 + [0.41, 0.43, 0.42]:
            assert det.update(v) is False
        alarms = [det.update(0.07) for _ in range(4)]
        assert alarms[-1] is True

    def test_higher_bad_direction(self):
        det = CusumDetector(warmup=4, direction="higher_bad")
        for v in [5.0] * 6:
            det.update(v)
        assert not det.alarm
        for _ in range(4):
            det.update(30.0)
        assert det.alarm

    def test_deterministic_fold(self):
        """Same value sequence -> bit-identical alarm history: the
        healthy same-seed replay can alarm exactly never."""
        seq = ([0.42, 0.41, 0.43, 0.42, 0.44, 0.41, 0.42, 0.43] +
               [0.40, 0.39, 0.12, 0.11, 0.10, 0.12, 0.11, 0.13])
        a = CusumDetector(warmup=8)
        b = CusumDetector(warmup=8)
        hist_a = [a.update(v) for v in seq]
        hist_b = [b.update(v) for v in seq]
        assert hist_a == hist_b
        assert (a.ref, a.scale, a.stat) == (b.ref, b.scale, b.stat)

    def test_bad_direction_rejected(self):
        with pytest.raises(ValueError):
            CusumDetector(direction="sideways")


# ----------------------------------------------------------- sentinel

def _mfu_sentinel(warmup=4, sustain_ticks=3):
    reg = MetricsRegistry()
    store = TimeSeriesStore(reg)
    pulls = {"v": None}

    def pull(_store):
        return pulls["v"]

    sent = RegressionSentinel(store, reg, watches=[
        SeriesWatch("profile_mfu", pull, direction="lower_bad",
                    warmup=warmup)], sustain_ticks=sustain_ticks)
    return sent, reg, pulls


class TestRegressionSentinel:
    def test_rising_edge_counts_once_and_fires_span(self):
        sent, reg, pulls = _mfu_sentinel()
        with SpanCollector() as col:
            for v in [0.42, 0.41, 0.43, 0.42]:   # warmup
                pulls["v"] = v
                assert sent.tick() == frozenset()
            pulls["v"] = 0.05
            for _ in range(5):                   # alarm + hold
                sent.tick()
        assert sent.active() == {"profile_mfu"}
        snap = reg.snapshot()
        assert snap['obs_regression_active{series="profile_mfu"}'] == 1.0
        # one event for the whole alarm episode, not one per tick
        assert snap['obs_regression_events_total{series="profile_mfu"}'] \
            == 1.0
        spans = [s for s in col.spans()
                 if s["name"] == "obs.regression"]
        assert len(spans) == 1
        assert spans[0]["attrs"]["series"] == "profile_mfu"

    def test_sustained_needs_consecutive_ticks(self):
        sent, _, pulls = _mfu_sentinel(sustain_ticks=3)
        for v in [0.42, 0.41, 0.43, 0.42]:
            pulls["v"] = v
            sent.tick()
        pulls["v"] = 0.05
        sent.tick()
        assert sent.active() == {"profile_mfu"}
        assert sent.sustained() == frozenset()   # 1 tick < 3
        sent.tick()
        sent.tick()
        assert sent.sustained() == {"profile_mfu"}

    def test_recovery_clears_active_and_gauge(self):
        sent, reg, pulls = _mfu_sentinel()
        for v in [0.42, 0.41, 0.43, 0.42]:
            pulls["v"] = v
            sent.tick()
        pulls["v"] = 0.05
        for _ in range(3):
            sent.tick()
        pulls["v"] = 0.42
        # stat ~ 3 x |z| ~ 51 drains at k=0.5 per healthy tick
        for _ in range(120):
            sent.tick()
        assert sent.active() == frozenset()
        assert sent.sustained() == frozenset()
        snap = reg.snapshot()
        assert snap['obs_regression_active{series="profile_mfu"}'] == 0.0

    def test_none_reading_does_not_feed_detector(self):
        sent, _, pulls = _mfu_sentinel(warmup=4)
        pulls["v"] = None
        for _ in range(50):                      # no signal, no warmup
            assert sent.tick() == frozenset()
        assert sent.watches[0].detector.ref is None

    def test_sustained_alarm_degrades_fleet_health(self):
        """ISSUE 16: a sustained regression turns /healthz DEGRADED —
        never critical, a slow fleet must not be drained."""
        sent, reg, pulls = _mfu_sentinel(sustain_ticks=2)
        health = FleetHealth(FleetAggregator(reg), registry=reg,
                             store=sent.store)
        health.attach_sentinel(sent)
        for v in [0.42, 0.41, 0.43, 0.42]:
            pulls["v"] = v
            sent.tick()
        assert health.tick() == "ok"
        pulls["v"] = 0.05
        sent.tick()
        sent.tick()
        assert health.tick() == "degraded"
        status, body = health.healthz_payload()
        assert status == 200
        payload = json.loads(body)
        assert any("regression=profile_mfu" in r
                   for r in payload["reasons"])


# ---------------------------------------------------- chaos acceptance

class TestRegressionChaosScenario:
    def test_seeded_fault_flips_alarm_within_20_ticks(self):
        """ISSUE 16 acceptance: a worker.slow x6 fault steps MFU down;
        obs_regression_active flips within 20 recorder ticks of the
        step and FleetHealth reads degraded."""
        from mmlspark_tpu.testing.benchmarks import \
            regression_chaos_scenario

        r = regression_chaos_scenario(chaos=True)
        assert r["step_at_tick"] is not None
        assert r["alarm_tick"] is not None
        assert r["ticks_to_alarm"] <= 20
        assert r["events"] == 1
        assert r["verdict_end"] == "degraded"
        assert r["mfu_degraded"] < r["mfu_healthy"] / 2

    def test_healthy_replay_alarms_exactly_never(self):
        from mmlspark_tpu.testing.benchmarks import \
            regression_chaos_scenario

        r = regression_chaos_scenario(chaos=False)
        assert r["events"] == 0
        assert r["alarm_tick"] is None
        assert r["verdict_end"] == "ok"

    def test_bit_deterministic_across_runs(self):
        from mmlspark_tpu.testing.benchmarks import \
            regression_chaos_scenario

        a = regression_chaos_scenario(chaos=True)
        b = regression_chaos_scenario(chaos=True)
        assert a == b
