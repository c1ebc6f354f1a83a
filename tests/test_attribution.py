"""Device cost-attribution plane (ISSUE 20): PeakSpec resolution, the
per-program roofline gauges, AOT meta.json cost persistence + warm
re-export, the LLM warm-path attribution, the goodput ledger's waste
taxonomy, the on-demand xprof capture surface (503/409/400, list,
fetch), both serving fronts' /debug routes, cost-model schema v6
back-compat, and the seeded attribution bench scenario."""

from __future__ import annotations

import io
import json
import zipfile

import numpy as np
import pytest

from mmlspark_tpu.obs import attribution as attr_mod
from mmlspark_tpu.obs.attribution import (CostAttribution, PEAK_SPECS,
                                          PeakSpec, cost_attribution,
                                          peak_spec)
from mmlspark_tpu.obs import xprof as xprof_mod
from mmlspark_tpu.obs.fleet import parse_sample
from mmlspark_tpu.obs.goodput import (DEFAULT_UNIT_COSTS, GoodputLedger,
                                      WASTE_CAUSES)
from mmlspark_tpu.obs.metrics import MetricsRegistry
from mmlspark_tpu.obs.xprof import XprofCaptures
from mmlspark_tpu.testing.benchmarks import (attribution_scenario,
                                             synth_attribution_rows)


def _reg():
    return MetricsRegistry()


def _roofline(reg, program):
    """{bound: value} for one program's roofline gauge samples."""
    out = {}
    for sample, value in reg.snapshot().items():
        name, labels = parse_sample(sample)
        if name == "profile_roofline_utilization" and \
                labels.get("program") == program:
            out[labels["bound"]] = value
    return out


# ---------------------------------------------------------- PeakSpec

class TestPeakSpec:
    def test_table_rows_resolve_by_name(self):
        assert peak_spec("tpu-v5e").peak_flops == \
            PEAK_SPECS["tpu-v5e"].peak_flops
        assert peak_spec("tpu-v4").hbm_bytes_per_s == \
            PEAK_SPECS["tpu-v4"].hbm_bytes_per_s
        assert peak_spec("cpu").platform == "cpu"

    def test_unknown_platform_raises(self):
        # the cpu row never stands in for a device without a row
        with pytest.raises(LookupError, match="riscv-accel"):
            peak_spec("riscv-accel")
        assert peak_spec("").platform in PEAK_SPECS   # live: cpu

    def test_tpu_resolves_through_device_kind(self, monkeypatch):
        monkeypatch.setattr(attr_mod, "_device_kind",
                            lambda: "TPU v5 lite")
        assert peak_spec("tpu").platform == "tpu-v5e"
        # a generation the table has no row for is an error, never v5e
        monkeypatch.setattr(attr_mod, "_device_kind", lambda: "TPU v9x")
        with pytest.raises(LookupError, match="TPU v9x"):
            peak_spec("tpu")

    def test_env_overrides_win_over_table(self, monkeypatch):
        monkeypatch.setenv(attr_mod.ENV_PEAK_FLOPS, "5e12")
        spec = peak_spec("tpu-v5e")
        assert spec.peak_flops == 5e12
        # the other axis keeps the table row
        assert spec.hbm_bytes_per_s == PEAK_SPECS["tpu-v5e"].hbm_bytes_per_s
        monkeypatch.setenv(attr_mod.ENV_PEAK_BYTES, "2e11")
        assert peak_spec("cpu").hbm_bytes_per_s == 2e11

    def test_junk_override_is_ignored(self, monkeypatch):
        monkeypatch.setenv(attr_mod.ENV_PEAK_FLOPS, "not-a-number")
        assert peak_spec("cpu").peak_flops == PEAK_SPECS["cpu"].peak_flops

    def test_roofline_seconds_is_slower_pipe(self):
        spec = PeakSpec("x", peak_flops=1e12, hbm_bytes_per_s=1e11)
        assert spec.roofline_seconds(1e12, 0.0) == pytest.approx(1.0)
        assert spec.roofline_seconds(0.0, 1e11) == pytest.approx(1.0)
        assert spec.roofline_seconds(1e12, 2e11) == pytest.approx(2.0)


# ------------------------------------------------- roofline gauges

class TestCostAttribution:
    def test_compute_bound_program_pins_compute_axis(self):
        reg = _reg()
        ca = CostAttribution(registry=reg)
        # flops saturate long before bytes at the cpu row's ratios
        info = ca.record_program("p_mm", 1e9, 1e3, service="svc",
                                 platform="cpu")
        assert info["bound"] == "compute"
        util = _roofline(reg, "p_mm")
        assert util["compute"] == pytest.approx(1.0)
        assert 0.0 <= util["memory"] < 1.0

    def test_memory_bound_program_pins_memory_axis(self):
        reg = _reg()
        ca = CostAttribution(registry=reg)
        info = ca.record_program("p_add", 1e3, 1e9, service="svc",
                                 platform="cpu")
        assert info["bound"] == "memory"
        util = _roofline(reg, "p_add")
        assert util["memory"] == pytest.approx(1.0)
        assert util["compute"] < 1.0

    def test_both_axes_never_exceed_one(self):
        reg = _reg()
        ca = CostAttribution(registry=reg)
        for i, (f, b) in enumerate([(1e9, 1e9), (0.0, 0.0), (5.0, 5.0)]):
            ca.record_program(f"p{i}", f, b, platform="cpu")
            for v in _roofline(reg, f"p{i}").values():
                assert v <= 1.0

    def test_device_without_a_row_is_counted_not_raised(self,
                                                       monkeypatch):
        """``peak_spec`` raises for a part the table does not know;
        the telemetry sinks on serving and build paths count it and
        skip the roofline / MFU gauges instead of stopping a warm-up,
        an AOT build or a served step."""
        from mmlspark_tpu.obs.metrics import registry as default_reg
        from mmlspark_tpu.obs.profile import StepProfiler
        monkeypatch.setattr(attr_mod, "_device_kind", lambda: "TPU v9x")
        missing = 'profile_peak_spec_missing_total{platform="tpu"}'
        before = default_reg.snapshot().get(missing, 0.0)
        reg = _reg()
        info = CostAttribution(registry=reg).record_program(
            "p_v9x", 1e9, 1e3, service="svc", platform="tpu")
        assert (info["flops"], info["bytes"]) == (1e9, 1e3)
        assert info["bound"] is None and info["roofline_seconds"] is None
        assert reg.snapshot()[
            'profile_analytic_flops{program="p_v9x"}'] == 1e9
        assert _roofline(reg, "p_v9x") == {}
        assert default_reg.snapshot()[missing] == before + 1
        # a served step on that part: timed and counted, no MFU gauge
        monkeypatch.setattr("mmlspark_tpu.obs.profile.telemetry_peak_spec",
                            lambda: attr_mod.telemetry_peak_spec("tpu"))
        sreg = _reg()
        with StepProfiler(registry=sreg).step("v9x_stage", flops=1e9):
            pass
        snap = sreg.snapshot()
        assert any(k.startswith("profile_steps_total") for k in snap)
        assert not any(k.startswith("profile_mfu") for k in snap)

    def test_analytic_gauges_and_service_sums(self):
        reg = _reg()
        ca = CostAttribution(registry=reg)
        ca.record_program("a", 10.0, 2.0, service="s1", platform="cpu")
        ca.record_program("b", 5.0, 1.0, service="s1", platform="cpu")
        ca.record_program("c", 7.0, 3.0, service="s2", platform="cpu")
        snap = reg.snapshot()
        assert snap['profile_analytic_flops{program="a"}'] == 10.0
        assert snap['profile_analytic_bytes{program="c"}'] == 3.0
        assert ca.service_cost("s1") == (15.0, 3.0)
        assert ca.service_cost("s2") == (7.0, 3.0)
        assert ca.service_cost("nobody") == (0.0, 0.0)
        assert set(ca.programs()) == {"a", "b", "c"}
        ca.clear()
        assert ca.service_cost("s1") == (0.0, 0.0)

    def test_matmul_bound_segment_cpu_analytic_path(self):
        """Acceptance: roofline_utilization <= 1.05 on a known
        matmul-bound program through the REAL cost_analysis path."""
        import jax
        import jax.numpy as jnp

        reg = _reg()
        ca = CostAttribution(registry=reg)
        f = jax.jit(lambda m: m @ m)
        compiled = f.lower(jnp.ones((256, 256), jnp.float32)).compile()
        info = ca.record_compiled("mm256", compiled, service="attr-t",
                                  platform="cpu")
        assert info is not None and info["flops"] > 0
        assert info["bound"] == "compute"
        util = _roofline(reg, "mm256")
        assert util["compute"] <= 1.05
        assert util["memory"] <= 1.05


# ----------------------------------------- AOT meta.json persistence

class TestAotCostPersistence:
    def _spec(self, n=8, width=4):
        from mmlspark_tpu.core import DataFrame
        from mmlspark_tpu.featurize.vector import (OneHotEncoderModel,
                                                   VectorAssembler)

        rng = np.random.default_rng(3)
        df = DataFrame({
            "x": rng.normal(size=(n, width)).astype(np.float32),
            "cat": (np.arange(n) % 3).astype(np.int32),
        })
        stages = [
            OneHotEncoderModel(inputCol="cat", outputCol="onehot",
                               categorySize=3, handleInvalid="keep"),
            VectorAssembler(inputCols=["x", "onehot"],
                            outputCol="features", handleInvalid="keep"),
        ]
        return stages, df

    def test_build_persists_cost_and_warm_reexports(self, tmp_path):
        from mmlspark_tpu.core import aot, compile_pipeline
        from mmlspark_tpu.core.aot import AotStore

        prev = aot.active_store()
        aot.uninstall()
        try:
            stages, df = self._spec()
            store = AotStore(str(tmp_path / "store"))
            cp = compile_pipeline(stages, df, service="attr-aot")
            records = aot.build_pipeline(cp, df, store)
            assert any(r.get("built") for r in records)
            entries = store.entries()
            assert entries
            for meta in entries:
                cost = meta.get("cost")
                assert isinstance(cost, dict), \
                    "every AOT entry must persist its analytic cost"
                assert cost["flops"] >= 0 and cost["bytes"] >= 0
            # a fresh plan's warm load re-exports the persisted pair
            # into the attribution table without re-analyzing
            seg = entries[0]["segment"]
            cost_attribution.clear()
            aot.install(store)
            fresh = compile_pipeline(stages, df, service="attr-aot")
            assert fresh.warm_aot() >= 1
            info = cost_attribution.program_cost(seg)
            assert info is not None
            assert info["flops"] == entries[0]["cost"]["flops"]
            assert info["bytes"] == entries[0]["cost"]["bytes"]
        finally:
            if prev is not None:
                aot.install(prev)
            else:
                aot.uninstall()


# ------------------------------------------------ LLM warm programs

class TestLLMWarmAttribution:
    def test_warm_records_prefill_and_decode_programs(self):
        import jax
        import jax.numpy as jnp

        from mmlspark_tpu.dl import (MaskedLMModel, TextEncoder,
                                     make_attention_fn)
        from mmlspark_tpu.serving.llm import LLMEngine

        enc = TextEncoder(vocab=32, width=16, depth=1, heads=2,
                          mlp_dim=32, dtype=jnp.float32,
                          attention_fn=make_attention_fn("dense",
                                                         causal=True))
        module = MaskedLMModel(enc)
        variables = module.init(jax.random.PRNGKey(0),
                                np.zeros((1, 8), np.int32))
        eng = LLMEngine(module, variables, slots=2, block_len=4,
                        max_seq_len=16, service="attr-llm",
                        registry=MetricsRegistry())
        eng.warm(mark_steady=False)
        progs = cost_attribution.programs()
        prefill = [p for p in progs
                   if p.startswith("llm_prefill_attr-llm")]
        decode = [p for p in progs
                  if p.startswith("llm_decode_") and "attr-llm" in p]
        assert prefill and decode
        for p in prefill + decode:
            assert progs[p]["flops"] > 0
            assert progs[p]["service"] == "attr-llm"
        flops, bytes_ = cost_attribution.service_cost("attr-llm")
        assert flops > 0 and bytes_ > 0


# -------------------------------------------------- goodput ledger

class TestGoodputLedger:
    def test_baseline_tick_is_ratio_one(self):
        led = GoodputLedger(registry=_reg())
        p = led.tick()
        assert p["goodput_ratio"] == 1.0
        assert p["ticks"] == 1
        assert p["waste_total_seconds"] == 0.0

    def test_spec_reject_priced_at_measured_token_time(self):
        reg = _reg()
        led = GoodputLedger(registry=reg)
        c_rej = reg.counter("gen_spec_rejected_total", "t")
        h_dec = reg.histogram("gen_decode_attn_seconds", "t")
        c_tok = reg.counter("gen_tokens_total", "t")
        led.tick()  # baseline
        for _ in range(8):
            h_dec.observe(0.002)
        c_tok.inc(8)
        c_rej.inc(10)
        p = led.tick()
        # unit = 0.016 / 8 tokens; waste = 10 * 0.002
        assert p["waste_seconds"]["spec_reject"] == pytest.approx(0.02)
        assert p["unit_costs"]["spec_reject"] == pytest.approx(0.002)
        # useful half = the decode seconds; ratio dips below 1
        assert p["useful_seconds"] == pytest.approx(0.016)
        assert p["goodput_ratio"] < 1.0

    def test_shed_expired_split_and_default_units(self):
        reg = _reg()
        led = GoodputLedger(registry=reg)
        c_shed = reg.counter("sched_shed_total", "t")
        c_cexp = reg.counter("sched_continuous_expired_total", "t")
        led.tick()
        c_shed.inc(3, reason="backpressure")
        c_shed.inc(2, reason="expired")
        c_cexp.inc(1)
        p = led.tick()
        assert p["waste_seconds"]["shed"] == pytest.approx(
            3 * DEFAULT_UNIT_COSTS["shed"])
        assert p["waste_seconds"]["expired"] == pytest.approx(
            3 * DEFAULT_UNIT_COSTS["expired"])

    def test_runtime_compile_priced_at_measured_mean(self):
        reg = _reg()
        led = GoodputLedger(registry=reg)
        c_rt = reg.counter("profile_runtime_compiles_total", "t")
        h_c = reg.histogram("profile_compile_seconds", "t")
        led.tick()
        c_rt.inc(2)
        h_c.observe(0.4)
        h_c.observe(0.6)
        p = led.tick()
        assert p["waste_seconds"]["runtime_compile"] == pytest.approx(1.0)

    def test_straggler_stretch_is_capped(self):
        reg = _reg()
        led = GoodputLedger(registry=reg)
        h_step = reg.histogram("profile_step_seconds", "t")
        g_s = reg.gauge("fleet_straggler_score", "t")
        led.tick()
        h_step.observe(1.0)
        g_s.set(1e9, worker="w0")  # wild score must not zero goodput
        p = led.tick()
        assert p["waste_seconds"]["straggler"] == pytest.approx(0.5)
        assert p["goodput_ratio"] >= 0.5

    def test_exports_and_reset(self):
        reg = _reg()
        led = GoodputLedger(registry=reg)
        c_shed = reg.counter("sched_shed_total", "t")
        led.tick()
        c_shed.inc(5, reason="backpressure")
        led.tick()
        snap = reg.snapshot()
        assert snap['goodput_waste_seconds_total{cause="shed"}'] > 0
        assert snap["goodput_ratio"] < 1.0
        assert snap["goodput_ticks_total"] == 2
        led.reset()
        assert led.tick()["goodput_ratio"] == 1.0

    def test_taxonomy_is_closed(self):
        led = GoodputLedger(registry=_reg())
        p = led.tick()
        assert set(p["waste_seconds"]) == set(WASTE_CAUSES)


# ------------------------------------------------- xprof captures

class TestXprofCaptures:
    def test_bad_duration_is_400(self, tmp_path):
        xc = XprofCaptures(root=str(tmp_path), registry=_reg())
        status, body = xc.handle_query("duration_ms=banana", b"")
        assert status == 400

    def test_no_jax_degrades_to_503_with_reason(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setattr(xprof_mod, "_jax_ready",
                            lambda: (False, "jax not imported"))
        reg = _reg()
        xc = XprofCaptures(root=str(tmp_path), registry=reg)
        status, body = xc.handle_query("duration_ms=10", b"")
        assert status == 503
        assert json.loads(body)["reason"] == "jax not imported"
        assert reg.snapshot()[
            'profile_xprof_captures_total{outcome="unavailable"}'] == 1
        # listing still answers, and says why captures cannot run
        listing = xc.list_captures()
        assert listing["available"] is False and listing["reason"]

    def test_second_capture_while_open_is_409(self, tmp_path):
        reg = _reg()
        xc = XprofCaptures(root=str(tmp_path), registry=reg)
        xc._active = "capture-0007-r0"
        status, body = xc.handle_query("duration_ms=10", b"")
        assert status == 409
        assert json.loads(body)["active"] == "capture-0007-r0"
        assert reg.snapshot()[
            'profile_xprof_captures_total{outcome="busy"}'] == 1

    def test_capture_list_fetch_roundtrip(self, tmp_path, monkeypatch):
        import jax
        import jax.numpy as jnp

        jax.block_until_ready(jnp.zeros(1))  # backend must be live
        monkeypatch.setenv(xprof_mod.ENV_MAX_MS, "50")
        reg = _reg()
        xc = XprofCaptures(root=str(tmp_path), registry=reg)
        status, body = xc.handle_query("duration_ms=5000&tag=t est", b"")
        assert status == 200, body
        out = json.loads(body)
        # duration clamped to the env ceiling; tag sanitized; the
        # capture name carries the pod rank suffix
        assert out["duration_ms"] == 50.0
        assert out["capture"].endswith("-r0")
        assert "t_est" in out["capture"]
        assert out["files"] >= 1
        assert reg.snapshot()[
            'profile_xprof_captures_total{outcome="ok"}'] == 1
        status, body = xc.handle_query("", b"")
        assert status == 200
        listing = json.loads(body)
        assert [c["capture"] for c in listing["captures"]] == \
            [out["capture"]]
        assert listing["active"] is None
        status, blob = xc.handle_query(f"fetch={out['capture']}", b"")
        assert status == 200
        names = zipfile.ZipFile(io.BytesIO(blob)).namelist()
        assert len(names) == out["files"]
        status, _ = xc.handle_query("fetch=no-such-capture", b"")
        assert status == 404

    def test_fetch_refuses_traversal(self, tmp_path):
        xc = XprofCaptures(root=str(tmp_path / "caps"), registry=_reg())
        assert xc.fetch("../../etc") is None


# --------------------------------------- serving fronts' debug routes

def _ok_pipeline():
    from mmlspark_tpu.io.http.schema import HTTPResponseData

    def pipeline(df):
        replies = np.empty(len(df), object)
        replies[:] = [HTTPResponseData(status_code=200, entity=b"ok")
                      for _ in df["request"]]
        return df.with_column("reply", replies)

    return pipeline


class TestDebugRoutesBothFronts:
    def _get(self, addr, path):
        import http.client
        conn = http.client.HTTPConnection(*addr, timeout=10)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def _assert_routes(self, addr):
        # goodput: a live ledger report, never staler than the request
        status, body = self._get(addr, "/debug/goodput")
        assert status == 200
        payload = json.loads(body)
        assert 0.0 <= payload["goodput_ratio"] <= 1.0
        assert set(payload["waste_seconds"]) == set(WASTE_CAUSES)
        # xprof: empty query lists (jax is live in this process, so
        # the surface reports available; no capture has to run)
        status, body = self._get(addr, "/debug/xprof")
        assert status == 200
        listing = json.loads(body)
        assert "captures" in listing and "available" in listing
        # bad capture requests degrade to 400, never a stack trace
        status, _ = self._get(addr, "/debug/xprof?duration_ms=banana")
        assert status == 400
        # the neighbors this PR rides along: fleet + timeline
        status, body = self._get(addr, "/debug/fleet")
        assert status == 200
        assert json.loads(body)["status"] in ("ok", "degraded",
                                              "critical")
        status, body = self._get(addr, "/debug/timeline")
        assert status == 200
        assert "series" in json.loads(body)

    def test_python_front(self):
        from mmlspark_tpu.serving import serving_query
        q = serving_query("attrdbgpy", _ok_pipeline(), backend="python")
        try:
            self._assert_routes(q.server.address)
        finally:
            q.stop()

    def test_native_front(self):
        from mmlspark_tpu.native.loader import get_httpfront
        if get_httpfront() is None:
            pytest.skip("native http front unavailable")
        from mmlspark_tpu.serving import serving_query
        q = serving_query("attrdbgnat", _ok_pipeline(), backend="native")
        try:
            self._assert_routes(q.server.address)
        finally:
            q.stop()


# ------------------------------------------- cost model schema v6

class TestCostModelV6:
    def test_analytic_columns_train_and_price(self):
        from mmlspark_tpu.perf.costmodel import CostModel

        m = CostModel(min_rows=32, registry=_reg())
        rows = synth_attribution_rows(600, seed=7)
        assert m.fit(rows) == len(rows)
        theta = next(iter(m._models.values()))["theta"]
        assert len(theta) == 10
        p = m.predict_batch_ms("attr-bench", 8, route="/gen",
                               entity_bytes=1024, queue_depth=1)
        assert p is not None and p > 0

    def test_rows_without_analytic_columns_train_as_zero(self):
        from mmlspark_tpu.perf.costmodel import CostModel
        from mmlspark_tpu.testing.benchmarks import synth_feature_rows

        reg = _reg()
        m = CostModel(min_rows=8, registry=reg)
        v5 = [dict(r, schema_version=5)
              for r in synth_feature_rows(64, seed=5)]
        v4 = [dict(r, schema_version=4)
              for r in synth_feature_rows(64, seed=6)]
        assert m.fit(v5 + v4) == 128
        assert reg.snapshot().get(
            'sched_costmodel_skipped_rows_total{reason="schema"}') \
            is None
        theta = next(iter(m._models.values()))["theta"]
        assert len(theta) == 10

    def test_pre_v6_theta_still_predicts(self):
        """A model persisted before the analytic pair has an 8-dim
        theta — prediction must use exactly what it was trained with."""
        from mmlspark_tpu.perf.costmodel import CostModel

        m = CostModel(registry=_reg())
        m._models[("old", "")] = {
            "theta": np.ones(8), "mean": np.ones(8),
            "n": 100, "train_mae_ms": 0.1}
        p = m.predict_batch_ms("old", 4, entity_bytes=2048,
                               queue_depth=1, context_blocks=3)
        assert p is not None and np.isfinite(p)

    def test_save_load_roundtrip_keeps_v6_features(self, tmp_path):
        from mmlspark_tpu.perf.costmodel import CostModel

        m = CostModel(min_rows=32, registry=_reg())
        m.fit(synth_attribution_rows(400, seed=3))
        path = m.save(str(tmp_path / "cm.json"))
        m2 = CostModel(registry=_reg())
        assert m2.load_file(path) >= 1
        a = m.predict_batch_ms("attr-bench", 8, route="/gen",
                               entity_bytes=1024, queue_depth=1,
                               count=False)
        b = m2.predict_batch_ms("attr-bench", 8, route="/gen",
                                entity_bytes=1024, queue_depth=1,
                                count=False)
        assert a == pytest.approx(b)


# ------------------------------------------------ scenario smoke

class TestAttributionScenario:
    def test_scenario_is_seeded_and_banks_the_acceptance(self):
        r1 = attribution_scenario(seed=29, n_rows=600, ticks=8)
        r2 = attribution_scenario(seed=29, n_rows=600, ticks=8)
        assert r1["matmul_compute_bound"] is True
        assert r1["add_memory_bound"] is True
        assert r1["utilization_max"] <= 1.05
        assert 0.0 < r1["goodput_ratio"] < 1.0
        assert r1["goodput_waste_itemized"] is True
        # same seed -> the same chaos schedule, tick for tick
        assert r1["goodput_ratio_trace"] == r2["goodput_ratio_trace"]
        assert r1["v6_no_worse"] is True
        assert r1["v6_mae_ms"] <= r1["v5_mae_ms"] * 1.001
