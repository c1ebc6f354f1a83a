"""Zero-downtime model lifecycle (serving/deploy.py, ISSUE 19).

Covers: the versioned registry (persist/reload, deploy-state
protection), the VersionRouter's atomic flip + deterministic canary
slice + drain-to-retire accounting, the RolloutController's
burn-driven rollback / healthy-window promotion / healthz flap, the
serving integration (X-Model-Version echo on every response, per-
version executor dispatch, seeded ``model.bad`` injection), aot gc's
never-collect-the-rollback-target regression, the loadgen per-version
summary split, and the full rollout acceptance scenario (blue/green
flip under chaos + seeded-bad-canary auto-rollback)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.aot import AotStore
from mmlspark_tpu.core.utils import cpu_child_env
from mmlspark_tpu.obs.metrics import MetricsRegistry
from mmlspark_tpu.obs.metrics import registry as _process_reg
from mmlspark_tpu.resilience import FaultRule, faults, injector
from mmlspark_tpu.serving.deploy import (ACTIVE, CANDIDATE, DRAINING,
                                         RETIRED, ModelRegistry,
                                         RolloutConfig,
                                         RolloutController,
                                         VersionRouter)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    injector.clear()
    yield
    injector.clear()
    # don't leak serving/deploy spans into the process-wide recorder:
    # later suites assert on its pending set (drain is bounded per call)
    from mmlspark_tpu.obs.export import flight_recorder
    while flight_recorder.pending_spans(drain=True):
        pass


def _registry(tmp_path=None, **kw):
    root = str(tmp_path) if tmp_path is not None else None
    return ModelRegistry(root=root, service="dep-test",
                         registry=MetricsRegistry(), **kw)


def _router(mreg, **kw):
    kw.setdefault("metrics", MetricsRegistry())
    return VersionRouter(mreg, **kw)


# --------------------------------------------------------- registry
class TestModelRegistry:
    def test_register_persist_reload(self, tmp_path):
        mreg = _registry(tmp_path)
        v1 = mreg.register("v1", transform=lambda b: b,
                           static_fps=("a" * 64,), meta={"tag": "r1"})
        mreg.register("v2", static_fps=("b" * 64,))
        mreg.set_state("v1", ACTIVE)
        assert v1.seq == 1
        # a fresh registry over the same root sees the same records —
        # minus the transforms, which are runtime-only
        back = _registry(tmp_path)
        names = [v.name for v in back.versions()]
        assert names == ["v1", "v2"]
        r1 = back.get("v1")
        assert r1.state == ACTIVE and r1.static_fps == ("a" * 64,)
        assert r1.meta == {"tag": "r1"} and r1.transform is None
        # re-register re-attaches the transform, keeps seq/state
        fn = lambda b: b + b                                 # noqa: E731
        again = back.register("v1", transform=fn)
        assert again.seq == 1 and again.state == ACTIVE
        assert back.get("v1").transform is fn

    def test_protected_fps_deploy_states_and_horizon(self, tmp_path):
        mreg = _registry(tmp_path)
        mreg.register("v1", static_fps=("a" * 64,))
        mreg.register("v2", static_fps=("b" * 64,))
        mreg.register("v3", static_fps=("c" * 64,))
        mreg.set_state("v2", ACTIVE)
        mreg.set_state("v3", CANDIDATE)
        # deploy states are protected unconditionally
        assert mreg.protected_fps() == {"b" * 64, "c" * 64}
        # the keep-last horizon adds retired/registered versions
        assert mreg.protected_fps(keep_last=3) == \
            {"a" * 64, "b" * 64, "c" * 64}


# ----------------------------------------------------------- router
class TestVersionRouter:
    def test_canary_stride_is_deterministic(self):
        mreg = _registry()
        mreg.register("v1", transform=lambda b: b)
        mreg.register("v2", transform=lambda b: b)
        router = _router(mreg, canary_share=0.25)
        router.set_active("v1")
        router.stage("v2")
        picks = [router.assign("gold") for _ in range(8)]
        assert [p[0] for p in picks] == \
            ["v1", "v1", "v1", "v2", "v1", "v1", "v1", "v2"]
        # the canary slice rides on its OWN tenant budget
        assert [p[1] for p in picks] == \
            [None, None, None, "canary", None, None, None, "canary"]

    def test_flip_drains_old_version_to_retired(self):
        mreg = _registry()
        mreg.register("v1", transform=lambda b: b)
        mreg.register("v2", transform=lambda b: b)
        router = _router(mreg)
        router.set_active("v1")
        # two requests admitted on v1 BEFORE the flip
        assert router.assign("t")[0] == "v1"
        assert router.assign("t")[0] == "v1"
        router.stage("v2")
        assert router.flip() == "v2"
        assert router.active == "v2" and router.prior == "v1"
        # the old version drains: state flips, inflight counted
        assert mreg.get("v1").state == DRAINING
        assert router.draining_inflight() == 2
        # new admissions only ever see the new version
        assert router.assign("t")[0] == "v2"
        # completions on the admitting version retire it at zero
        router.release("v1")
        assert router.draining_inflight() == 1
        router.release("v1")
        assert router.draining_inflight() == 0
        assert mreg.get("v1").state == RETIRED
        # flip without a candidate is a no-op
        assert router.flip() is None

    def test_rollback_restores_prior_and_counts_reason(self):
        reg = MetricsRegistry()
        mreg = _registry()
        mreg.register("v1", transform=lambda b: b)
        mreg.register("v2", transform=lambda b: b)
        router = _router(mreg, metrics=reg)
        router.set_active("v1")
        router.stage("v2")
        router.flip()
        assert router.rollback("burn") == "v2"
        assert router.active == "v1" and router.prior is None
        snap = reg.snapshot()
        assert snap['deploy_rollbacks_total{reason="burn",'
                    'service="dep-test"}'] == 1
        # nothing left to roll back
        assert router.rollback("burn") is None

    def test_rollback_demotes_live_candidate(self):
        mreg = _registry()
        mreg.register("v1", transform=lambda b: b)
        mreg.register("v2", transform=lambda b: b)
        router = _router(mreg, canary_share=0.5)
        router.set_active("v1")
        router.stage("v2")
        assert router.rollback("burn") == "v2"
        assert router.active == "v1" and router.candidate is None
        # the canary slice is gone with the candidate
        assert all(router.assign("t")[0] == "v1" for _ in range(6))

    def test_shadow_mode_mirrors_not_routes(self):
        mreg = _registry()
        mreg.register("v1", transform=lambda b: b)
        mreg.register("v2", transform=lambda b: b)
        router = _router(mreg, canary_share=0.5, shadow=True)
        router.set_active("v1")
        router.stage("v2")
        # shadow: the candidate gets NO live traffic...
        assert all(router.assign("t") == ("v1", None)
                   for _ in range(6))
        # ...but the executor is told to mirror-and-compare
        assert router.shadow_pair() == ("v1", "v2")

    def test_active_transform_factory_tracks_flips(self):
        mreg = _registry()
        f1, f2 = (lambda b: b"1"), (lambda b: b"2")
        mreg.register("v1", transform=f1)
        mreg.register("v2", transform=f2)
        router = _router(mreg)
        router.set_active("v1")
        factory = router.transform_factory()
        assert factory() is f1
        router.stage("v2")
        router.flip()
        # a worker spawned after the flip builds the NEW version
        assert factory() is f2


# ------------------------------------------------------- controller
def _burns(fast, slow):
    return {"canary": {"fast": fast, "slow": slow}}


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _staged_pair(**router_kw):
    mreg = _registry()
    mreg.register("v1", transform=lambda b: b)
    mreg.register("v2", transform=lambda b: b)
    router = _router(mreg, canary_share=0.25, **router_kw)
    router.set_active("v1")
    router.stage("v2")
    return mreg, router


class TestRolloutController:
    def test_rollback_after_sustained_burn(self):
        _, router = _staged_pair()
        clock = _FakeClock()
        ctl = RolloutController(
            router, metrics=MetricsRegistry(), clock=clock,
            config=RolloutConfig(rollback_windows=2))
        # one burning window is a blip: multi-window hold, no action
        assert ctl.tick(burns=_burns(50.0, 10.0)) == "hold"
        clock.t += 1
        assert ctl.tick(burns=_burns(50.0, 10.0)) == "rollback"
        assert router.candidate is None and router.active == "v1"
        assert ctl.events[-1]["kind"] == "rollback"
        assert ctl.events[-1]["reason"] == "burn"
        # cooldown: a freshly re-staged candidate gets no decisions
        # while the dust settles
        router.stage("v2")
        clock.t += 0.1
        assert ctl.tick(burns=_burns(0.0, 0.0)) == "cooldown"

    def test_blip_resets_on_healthy_window(self):
        _, router = _staged_pair()
        clock = _FakeClock()
        ctl = RolloutController(
            router, metrics=MetricsRegistry(), clock=clock,
            config=RolloutConfig(rollback_windows=2))
        assert ctl.tick(burns=_burns(50.0, 10.0)) == "hold"
        clock.t += 1
        # fast window recovered -> the unhealthy streak resets
        assert ctl.tick(burns=_burns(0.0, 0.5)) == "hold"
        clock.t += 1
        assert ctl.tick(burns=_burns(50.0, 10.0)) == "hold"
        assert router.candidate == "v2"

    def test_slow_window_confirmation_required(self):
        _, router = _staged_pair()
        clock = _FakeClock()
        ctl = RolloutController(
            router, metrics=MetricsRegistry(), clock=clock,
            config=RolloutConfig(rollback_windows=1))
        # fast spike without slow-window confirmation must not act
        assert ctl.tick(burns=_burns(50.0, 0.2)) == "hold"
        assert router.candidate == "v2"

    def test_promotion_after_healthy_windows(self):
        _, router = _staged_pair()
        clock = _FakeClock()
        ctl = RolloutController(
            router, metrics=MetricsRegistry(), clock=clock,
            config=RolloutConfig(promote_windows=3))
        for _ in range(2):
            assert ctl.tick(burns=_burns(0.0, 0.0)) == "hold"
            clock.t += 1
        assert ctl.tick(burns=_burns(0.0, 0.0)) == "promote"
        assert router.active == "v2"
        assert ctl.events[-1]["kind"] == "promote"

    def test_flap_degrades_healthz(self):
        from mmlspark_tpu.obs.fleet import FleetAggregator, FleetHealth

        _, router = _staged_pair()
        reg = MetricsRegistry()
        health = FleetHealth(FleetAggregator(MetricsRegistry()),
                             registry=reg)
        clock = _FakeClock()
        ctl = RolloutController(
            router, metrics=reg, clock=clock, health=health,
            config=RolloutConfig(rollback_windows=1, flap_s=5.0))
        assert health.tick() == "ok"
        assert ctl.tick(burns=_burns(50.0, 10.0)) == "rollback"
        # degraded (not critical) while traffic snaps back
        verdict = health.tick()
        assert verdict == "degraded"
        status, body = health.healthz_payload()
        assert status == 200 and b"deploy rollback flap" in body
        # the flap window expires and the fleet reads ok again
        clock.t += 6.0
        assert health.tick() == "ok"


# ------------------------------------------- serving integration
def _post(url, payload, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, r.headers.get("X-Model-Version"), r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("X-Model-Version"), e.read()


def _version_pipeline(tag):
    from mmlspark_tpu.io.http import string_to_response

    def pipeline(df):
        replies = np.empty(len(df), object)
        for i, r in enumerate(df["request"]):
            body = json.loads(r.entity)
            replies[i] = string_to_response(f"{tag}:{body['x']}")
        return df.with_column("reply", replies)
    return pipeline


class TestServingIntegration:
    def test_version_header_flip_and_drain(self):
        from mmlspark_tpu.serving.server import serving_query

        mreg = ModelRegistry(service="hdr-test",
                             registry=MetricsRegistry())
        mreg.register("v1", transform=_version_pipeline("v1"))
        mreg.register("v2", transform=_version_pipeline("v2"))
        router = _router(mreg, service="hdr-test")
        router.set_active("v1")
        q = serving_query("hdr-test", _version_pipeline("v0"),
                          backend="python", router=router)
        host, port = q.server.address
        url = f"http://{host}:{port}/"
        try:
            status, ver, body = _post(url, {"x": 7})
            assert (status, ver, body) == (200, "v1", b"v1:7")
            # stage + one atomic flip: next admission sees only v2
            router.stage("v2")
            router.flip()
            status, ver, body = _post(url, {"x": 8})
            assert (status, ver, body) == (200, "v2", b"v2:8")
            assert router.draining_inflight() == 0
            # the deploy debug route reports the router state
            with urllib.request.urlopen(
                    f"http://{host}:{port}/debug/deploy",
                    timeout=10) as r:
                state = json.loads(r.read())
            assert state["active"] == "v2" and state["prior"] == "v1"
        finally:
            q.stop()

    def test_model_bad_injected_5xx_carries_version(self):
        from mmlspark_tpu.serving.server import serving_query

        mreg = ModelRegistry(service="bad-test",
                             registry=MetricsRegistry())
        mreg.register("v1", transform=_version_pipeline("v1"))
        router = _router(mreg, service="bad-test")
        router.set_active("v1")
        q = serving_query("bad-test", _version_pipeline("v0"),
                          backend="python", router=router)
        host, port = q.server.address
        url = f"http://{host}:{port}/"
        try:
            rules = [FaultRule(point="model.bad", kind="error",
                               match="v1", status=503)]
            with faults(7, rules):
                status, ver, _ = _post(url, {"x": 1})
            assert (status, ver) == (503, "v1")
            # disarmed: the same version serves again
            status, ver, body = _post(url, {"x": 2})
            assert (status, ver, body) == (200, "v1", b"v1:2")
            assert router.draining_inflight() == 0
        finally:
            q.stop()


# ---------------------------------------------- aot gc protection
def _fake_entry(store, full, static):
    store.save(full_fp=full * 64, static_fp=static * 64,
               segment_name=f"seg-{static}",
               meta_extra={"versions": "stale-jax/0.0"},
               blob=None, hlo_text=None)


class TestAotGcProtection:
    def test_gc_never_removes_rollback_target(self, tmp_path):
        """The regression the deploy plane exists to prevent: a gc
        running MID-DEPLOY (old version draining, new one active)
        must never collect either side, whatever keep_static says."""
        store = AotStore(str(tmp_path / "store"))
        mreg = ModelRegistry(root=store.root, service="gc-test",
                             registry=MetricsRegistry())
        mreg.register("v0", static_fps=("c" * 64,))     # pre-history
        mreg.register("v1", static_fps=("a" * 64,))     # rollback target
        mreg.register("v2", static_fps=("b" * 64,))
        mreg.set_state("v1", DRAINING)
        mreg.set_state("v2", ACTIVE)
        _fake_entry(store, "1", "a")
        _fake_entry(store, "2", "b")
        _fake_entry(store, "3", "c")
        before = _process_reg.snapshot().get(
            "aot_gc_kept_versions", 0)
        # every entry is stale (version-mismatched AND not in
        # keep_static) — yet the deploy-state fingerprints survive
        removed = store.gc(keep_static=set())
        assert [fp[:1] for fp in removed] == ["3"]
        left = {m["static_fp"] for m in store.entries()}
        assert left == {"a" * 64, "b" * 64}
        assert _process_reg.snapshot()["aot_gc_kept_versions"] \
            == before + 2

    def test_gc_keep_versions_pins_rollback_horizon(self, tmp_path):
        store = AotStore(str(tmp_path / "store"))
        mreg = ModelRegistry(root=store.root, service="gc-test",
                             registry=MetricsRegistry())
        mreg.register("v0", static_fps=("c" * 64,))
        mreg.register("v1", static_fps=("a" * 64,))
        mreg.set_state("v1", ACTIVE)
        _fake_entry(store, "1", "a")
        _fake_entry(store, "3", "c")
        # keep-last-2 pins v0 too, even though it is out of deploy
        assert store.gc(keep_static=set(),
                        keep_model_versions=2) == []
        # without the horizon, only the deploy-state entry survives
        removed = store.gc(keep_static=set())
        assert [fp[:1] for fp in removed] == ["3"]

    def test_cli_list_and_gc_keep_versions(self, tmp_path):
        root = str(tmp_path / "store")
        store = AotStore(root)
        mreg = ModelRegistry(root=root, service="cli-test",
                             registry=MetricsRegistry())
        mreg.register("v1", static_fps=("a" * 64,))
        mreg.set_state("v1", ACTIVE)
        mreg.register("v2", static_fps=("b" * 64,))
        _fake_entry(store, "1", "a")
        _fake_entry(store, "2", "b")
        env = cpu_child_env()
        out = subprocess.run(
            [sys.executable, "-m", "mmlspark_tpu.core.aot", "list",
             "--root", root],
            capture_output=True, text=True, cwd=REPO, env=env,
            timeout=600)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "registry versions:" in out.stdout
        assert "v1" in out.stdout and "active" in out.stdout
        out = subprocess.run(
            [sys.executable, "-m", "mmlspark_tpu.core.aot", "gc",
             "--root", root, "--keep-static", "f" * 64,
             "--keep-versions", "2"],
            capture_output=True, text=True, cwd=REPO, env=env,
            timeout=600)
        assert out.returncode == 0, out.stdout + out.stderr
        # both versions pinned (deploy state + rollback horizon)
        assert "removed 0" in out.stdout


# ------------------------------------------- loadgen version split
class TestLoadgenVersionSplit:
    def test_summarize_splits_per_version(self):
        from mmlspark_tpu.serving.loadgen import summarize

        nreq = 30
        lat = np.full((1, nreq), 5.0)
        lat[0, 20:] = 9.0                  # v2 serves slower
        status = np.full((1, nreq), 200)
        status[0, 25] = 500                # one v2 error
        versions = np.empty((1, nreq), object)
        versions[0, :20] = "v1"
        versions[0, 20:] = "v2"
        out = summarize(lat, status, wall_s=1.0, warmup=5,
                        versions=versions)
        v = out["versions"]
        assert set(v) == {"v1", "v2"}
        assert v["v1"]["n"] == 15 and v["v1"]["errors"] == 0
        assert v["v1"]["p50_ms"] == 5.0
        assert v["v2"]["n"] == 10 and v["v2"]["errors"] == 1
        assert v["v2"]["p50_ms"] == 9.0
        assert v["v2"]["error_rate"] == pytest.approx(0.1)

    def test_summarize_without_versions_unchanged(self):
        from mmlspark_tpu.serving.loadgen import summarize

        lat = np.full((1, 30), 5.0)
        status = np.full((1, 30), 200)
        out = summarize(lat, status, wall_s=1.0, warmup=5)
        # unversioned runs keep the key (same shape as "tenants"),
        # just empty — nothing invents a version label
        assert out["versions"] == {}


# ------------------------------------------ the rollout acceptance
class TestRolloutScenario:
    def test_rollout_acceptance_and_reproducibility(self):
        """ISSUE 19 acceptance: the blue/green flip rolls across the
        autoscaled mixed-tenant fleet with zero non-canary 5xx, zero
        dropped in-flight requests (worker kill included), every
        request answered byte-identically by its admitting version,
        the drain gauge at 0 and zero runtime compiles; the seeded
        bad canary rolls back from burn rate alone within bounded
        ticks with the gold tier untouched; and the same seed
        realizes the same fault schedule."""
        from mmlspark_tpu.testing.benchmarks import rollout_scenario

        runs = [rollout_scenario(registry=MetricsRegistry(),
                                 service=f"rollout-t{i}")
                for i in range(2)]
        for r in runs:
            assert r["rollout_zero_5xx"], r["non_canary_5xx"]
            assert r["drained_completed"] and r["unanswered"] == 0
            assert r["byte_identical"], r["version_mismatches"]
            assert r["drained_to_zero"], r["draining_inflight_final"]
            assert r["zero_runtime_compiles"], r["runtime_compiles"]
            assert r["worker_killed"] and r["lease_replays"] >= 1
            assert r["rolled_back"], r["deploy_log"]
            assert r["rollback_ticks"] <= 80, r["rollback_ticks"]
            assert r["rollback_reason"] == "burn"
            assert r["active_after"] == "v2"
            assert r["candidate_after"] is None
            assert r["canary_5xx"] >= 1
            assert r["canary_gold_sheds"] == 0
            assert r["gold_unharmed"], r["per_tenant"].get("cognitive")
            assert r["workers_peak"] >= 2
        assert runs[0]["schedule"] == runs[1]["schedule"], \
            "same seed must realize the same fault schedule"


# ------------------------------------------------------ no-JAX smoke
def test_deploy_plane_imports_without_jax():
    """The deploy plane is control-plane code: registry + router flip
    + controller tick with no JAX in the process (CI runs the same
    smoke in its style job)."""
    code = (
        "import sys\n"
        "from mmlspark_tpu.serving.deploy import (ModelRegistry, "
        "RolloutConfig, RolloutController, VersionRouter)\n"
        "from mmlspark_tpu.obs.metrics import MetricsRegistry\n"
        "assert 'jax' not in sys.modules, 'deploy import pulled jax'\n"
        "reg = MetricsRegistry()\n"
        "m = ModelRegistry(service='smoke', registry=reg)\n"
        "m.register('v1', transform=lambda b: b)\n"
        "m.register('v2', transform=lambda b: b)\n"
        "r = VersionRouter(m, service='smoke', metrics=reg)\n"
        "r.set_active('v1'); r.stage('v2')\n"
        "assert r.flip() == 'v2' and r.active == 'v2'\n"
        "c = RolloutController(r, metrics=reg, "
        "config=RolloutConfig(rollback_windows=1))\n"
        "assert c.tick(burns={}) == 'idle'\n"
        "assert 'jax' not in sys.modules, 'deploy plane pulled jax'\n"
        "print('deploy plane OK (no jax)')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env=cpu_child_env(), timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "deploy plane OK (no jax)" in out.stdout
