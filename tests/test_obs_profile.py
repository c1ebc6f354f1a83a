"""Cross-process tracing + continuous profiler (ISSUE 8): trace
propagation (headers, scheduler thread handoff, worker mesh), the
flight recorder / Chrome-trace export / GET /debug/trace surface, the
CompileTracker's recompile flags, the StepProfiler's host/device
attribution, the cost-model feature log, and the profiler-overhead
bench guard.
"""

import json
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.obs import (Span, TraceContext, chrome_trace, extract,
                              inject, registry, tracer)
from mmlspark_tpu.obs.export import (FlightRecorder, SpanCollector,
                                     debug_trace_payload)
from mmlspark_tpu.obs.profile import (CompileTracker, FeatureLog,
                                      StepProfiler)
from mmlspark_tpu.obs.propagation import (format_traceparent,
                                          span_from_dict)


class TestPropagation:
    def test_inject_extract_round_trip(self):
        with tracer.span("root") as root:
            headers = inject({}, root)
        ctx = extract(headers)
        assert ctx == TraceContext(root.trace_id, root.span_id)

    def test_inject_uses_ambient_span(self):
        with tracer.span("ambient") as sp:
            headers = inject({"Content-Type": "application/json"})
            assert extract(headers).trace_id == sp.trace_id
        # no ambient trace → no header is invented
        assert "traceparent" not in inject({})

    def test_extract_is_case_insensitive_and_safe(self):
        assert extract({"Traceparent": "00-abc123-def456-01"}) == \
            TraceContext("abc123", "def456")
        # malformed forms degrade to None, never raise
        for bad in ("", "xx", "00-abc123-01", "00-ab cd-ef-01",
                    "00-xyz!-def-01", "a-b-c-d-e"):
            assert extract({"traceparent": bad}) is None
        assert extract({}) is None
        assert extract(None) is None

    def test_remote_context_parents_local_span(self):
        ctx = extract({"traceparent": "00-cafe01-beef02-01"})
        sp = tracer.start_span("child", parent=ctx, current=False)
        tracer.end_span(sp, emit=False)
        assert sp.trace_id == "cafe01"
        assert sp.parent_id == "beef02"

    def test_span_ids_are_traceparent_safe_hex(self):
        with tracer.span("hexcheck") as sp:
            pass
        for token in (sp.trace_id, sp.span_id):
            assert token and all(c in "0123456789abcdef" for c in token)
        # format → extract round-trips through the actual header shape
        assert extract(
            {"traceparent": format_traceparent(sp)}).trace_id == \
            sp.trace_id

    def test_span_wire_round_trip(self):
        with tracer.span("wire", service="svc") as sp:
            pass
        back = span_from_dict(sp.to_dict())
        assert (back.name, back.trace_id, back.span_id, back.parent_id,
                back.proc) == (sp.name, sp.trace_id, sp.span_id,
                               sp.parent_id, sp.proc)
        assert back.attrs["service"] == "svc"

    def test_emit_span_retroactive_parentage_and_sink(self):
        got = []
        tracer.add_sink(got.append)
        try:
            with tracer.span("root") as root:
                pass
            retro = tracer.emit_span("queue.wait", parent=root,
                                     seconds=0.25, service="s")
        finally:
            tracer.remove_sink(got.append)
        assert retro.trace_id == root.trace_id
        assert retro.parent_id == root.span_id
        assert retro.seconds == 0.25
        # start_wall back-dates by the duration (< root would be wrong)
        assert retro.start_wall <= root.start_wall + (root.seconds or 0) \
            + 1.0
        assert any(s.name == "queue.wait" for s in got)

    def test_scheduler_thread_handoff_preserves_trace(self):
        """A request span survives submit (front thread) → next_batch
        (executor thread): the scheduler stamps queue_wait and emits a
        sched.queue child span under the request's trace."""
        from mmlspark_tpu.sched import RequestScheduler

        class Item:
            pass

        sched = RequestScheduler("handoff-test")
        item = Item()
        item.span = tracer.start_span("serving.request", parent=None,
                                      current=False)
        got = {}

        def executor():
            with SpanCollector() as col:
                batch = sched.next_batch(max_batch=4, max_wait=5.0)
                got["batch"] = batch
                got["spans"] = col.spans()

        t = threading.Thread(target=executor)
        t.start()
        time.sleep(0.05)
        sched.submit(item)
        t.join(timeout=10)
        assert got["batch"] == [item]
        assert item.queue_wait is not None and item.queue_wait >= 0
        queue_spans = [s for s in got["spans"]
                       if s["name"] == "sched.queue"]
        assert len(queue_spans) == 1
        assert queue_spans[0]["traceId"] == item.span.trace_id
        assert queue_spans[0]["parentId"] == item.span.span_id
        tracer.end_span(item.span, emit=False)


class TestChromeTraceExport:
    def test_chrome_trace_shape(self):
        with tracer.span("outer") as outer:
            with tracer.span("inner"):
                pass
        ct = chrome_trace([outer.to_dict()])
        (ev,) = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        assert ev["name"] == "outer"
        assert ev["dur"] == pytest.approx(outer.seconds * 1e6)
        assert ev["ts"] == pytest.approx(outer.start_wall * 1e6)
        assert ev["args"]["traceId"] == outer.trace_id
        metas = [e for e in ct["traceEvents"] if e["ph"] == "M"]
        assert metas and metas[0]["name"] == "process_name"
        assert ct["displayTimeUnit"] == "ms"

    def test_cross_process_spans_get_distinct_pids(self):
        a = Span(name="a", trace_id="t1", span_id="s1", proc="aaa",
                 seconds=0.1)
        b = Span(name="b", trace_id="t1", span_id="s2", proc="bbb",
                 seconds=0.1)
        ct = chrome_trace([a, b])
        pids = {e["pid"] for e in ct["traceEvents"] if e["ph"] == "X"}
        assert len(pids) == 2


class TestFlightRecorder:
    def _span(self, trace_id, name="s", span_id=None, err=None):
        return {"name": name, "traceId": trace_id,
                "spanId": span_id or f"{trace_id}-{name}",
                "parentId": None, "startWall": 1.0, "seconds": 0.01,
                "proc": "p", "error": err}

    def test_keeps_slowest_n(self):
        rec = FlightRecorder(keep_slowest=2, keep_errored=2,
                             registry=type(registry)())
        for i, secs in enumerate((0.01, 0.5, 0.02, 0.9, 0.03)):
            t = f"t{i}"
            rec.ingest([self._span(t)])
            rec.note_request(t, secs, status=200)
        kept = {t["trace_id"]: t["seconds"] for t in rec.trees()}
        assert kept == {"t1": 0.5, "t3": 0.9}

    def test_errored_always_kept_and_bounded(self):
        rec = FlightRecorder(keep_slowest=1, keep_errored=2,
                             registry=type(registry)())
        for i in range(4):
            t = f"e{i}"
            rec.ingest([self._span(t)])
            rec.note_request(t, 0.001, status=500)
        kept = [t["trace_id"] for t in rec.trees()]
        assert sorted(kept) == ["e2", "e3"]  # FIFO-bounded errored set
        assert all(t["error"] for t in rec.trees())

    def test_late_remote_spans_complete_a_kept_tree(self):
        """The mesh race: note_request fires when the driver-side span
        closes; a worker's spans may arrive in the same reply payload
        or (pathologically) after — both must land in the kept tree."""
        rec = FlightRecorder(keep_slowest=4, registry=type(registry)())
        rec.ingest([self._span("tr", "serving.request")])
        rec.note_request("tr", 0.1, status=200)
        rec.ingest([self._span("tr", "worker.execute")])
        tree = rec.tree("tr")
        assert {s["name"] for s in tree["spans"]} == \
            {"serving.request", "worker.execute"}

    def test_ingest_dedups_by_span_id(self):
        rec = FlightRecorder(registry=type(registry)())
        d = self._span("td")
        rec.ingest([d])
        rec.ingest([d])
        rec.note_request("td", 0.1)
        assert len(rec.tree("td")["spans"]) == 1

    def test_pending_is_bounded(self):
        rec = FlightRecorder(max_pending=8, registry=type(registry)())
        for i in range(64):
            rec.ingest([self._span(f"p{i}")])
        with rec._lock:
            assert len(rec._pending) <= 8

    def test_lone_root_spans_do_not_evict_request_trees(self):
        """Regression: the steady stream of one-span root traces (an
        outbound http.send with no ambient parent) overflowing pending
        must not flush a multi-span in-flight request tree — the slow
        request the recorder exists to keep."""
        rec = FlightRecorder(max_pending=4, registry=type(registry)())
        rec.ingest([self._span("req1", "serving.request"),
                    self._span("req1", "sched.queue")])
        for i in range(32):  # a flood of lone http.send roots
            rec.ingest([self._span(f"send{i}", "http.send")])
        rec.note_request("req1", 9.9, status=200)
        tree = rec.tree("req1")
        assert tree is not None
        assert {s["name"] for s in tree["spans"]} == \
            {"serving.request", "sched.queue"}

    def test_debug_trace_payload_is_perfetto_loadable_json(self):
        rec = FlightRecorder(registry=type(registry)())
        rec.ingest([self._span("tp", "serving.request")])
        rec.note_request("tp", 0.2, status=200)
        payload = json.loads(debug_trace_payload(rec))
        assert payload["kept"] == 1
        assert payload["traces"][0]["trace_id"] == "tp"
        assert any(e.get("args", {}).get("traceId") == "tp"
                   for e in payload["traceEvents"])


class TestCompileTracker:
    def test_flags_shape_unstable_fn_and_counts_hits(self):
        """ISSUE 8 acceptance: an intentionally shape-unstable jitted
        fn shows recompile count >= 2; a shape-stable one stays at 1
        compile with hits after warmup."""
        import jax.numpy as jnp

        from mmlspark_tpu.parallel import compat

        reg = type(registry)()
        tracker = CompileTracker(registry=reg)

        unstable = tracker.jit(lambda x: (x * 2).sum(), name="unstable")
        stable = tracker.jit(lambda x: x + 1, name="stable")
        for n in (4, 8, 16):  # novel shape every call
            unstable(jnp.ones((n,)))
        for _ in range(3):
            stable(jnp.ones((4,)))
        assert tracker.compiles("unstable") >= 2
        assert tracker.unstable() == {"unstable":
                                      tracker.compiles("unstable")}
        assert tracker.compiles("stable") == 1
        snap = reg.snapshot()
        assert snap['profile_jit_calls_total{fn="stable",'
                    'outcome="hit"}'] == 2
        assert snap['profile_jit_calls_total{fn="stable",'
                    'outcome="miss"}'] == 1
        assert snap['profile_compiles_total{fn="unstable"}'] >= 2
        assert snap['profile_compile_seconds_count{fn="unstable"}'] \
            >= 2
        # compat.jit routes through the process-wide tracker with the
        # same semantics (the call-site surface dl/train uses)
        f = compat.jit(lambda x: x * 3, name="compat_smoke_fn")
        f(jnp.ones((2,)))
        from mmlspark_tpu.obs import compile_tracker
        assert compile_tracker.compiles("compat_smoke_fn") == 1

    def test_jit_kwargs_and_result_pass_through(self):
        import jax.numpy as jnp

        tracker = CompileTracker(registry=type(registry)())
        f = tracker.jit(lambda x: x * 2, name="passthrough")
        out = f(jnp.asarray([1.0, 2.0]))
        assert np.allclose(np.asarray(out), [2.0, 4.0])
        assert callable(getattr(f, "lower", None))  # AOT escape hatch

    def test_train_step_is_tracked(self):
        """dl.make_train_step routes through compat.jit: one compile,
        then hits — steady-state training shows zero recompiles."""
        pytest.importorskip("flax")
        import jax
        import optax
        from flax import linen as nn

        from mmlspark_tpu.dl.train import init_train_state, \
            make_train_step
        from mmlspark_tpu.obs import compile_tracker

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, train=True):
                return nn.Dense(3)(x)

        tx = optax.sgd(0.1)
        state = init_train_state(Tiny(), jax.random.PRNGKey(0),
                                 np.zeros((4, 5), np.float32), tx)
        step = make_train_step(Tiny(), tx)
        before = compile_tracker.compiles("train_step")
        x = np.zeros((4, 5), np.float32)
        y = np.zeros((4,), np.int32)
        state, _ = step(state, x, y)
        state, _ = step(state, x, y)
        assert compile_tracker.compiles("train_step") == before + 1


class TestProgramNamesAndBuildLedger:
    """ISSUE 37: the name a call site gives is the name XLA has, and
    JAX's own compile events keep a ledger of what was built."""

    def test_the_label_is_the_lowered_modules_name(self):
        import jax.numpy as jnp

        tracker = CompileTracker(registry=type(registry)())
        f = tracker.jit(lambda x: x * 2, name="llm_step_bench-x.y_S4_w32")
        text = f.lower(jnp.ones((2,))).as_text()
        assert "module @jit_llm_step_bench_x_y_S4_w32 " in text
        assert f.__name__ == "llm_step_bench_x_y_S4_w32"
        # the tracker's own series keep the label as given
        assert f.__tracked_label__ == "llm_step_bench-x.y_S4_w32"

        def plain(x):
            return x + 1

        assert "module @jit_plain " in tracker.jit(plain).lower(
            jnp.ones((2,))).as_text()

    def test_train_step_is_the_modules_name(self):
        pytest.importorskip("flax")
        import jax
        import optax
        from flax import linen as nn

        from mmlspark_tpu.dl.train import init_train_state, \
            make_train_step

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, train=True):
                return nn.Dense(3)(x)

        tx = optax.sgd(0.1)
        state = init_train_state(Tiny(), jax.random.PRNGKey(0),
                                 np.zeros((4, 5), np.float32), tx)
        text = make_train_step(Tiny(), tx).lower(
            state, np.zeros((4, 5), np.float32),
            np.zeros((4,), np.int32)).as_text()
        assert "module @jit_train_step " in text

    def test_ledger_of_a_tracked_and_an_untracked_jit(self):
        """Trace, lower and backend seconds of both; a second ``lower``
        of the same arguments traces again and lowers nothing; a new
        shape does both again; a jitted function called inside is no
        entry of its own."""
        import jax
        import jax.numpy as jnp

        tracker = CompileTracker(registry=type(registry)())
        silent = CompileTracker(registry=type(registry)())
        tracker.listen()
        tracker.listen()                    # once, whoever asks again

        @jax.jit
        def ledger_inner(x):
            return jnp.sin(x)

        def body(x):
            return ledger_inner(x) * 2

        tracked = tracker.jit(body, name="ledger-tracked")

        def ledger_untracked(x):
            return x + 3

        untracked = jax.jit(ledger_untracked)
        tracked(jnp.ones((4,)))
        untracked(jnp.ones((4,)))
        # only the process-wide tracker listens unasked
        from mmlspark_tpu.obs import compile_tracker
        silent.jit(lambda x: x - 1, name="ledger_silent")(jnp.ones((4,)))
        assert silent.ledger() == [] and not silent._listening
        assert "ledger_silent" in {e["fn"] for e in compile_tracker.ledger()}
        by_fn = {e["fn"]: e for e in tracker.ledger()}
        assert "ledger_inner" not in by_fn
        for fn in ("ledger_tracked", "ledger_untracked"):
            e = by_fn[fn]
            assert (e["traced"], e["lowered"]) == (1, 1)
            assert e["compiled"] + e["loaded"] == 1
            assert min(e["trace_s"], e["lower_s"], e["backend_s"]) > 0
        order = [e["fn"] for e in tracker.ledger()]
        assert order.index("ledger_tracked") \
            < order.index("ledger_untracked")
        before = by_fn["ledger_tracked"]
        tracked.lower(jnp.ones((4,)))       # the same arguments
        tracked.lower(jnp.ones((6,)))       # a shape it has not seen
        tracked(jnp.ones((4,)))             # a hit: nothing is built
        after = next(e for e in tracker.ledger()
                     if e["fn"] == "ledger_tracked")
        assert (after["traced"], after["lowered"]) == (3, 2)
        assert after["compiled"] + after["loaded"] == 1
        assert after["lower_s"] > before["lower_s"]
        # a read is a copy
        tracker.ledger()[0]["traced"] = 99
        assert tracker.ledger()[0]["traced"] != 99

    def test_ledger_says_loaded_for_a_program_the_cache_held(self,
                                                             tmp_path):
        """The same program built twice against a persistent cache: the
        backend step compiles it, then loads it."""
        import jax
        import jax.numpy as jnp
        from jax.experimental.compilation_cache import compilation_cache

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
        was = {k: getattr(jax.config, k) for k in keys}
        tracker = CompileTracker(registry=type(registry)())
        tracker.listen()
        try:
            for k, v in zip(keys, (str(tmp_path), 0.0, 0)):
                jax.config.update(k, v)
            compilation_cache.reset_cache()
            for _ in range(2):              # a new function each time
                f = tracker.jit(lambda x: jnp.cos(x) * 5 + 2,
                                name="ledger_built_twice")
                f(jnp.ones((3,)))
        finally:
            for k, v in was.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
        e = next(e for e in tracker.ledger()
                 if e["fn"] == "ledger_built_twice")
        assert (e["traced"], e["lowered"]) == (2, 2)
        assert (e["compiled"], e["loaded"]) == (1, 1)

    def test_importing_obs_alone_imports_no_jax(self):
        import subprocess
        import sys
        code = ("import sys; import mmlspark_tpu.obs; "
                "from mmlspark_tpu.obs import compile_tracker as t; "
                "assert 'jax' not in sys.modules; "
                "assert t.ledger() == [] and not t._listening; "
                "import jax; import importlib; "
                "import mmlspark_tpu.obs.profile as p; importlib.reload(p); "
                "assert p.compile_tracker._listening")
        subprocess.run([sys.executable, "-c", code], check=True,
                       timeout=120)


class TestStepProfiler:
    def test_dispatch_device_split_and_spans(self):
        import jax.numpy as jnp

        reg = type(registry)()
        prof = StepProfiler(service="t", registry=reg)
        with SpanCollector() as col:
            with tracer.span("request") as root:
                with prof.step("matmul",
                               flops=2 * 32 * 32 * 32) as h:
                    h.done(jnp.ones((32, 32)) @ jnp.ones((32, 32)))
        snap = reg.snapshot()
        assert snap['profile_steps_total{stage="matmul"}'] == 1
        assert snap['profile_step_seconds_count{phase="device",'
                    'stage="matmul"}'] == 1
        assert snap['profile_step_seconds_count{phase="dispatch",'
                    'stage="matmul"}'] == 1
        # the MFU gauge carries the PeakSpec platform it was computed
        # against (obs.attribution) — tier-1 pins JAX_PLATFORMS=cpu
        assert snap['profile_mfu{platform="cpu",stage="matmul"}'] > 0
        spans = {s["name"]: s for s in col.spans()}
        assert spans["profile.dispatch"]["traceId"] == root.trace_id
        assert spans["profile.dispatch"]["parentId"] == root.span_id
        assert spans["profile.device"]["parentId"] == \
            spans["profile.dispatch"]["spanId"]
        assert spans["profile.device"]["attrs"]["synced"] is True

    def test_block_on_string_data_terminates(self):
        """Regression: a str iterates to itself — _block_on must cut
        scalars/strings off before the generic __iter__ recursion, or
        every object column holding text (mesh 'id' columns, replies)
        dies in RecursionError and device attribution silently breaks."""
        from mmlspark_tpu.obs.profile import _block_on

        assert _block_on("hello") is False
        assert _block_on(b"bytes") is False
        assert _block_on(np.array(["a", "bb"], dtype=object)) is False
        assert _block_on({"col": ["text", 1, None]}) is False
        prof = StepProfiler(registry=type(registry)())
        with prof.step("textstage") as h:  # must not raise
            h.done(np.array(["x" * 50] * 100, dtype=object))

    def test_host_only_step_reports_unsynced(self):
        prof = StepProfiler(registry=type(registry)())
        with SpanCollector() as col:
            with prof.step("hostwork") as h:
                h.done([1, 2, 3])
        (dev,) = [s for s in col.spans()
                  if s["name"] == "profile.device"]
        assert dev["attrs"]["synced"] is False

    def test_pipeline_profiling_hook(self):
        """PipelineModel.transform routes stages through the profiler
        when enabled, and is untouched (no step series) when not."""
        from mmlspark_tpu.core import DataFrame
        from mmlspark_tpu.obs import profile as obs_profile
        from mmlspark_tpu.stages import RenameColumn, SelectColumns
        from mmlspark_tpu.core.pipeline import PipelineModel

        df = DataFrame({"a": np.arange(4), "b": np.arange(4)})
        model = PipelineModel([
            RenameColumn(inputCol="a", outputCol="c"),
            SelectColumns(cols=["c"])])
        reg = type(registry)()
        prof = StepProfiler(registry=reg)
        try:
            obs_profile.enable_pipeline_profiling(prof)
            out = model.transform(df)
        finally:
            obs_profile.disable_pipeline_profiling()
        assert out.columns == ["c"]
        snap = reg.snapshot()
        assert snap['profile_steps_total{stage="RenameColumn"}'] == 1
        assert snap['profile_steps_total{stage="SelectColumns"}'] == 1
        # disabled again: no new observations
        model.transform(df)
        assert reg.snapshot() == snap


class TestFeatureLog:
    def test_bounded_ring_and_snapshot(self):
        log = FeatureLog(maxlen=4, registry=type(registry)())
        for i in range(10):
            log.record(service="s", route="/", batch=i)
        snap = log.snapshot()
        assert len(snap) == 4 and len(log) == 4
        assert [r["batch"] for r in snap] == [6, 7, 8, 9]
        log.clear()
        assert len(log) == 0

    def test_serving_executor_records_features(self):
        """One record per served request with the learned-model feature
        schema (route, batch/bucket, queue/execute ms, trace id)."""
        from mmlspark_tpu.io.http.schema import HTTPResponseData
        from mmlspark_tpu.obs.profile import feature_log
        from mmlspark_tpu.serving.server import serving_query

        import http.client

        def transform(df):
            replies = np.empty(len(df), object)
            replies[:] = [HTTPResponseData(status_code=200,
                                           entity=b"ok")] * len(df)
            return df.with_column("reply", replies)

        feature_log.clear()
        query = serving_query("feat-e2e", transform, backend="python")
        addr = query.server.address
        try:
            conn = http.client.HTTPConnection(*addr, timeout=10)
            for _ in range(3):
                conn.request("POST", "/", body=b"xy")
                assert conn.getresponse().read() == b"ok"
            conn.close()
        finally:
            query.stop()
        records = [r for r in feature_log.snapshot()
                   if r.get("service") == "feat-e2e"]
        assert len(records) == 3
        for r in records:
            assert r["route"] == "/"
            assert r["bucket"] >= r["batch"] >= 1
            assert r["queue_ms"] >= 0 and r["execute_ms"] >= 0
            assert r["entity_bytes"] == 2
            assert r["trace_id"]


class TestLoadgenTraceIds:
    def test_summarize_reports_p99_slowest_trace_ids(self):
        from mmlspark_tpu.serving.loadgen import summarize, trace_id_of

        lat = np.asarray([[5.0, 5.0, 3.0, 50.0, 2.0, 5.0],
                          [4.0, 5.0, 90.0, 5.0, 5.0, 429.0]])
        st = np.asarray([[200, 200, 200, 200, 200, 200],
                         [200, 200, 200, 200, 200, 429]])
        r = summarize(lat, st, wall_s=1.0, warmup=0,
                      trace_prefix="abc0")
        assert r["slowest"], "no slow trace ids reported"
        # the single slowest success is conn 1, req 2 (90 ms); the 429
        # never qualifies even though its recorded latency is huge
        assert r["slowest"][0]["trace_id"] == trace_id_of("abc0", 1, 2)
        assert r["slowest"][0]["ms"] == pytest.approx(90.0)
        ids = {s["trace_id"] for s in r["slowest"]}
        assert trace_id_of("abc0", 1, 5) not in ids

    def test_summarize_trace_ids_respect_warmup_offset(self):
        from mmlspark_tpu.serving.loadgen import summarize, trace_id_of

        lat = np.asarray([[1.0, 1.0, 1.0, 99.0]])
        st = np.asarray([[200, 200, 200, 200]])
        r = summarize(lat, st, wall_s=1.0, warmup=2,
                      trace_prefix="dd")
        # slot 3 in the FULL matrix (warmup excluded from stats, but
        # the id must name the request as actually sent)
        assert r["slowest"][0]["trace_id"] == trace_id_of("dd", 0, 3)

    def test_summarize_without_prefix_keeps_quiet(self):
        from mmlspark_tpu.serving.loadgen import summarize

        lat = np.asarray([[1.0, 2.0]])
        st = np.asarray([[200, 200]])
        assert summarize(lat, st, wall_s=1.0, warmup=0)["slowest"] == []


class TestDeprecationShim:
    def test_utils_profiling_warns_and_reexports(self):
        import importlib
        import sys
        import warnings

        sys.modules.pop("mmlspark_tpu.utils.profiling", None)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            mod = importlib.import_module("mmlspark_tpu.utils.profiling")
        assert any(issubclass(w.category, DeprecationWarning)
                   for w in caught)
        from mmlspark_tpu.obs.profile import profile_trace, profiled
        assert mod.profile_trace is profile_trace
        assert mod.profiled is profiled

    def test_utils_package_import_does_not_warn(self):
        import warnings

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            from mmlspark_tpu.utils import StageTimer  # noqa: F401
        assert not any(issubclass(w.category, DeprecationWarning)
                       for w in caught)


class TestOverheadGuard:
    #: CPU microseconds one started-and-ended span may cost this thread:
    #: ten times the 3.3 measured on the builder's box (PR 26), so only
    #: a span path grown by an order of magnitude trips it
    SPAN_COST_LIMIT_US = 35.0

    def test_tracing_profiler_overhead_within_5pct(self):
        """What tracing costs a served request, by what does not depend
        on the box. The test used to compare two p99 wall times (ON
        within 5 % of OFF) and failed whenever six xdist workers shared
        the cores. It now guards (a) that the ON run of the serving
        scenario really traced: per request one ``serving.request``, one
        ``sched.queue`` and one ``serving.execute`` span and one
        feature-log record, and (b) the cost of a span itself: 20,000
        spans in a tight loop, on this thread's CPU clock
        (``time.thread_time`` does not run while the thread waits for a
        core), under ten times the measured cost. A request of the
        scenario takes 5 ms and finishes 5 spans, so the limit bounds
        tracing at 3.5 % of it. The wall-time ratio is still banked by
        ``bench.py`` and not asserted here."""
        from mmlspark_tpu.obs import feature_log
        from mmlspark_tpu.obs.tracing import Tracer, now_ns
        from mmlspark_tpu.testing.benchmarks import \
            tracing_overhead_scenario

        n = 40
        mark, records = now_ns(), len(feature_log)
        r = tracing_overhead_scenario(service="tracing-guard",
                                      n_requests=n, reps=1)
        assert r["p99_on_s"] > 0 and r["p99_off_s"] > 0
        assert len(feature_log) - records == n  # the ON run really traced
        spans = tracer.recent(since=mark)
        traces = {s.trace_id for s in spans if s.name == "serving.request"
                  and s.attrs.get("service") == "tracing-guard"}
        names = [s.name for s in spans if s.trace_id in traces]
        for name in ("serving.request", "sched.queue", "serving.execute"):
            assert names.count(name) == n, (name, names.count(name))

        tr = Tracer()                       # no sink, telemetry above INFO

        def per_span_us(count=20_000):
            t0 = time.thread_time()
            for i in range(count):
                tr.end_span(tr.start_span("guard", parent=None,
                                          current=False, i=i))
            return (time.thread_time() - t0) / count * 1e6

        cost = min(per_span_us() for _ in range(3))
        assert 0 < cost < self.SPAN_COST_LIMIT_US, cost
        assert 5 * self.SPAN_COST_LIMIT_US * 1e-6 <= \
            0.05 * r["item_service_s"]


class TestChaosTraceAcceptance:
    def test_chaos_run_yields_complete_span_trees(self, tmp_path):
        """ISSUE 8 acceptance: the seeded chaos scenario (worker kill +
        injected 503s/latency) exports a Perfetto/Chrome trace, EVERY
        answered request has a complete cross-process span tree (driver
        queue, worker execute, device — one trace id), and steady-state
        serving shows zero recompiles (no profile_compiles series for
        the serving path)."""
        from mmlspark_tpu.testing.benchmarks import (
            COMPLETE_TRACE_SPANS, chaos_scenario)

        r = chaos_scenario(seed=7, n_requests=20, n_workers=3,
                           error_rate=0.1, trace_dir=str(tmp_path))
        assert r["answered_200"] + r["policy_sheds"] == r["offered"]
        assert r["answered_traces"] == r["answered_200"]
        assert r["complete_traces"] == r["answered_traces"], r
        assert r["sampled_trace"] is not None
        assert COMPLETE_TRACE_SPANS <= set(r["sampled_trace"]["spans"])
        # the exported artifact is real Perfetto-loadable JSON whose
        # sampled trace carries the whole tree under one trace id
        ct = json.loads((tmp_path / "chaos_trace.json").read_text())
        sampled = r["sampled_trace"]["trace_id"]
        names = {e["name"] for e in ct["traceEvents"]
                 if e.get("args", {}).get("traceId") == sampled}
        assert COMPLETE_TRACE_SPANS <= names
        # steady-state serving path: the chaos run jits nothing, so the
        # tracker must show zero serving-side recompiles
        from mmlspark_tpu.obs import compile_tracker
        assert not any(k.startswith("serving")
                       for k in compile_tracker.unstable())
