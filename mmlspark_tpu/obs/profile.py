"""Continuous compile/device profiler + the cost-model feature log.

Three instruments, all always-on-capable (bounded, registry-backed, no
trace files to rotate):

- :class:`CompileTracker` — wraps ``jax.jit`` call sites (route through
  :func:`mmlspark_tpu.parallel.compat.jit`) so every retrace is counted
  and every compile's wall time lands in a histogram, per function.
  This is the RUNTIME counterpart of graftcheck's static
  recompile-hazard pass: the static pass says "this branch COULD
  recompile per step"; the tracker says "this function DID compile 14
  times in the last hour". Steady-state serving must show zero misses.
  The name a call site gives is the name XLA has for the program
  (``jit_<name>`` in a lowered module, a device trace and JAX's own
  compile events), and from those events the tracker keeps a BUILD
  LEDGER of every jitted function of the process, tracked or not: the
  seconds each was traced, lowered and in the backend, and whether the
  backend compiled it or loaded it from the persistent cache.

- :class:`StepProfiler` — attributes wall time into host-dispatch vs
  device-execute per pipeline stage using the ``block_until_ready``
  delta (dispatch returns as soon as XLA enqueues; the remainder until
  the sync completes is device/transfer time). This generalizes
  bench.py's MFU accounting into an always-on gauge: pass ``flops`` and
  ``profile_mfu{stage=...}`` updates per step. A dispatch that costs
  more than the step is exactly what this surface makes visible per
  stage, continuously.

- :class:`FeatureLog` — a bounded structured log appending one record
  per served request (route, batch/bucket, dtype/shapes when known,
  queue ms, execute ms, device ms): the training data for the learned
  scheduler cost model (arXiv:2008.01040) and the measurement substrate
  a TVM-style autotuner (arXiv:1802.04799) searches over.

``utils.profiling``'s device-trace helpers (:func:`profile_trace`,
:func:`profiled`) moved here — that module keeps deprecation shims.

Import is stdlib-only; JAX is imported lazily inside the jit wrapper
and the XProf helpers only.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
import re
import sys
import threading
import time

from .attribution import PEAK_SPECS, peak_spec, telemetry_peak_spec
from .metrics import registry as _registry
from .timeline import PING, Capture
from .tracing import now_ns, tracer as _tracer, wall_now

# kept importable for callers that pinned against the old constant —
# but it is now the v5e row of the shared PeakSpec table
# (obs.attribution), not a free-floating literal. The MFU gauge itself
# resolves the LIVE platform's peak per call unless explicitly
# overridden.
DEFAULT_PEAK_FLOPS = PEAK_SPECS["tpu-v5e"].peak_flops

# JAX's own events of a build (jax.monitoring): each of the three phases
# arrives as a scalar when it starts and as a duration when it ends, both
# with ``fun_name``; the persistent cache's arrive inside the backend
# phase, with no name
_BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_NOT_IN_A_MODULE_NAME = re.compile(r"[^0-9A-Za-z_]")


class CompileTracker:
    """Counts retraces and compile time per jitted function.

    ``tracker.jit(fn, name=..., **jit_kwargs)`` returns a callable with
    ``jax.jit`` semantics whose Python body is instrumented: the wrapped
    function executes once per TRACE, so each execution is a cache miss
    (a compile). Per-call hit/miss outcomes and compile wall seconds go
    to the obs registry:

    - ``profile_compiles_total{fn=...}`` — retrace count (>= 2 on a
      shape-unstable function; the static recompile-hazard pass's
      runtime ground truth),
    - ``profile_jit_calls_total{fn=...,outcome=hit|miss}``,
    - ``profile_compile_seconds{fn=...}`` — trace+compile wall time.

    The label, with whatever an HLO module name cannot hold replaced by
    ``_``, is the jitted function's ``__name__``: XLA calls the program
    ``jit_<label>`` and the returned callable's ``__name__`` says it
    (labels that differ only in such characters are one name to XLA, as
    two functions of one name are).

    The BUILD LEDGER (:meth:`ledger`) is fed by listeners on
    ``jax.monitoring``, which fire only when JAX builds — a call that
    hits pays nothing. One entry a function name, for every jitted
    function of the process, in the order first seen. JAX keeps a
    listener for the life of the process, so only the process-wide
    ``compile_tracker`` listens unasked (when this module is imported
    after JAX, else at its first :meth:`jit`); another tracker keeps a
    ledger from its :meth:`listen` on.

    Intentionally lock-free: the trace-noting shim runs INSIDE the
    traced region (that is the mechanism), where lock acquisition is a
    trace-safety hazard. Python-level dict bumps are GIL-atomic enough
    for compile events, which JAX serializes under its own tracing
    machinery; the registry counters (internally locked) carry the
    authoritative monotone series.
    """

    def __init__(self, registry=None):
        reg = registry if registry is not None else _registry
        self._traces: dict[str, int] = {}
        self._calls: dict[str, int] = {}
        # steady-state assertion mode (AOT acceptance, ISSUE 11):
        # after mark_steady(), every further compile is a violation —
        # counted separately so "did the warm worker compile?" is one
        # scrape of profile_runtime_compiles_total, which must stay 0.
        self._steady = False
        self._steady_base: dict[str, int] = {}
        self._c_compiles = reg.counter(
            "profile_compiles_total",
            "jit retraces (compiles) per tracked function")
        self._c_runtime = reg.counter(
            "profile_runtime_compiles_total",
            "compiles AFTER steady state was declared (mark_steady) — "
            "an AOT-warmed server must hold this at 0")
        self._c_calls = reg.counter(
            "profile_jit_calls_total",
            "tracked jit calls, by function and cache outcome")
        self._h_compile = reg.histogram(
            "profile_compile_seconds",
            "trace+compile wall seconds per tracked function")
        self._builds: dict[str, dict] = {}
        self._building = threading.local()
        self._listening = False

    # -- the build ledger --------------------------------------------------
    def listen(self) -> None:
        """Register the ledger's listeners on ``jax.monitoring``, once."""
        if self._listening:
            return
        self._listening = True
        from jax import monitoring
        monitoring.register_scalar_listener(self._on_phase_start)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_seconds)

    def _entry(self, fun_name: str) -> dict:
        # the trace's event names the function ``<name>``, the other two
        # the program, ``jit(<name>)``: one entry
        name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
        return self._builds.setdefault(name, {
            "fn": name, "traced": 0, "trace_s": 0.0, "lowered": 0,
            "lower_s": 0.0, "backend_s": 0.0, "compiled": 0, "loaded": 0})

    def _on_phase_start(self, event: str, _value, **_):
        phase = _BUILD_PHASES.get(event)
        if phase == "trace":
            self._building.traces = getattr(self._building, "traces", 0) + 1
        elif phase == "backend":
            # the build a nameless cache hit on this thread belongs to
            self._building.how = "compiled"

    def _on_event(self, event: str, **_):
        if event == _CACHE_HIT:
            self._building.how = "loaded"

    def _on_seconds(self, event: str, seconds: float, fun_name: str = "",
                    **_):
        phase = _BUILD_PHASES.get(event)
        if phase is None:
            return
        if phase == "trace":
            self._building.traces = inside = max(
                getattr(self._building, "traces", 1) - 1, 0)
            if inside:
                # a jitted function called by the one being traced: its
                # seconds are part of that one's, and it is no program
                return
            counted = "traced"
        elif phase == "lower":
            counted = "lowered"
        else:
            counted = getattr(self._building, "how", "compiled")
        entry = self._entry(fun_name)
        entry[counted] += 1
        entry[f"{phase}_s"] += seconds

    def ledger(self) -> list[dict]:
        """What JAX built in this process, one plain dict a function
        name in the order first seen: ``fn``, ``traced`` and ``lowered``
        (how often; a second ``lower`` of the same arguments traces
        again, in microseconds, and lowers nothing), ``trace_s``,
        ``lower_s``, ``backend_s``, ``compiled`` and ``loaded`` (backend
        builds by how)."""
        return [dict(entry) for entry in self._builds.values()]

    def _note_trace(self, label: str) -> None:
        # runs at trace time, inside the traced region: must stay free
        # of locks/clock/IO (graftcheck's trace-safety pass gates this
        # file). The dict bump is best-effort; the counter is exact.
        self._traces[label] = self._traces.get(label, 0) + 1
        self._c_compiles.inc(1, fn=label)
        if self._steady:
            self._c_runtime.inc(1, fn=label)

    def jit(self, fn=None, *, name: str | None = None, **jit_kwargs):
        """``jax.jit`` with compile tracking. Usable as a decorator
        (``@tracker.jit`` / ``@tracker.jit(name=...)``) or call-form;
        ``jit_kwargs`` pass through (donate_argnums, in_shardings, ...).
        ``lower``/``eval_shape``/``clear_cache`` forward to the
        underlying jitted callable."""
        if fn is None:
            return functools.partial(self.jit, name=name, **jit_kwargs)
        import jax
        if self is compile_tracker:
            self.listen()
        label = name or getattr(fn, "__name__", None) or "<jit>"
        xla_name = _NOT_IN_A_MODULE_NAME.sub("_", label)

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            self._note_trace(label)
            return fn(*args, **kwargs)

        # what XLA, a device trace and JAX's compile events call it
        traced.__name__ = traced.__qualname__ = xla_name
        compiled = jax.jit(traced, **jit_kwargs)

        @functools.wraps(fn)
        def call(*args, **kwargs):
            before = self._traces.get(label, 0)
            t0 = time.perf_counter()
            out = compiled(*args, **kwargs)
            if self._traces.get(label, 0) > before:
                # the call that traced pays trace+compile inline: its
                # wall time IS the compile cost (async device dispatch
                # makes a cache-hit call return in microseconds)
                self._h_compile.observe(time.perf_counter() - t0,
                                        fn=label)
                self._c_calls.inc(1, fn=label, outcome="miss")
            else:
                self._c_calls.inc(1, fn=label, outcome="hit")
            self._calls[label] = self._calls.get(label, 0) + 1
            return out

        for attr in ("lower", "eval_shape", "trace", "clear_cache"):
            if hasattr(compiled, attr):
                setattr(call, attr, getattr(compiled, attr))
        call.__name__ = call.__qualname__ = xla_name
        call.__tracked_label__ = label
        return call

    # -- read surface ------------------------------------------------------
    def compiles(self, name: str) -> int:
        """Retrace count for a tracked function (0 if never traced)."""
        return self._traces.get(name, 0)

    def calls(self, name: str) -> int:
        return self._calls.get(name, 0)

    def stats(self) -> dict[str, dict[str, int]]:
        return {label: {"compiles": n,
                        "calls": self._calls.get(label, 0)}
                for label, n in sorted(self._traces.items())}

    def unstable(self, min_compiles: int = 2) -> dict[str, int]:
        """Functions that recompiled — the runtime recompile-hazard
        flags. A steady-state serving process must return ``{}`` here
        (after warmup); a shape-unstable fn shows its retrace count."""
        return {label: n for label, n in sorted(self._traces.items())
                if n >= min_compiles}

    # -- steady-state assertion mode (AOT warm-boot acceptance) ----------
    def mark_steady(self) -> None:
        """Declare warmup over: from here, every compile is a
        violation (``profile_runtime_compiles_total`` counts it). Call
        after an AOT warm load, or after a deliberate warmup sweep."""
        self._steady_base = dict(self._traces)
        self._steady = True

    def unmark_steady(self) -> None:
        self._steady = False

    @property
    def steady(self) -> bool:
        return self._steady

    def runtime_compiled(self) -> dict[str, int]:
        """Per-function compiles since :meth:`mark_steady` — the
        functions an operator must add to the AOT build."""
        if not self._steady:
            return {}
        return {label: n - self._steady_base.get(label, 0)
                for label, n in sorted(self._traces.items())
                if n > self._steady_base.get(label, 0)}

    def runtime_compiles(self) -> int:
        """Total compiles since steady state was declared (0 = the
        AOT contract held)."""
        return sum(self.runtime_compiled().values())

    def assert_steady_state(self) -> None:
        """Raise (loudly, with the offending functions) if anything
        compiled after :meth:`mark_steady` — the scale-up acceptance's
        programmatic form."""
        bad = self.runtime_compiled()
        if bad:
            raise AssertionError(
                f"{sum(bad.values())} runtime compile(s) in steady "
                f"state: {bad} — add these (fn × bucket) to the AOT "
                "build (python -m mmlspark_tpu.core.aot build)")


#: THE process-wide tracker (``parallel.compat.jit`` routes through it).
compile_tracker = CompileTracker()
if sys.modules.get("jax") is not None:
    # imported after JAX (a program that already builds): the ledger
    # holds what is built from here on; importing this module alone
    # still imports no JAX
    compile_tracker.listen()


class _StepHandle:
    """Yielded by :meth:`StepProfiler.step`: call ``done(result)`` with
    whatever the stage produced so the profiler can measure the
    device-execute tail (``block_until_ready`` delta). Without it the
    whole step is attributed to host dispatch. After the ``with`` block
    exits, ``seconds`` / ``dispatch_seconds`` / ``device_seconds``
    carry the measured split (callers like ``stages.Timer`` re-surface
    them)."""

    __slots__ = ("result", "seconds", "dispatch_seconds",
                 "device_seconds")

    def __init__(self):
        self.result = None
        self.seconds = 0.0
        self.dispatch_seconds = 0.0
        self.device_seconds = 0.0

    def done(self, result):
        self.result = result
        return result


def _block_on(obj) -> bool:
    """Best-effort sync on anything block_until_ready-able (a jax
    array, a tuple/list/dict of them, or a DataFrame's columns).
    Returns whether anything was actually synced — a pure-host stage
    records device_seconds ~0 with ``synced=False``."""
    if obj is None or isinstance(obj, (str, bytes, int, float, bool)):
        # scalars can't hold device handles, and a str ITERATES TO
        # ITSELF — without this cut a single text cell recurses forever
        return False
    synced = False
    blocker = getattr(obj, "block_until_ready", None)
    if callable(blocker):
        blocker()
        return True
    # numeric numpy arrays cannot hold device handles: skip before the
    # generic __iter__ branch walks a million rows in Python
    dt = getattr(obj, "dtype", None)
    if dt is not None and getattr(dt, "kind", "O") != "O":
        return False
    cols = getattr(obj, "columns", None)
    if cols is not None and hasattr(obj, "__getitem__"):
        for c in cols:  # DataFrame-shaped: sync column by column
            if _block_on(obj[c]):
                synced = True
        return synced
    if isinstance(obj, dict):
        obj = obj.values()
    if isinstance(obj, (list, tuple)) or hasattr(obj, "__iter__"):
        try:
            for leaf in obj:
                if _block_on(leaf):
                    synced = True
        except TypeError:
            pass
    return synced


def process_label() -> str | None:
    """This worker's ``process`` metric label, or None when the label
    should not be attached. Single-process runs (the overwhelmingly
    common case, and every existing dashboard/test) get None so their
    sample names stay exactly as before; only a live multi-process
    (pod) backend yields ``"0"``/``"1"``/… so per-worker series stay
    distinguishable when N workers push to one aggregation point.
    Guarded like :func:`device_platform`: never imports jax, never
    initializes a backend — ``jax.process_count()`` would bring one up.
    """
    mod = sys.modules.get("jax")
    if mod is None:
        return None
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not getattr(xb, "_backends", None):
        return None     # don't cache: distributed init may come later
    try:
        if int(mod.process_count()) <= 1:
            return None
        return str(int(mod.process_index()))
    except Exception:
        return None


class StepProfiler:
    """Host-dispatch vs device-execute attribution per pipeline stage.

    ``with profiler.step("featurize", flops=f) as h: h.done(stage(x))``
    records:

    - ``profile_step_seconds{stage=...,phase=dispatch|device}`` — the
      host time until dispatch returned vs the block_until_ready tail,
    - ``profile_steps_total{stage=...}``,
    - ``profile_mfu{stage=...}`` when ``flops`` is given (always-on MFU:
      flops / total seconds / peak),

    and emits ``profile.dispatch`` / ``profile.device`` child spans
    under the ambient trace (or an explicit ``parent=``), so a request's
    flame graph shows where host↔device time went per stage.
    """

    def __init__(self, service: str = "", registry=None, tracer=None,
                 peak_flops: float | None = None):
        reg = registry if registry is not None else _registry
        self.service = service
        # None (default) = resolve the live platform's PeakSpec per
        # call — the platform may only initialize after construction
        self.peak_flops = None if peak_flops is None \
            else float(peak_flops)
        self._tracer = tracer if tracer is not None else _tracer
        self._h_step = reg.histogram(
            "profile_step_seconds",
            "per-stage wall seconds, split host-dispatch vs device")
        self._c_steps = reg.counter(
            "profile_steps_total", "profiled stage executions")
        self._g_mfu = reg.gauge(
            "profile_mfu",
            "achieved FLOP/s over peak per stage (always-on MFU)")

    _AMBIENT = object()

    @contextlib.contextmanager
    def step(self, stage: str, *, parent=_AMBIENT,
             flops: float | None = None, features: dict | None = None):
        handle = _StepHandle()
        if parent is StepProfiler._AMBIENT:
            parent = self._tracer.current_span()
        # lazy: memory imports this module for process_label
        from .memory import memory_profiler
        mem0 = memory_profiler.watermark()
        w0 = wall_now()
        t0 = time.perf_counter()
        try:
            yield handle
        finally:
            t1 = time.perf_counter()
            synced = False
            if handle.result is not None:
                try:
                    synced = _block_on(handle.result)
                except Exception:
                    synced = False
            t2 = time.perf_counter()
            dispatch_s, device_s = t1 - t0, t2 - t1
            handle.dispatch_seconds = dispatch_s
            handle.device_seconds = device_s
            handle.seconds = t2 - t0
            # on a pod worker the step/mfu families carry a `process`
            # label; single-process series keep their exact names
            pl = process_label()
            plab = {"process": pl} if pl is not None else {}
            self._h_step.observe(dispatch_s, stage=stage,
                                 phase="dispatch", **plab)
            self._h_step.observe(device_s, stage=stage, phase="device",
                                 **plab)
            self._c_steps.inc(1, stage=stage, **plab)
            # live-buffer delta this stage left behind (HBM profiler;
            # absent on hosts whose devices report no memory stats)
            memory_profiler.segment_delta(
                stage, mem0, memory_profiler.watermark())
            # telemetry inside a served step: a device without a
            # PeakSpec row is counted and the gauge skipped, not raised
            if flops and (self.peak_flops is not None
                          or telemetry_peak_spec() is not None):
                self.record_mfu(stage, flops, t2 - t0)
            dspan = self._tracer.emit_span(
                "profile.dispatch", parent=parent, seconds=dispatch_s,
                start_wall=w0, stage=stage)
            self._tracer.emit_span(
                "profile.device", parent=dspan, seconds=device_s,
                start_wall=w0 + dispatch_s, stage=stage, synced=synced)
            if features is not None:
                feature_log.record(
                    stage=stage, dispatch_ms=dispatch_s * 1e3,
                    device_ms=device_s * 1e3, **features)

    def record_mfu(self, stage: str, flops: float,
                   seconds: float) -> float:
        """Set the always-on MFU gauge from an externally measured
        (flops, seconds) pair — bench.py's sweep and the step context
        both land here. The peak divided by is the resolved PeakSpec's
        (env-overridable; obs.attribution) unless the profiler was
        built with an explicit ``peak_flops``, and the gauge carries
        the platform it was computed against."""
        if self.peak_flops is not None:
            peak, platform = self.peak_flops, device_platform()
        else:
            spec = peak_spec()
            peak, platform = spec.peak_flops, spec.platform
        mfu = float(flops) / max(float(seconds), 1e-12) / peak
        labels = {"stage": stage, "platform": platform}
        pl = process_label()
        if pl is not None:
            labels["process"] = pl
        self._g_mfu.set(mfu, **labels)
        return mfu


#: THE process-wide step profiler (serving, pipelines, benches share it
#: so the mfu/step series stay one family).
step_profiler = StepProfiler()


#: Feature-row schema version. v2 (ISSUE 12) added the fields the cost
#: model needs that PR 6 did not record — ``padded_batch`` (the
#: post-bucket batch shape the executor actually runs), ``queue_depth``
#: at execute time, ``compiled_segments``, and the device ``platform``
#: — plus this stamp itself. v3 (ISSUE 15) stamps the ``process`` index
#: (``process_label()``; None on single-process hosts) so fleet-merged
#: training data is rank-attributable. v4 (ISSUE 17) adds the
#: generation-row fields ``decode_steps`` and ``prefill_tokens`` (the
#: LLM serving engine records one row per completed sequence) so the
#: cost model can price decode separately from prefill; non-generation
#: rows simply omit them. v5 (ISSUE 18) adds ``context_blocks`` (KV
#: blocks resident at completion) so decode-step time is priced by
#: resident context, not just batch — the paged-attention kernel's
#: cost scales with the chain length it streams. v6 (ISSUE 20) adds
#: the analytic-cost pair ``analytic_flops`` / ``analytic_bytes``
#: (XLA ``cost_analysis`` totals for the service's compiled programs,
#: from ``obs.attribution``) so the model can price requests by the
#: device work they actually dispatch, not just by shape proxies.
#: Consumers (``perf.costmodel``) accept v6 through v2 rows and SKIP
#: anything else, loudly, instead of misparsing old logs; fields
#: absent in old rows train as 0.
FEATURE_SCHEMA_VERSION = 6

_platform_cache: str | None = None


def device_platform() -> str:
    """Best-effort device platform for feature rows WITHOUT importing
    jax OR initializing its backend — a host-only serving process must
    not drag backend bring-up (seconds; on a TPU host it claims the
    device) into its executor thread. ``"none"`` until something else
    imports jax; a merely-imported jax reports the pinned platform
    config (or ``"uninitialized"``) until something else actually
    initializes a backend; cached once a live backend answers."""
    global _platform_cache
    if _platform_cache is not None:
        return _platform_cache
    mod = sys.modules.get("jax")
    if mod is None:
        return "none"       # don't cache: jax may import later
    # only ask default_backend() once backends exist — the call itself
    # INITIALIZES them otherwise (private attr read is guarded: on API
    # drift this degrades to the config string, never to an init)
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and getattr(xb, "_backends", None):
        try:
            _platform_cache = str(mod.default_backend())
            return _platform_cache
        except Exception:
            return "unknown"    # don't cache a failed backend
    try:
        plats = mod.config.jax_platforms
        if plats:
            return str(plats).split(",")[0]
    except Exception:
        pass
    return "uninitialized"


class FeatureLog:
    """Bounded in-memory log of per-request cost-model features.

    One dict per served request, appended by the serving executor
    (route, batch, padding bucket, queue/execute ms) and enriched by
    model transforms through :meth:`record` or
    ``StepProfiler.step(features=...)`` (op shapes, dtype, device ms).
    This is TRAINING DATA for the learned performance model
    (``perf.costmodel``) that prices ``sched/policy.py``'s admission
    and batch-close decisions — bounded (ring buffer) so an always-on
    server never grows it past ``maxlen`` records.

    Every record is stamped with :data:`FEATURE_SCHEMA_VERSION` and the
    device ``platform`` unless the caller supplies them;
    :attr:`total_recorded` counts monotonically past the ring bound
    (the cost model's refresh trigger).
    """

    def __init__(self, maxlen: int = 4096, registry=None):
        reg = registry if registry is not None else _registry
        self._lock = threading.Lock()
        self._records = collections.deque(maxlen=int(maxlen))
        self._total = 0
        self._c_records = reg.counter(
            "profile_feature_records_total",
            "cost-model feature records appended, by service")

    def record(self, **fields) -> None:
        fields.setdefault("schema_version", FEATURE_SCHEMA_VERSION)
        fields.setdefault("platform", device_platform())
        fields.setdefault("process", process_label())
        with self._lock:
            self._records.append(dict(fields))
            self._total += 1
        self._c_records.inc(1, service=str(fields.get("service", "")))

    @property
    def total_recorded(self) -> int:
        """Monotone append count (NOT bounded by the ring)."""
        with self._lock:
            return self._total

    def snapshot(self) -> list[dict]:
        """Copy of the retained records, oldest first."""
        with self._lock:
            return [dict(r) for r in self._records]

    def clear(self) -> None:
        with self._lock:
            self._records.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)


#: THE process-wide feature log.
feature_log = FeatureLog()


# ------------------------------------------------- pipeline profiling hook
# PipelineModel.transform consults this: None (the default) keeps the
# async-dispatch pipeline untouched; enabling it syncs per stage (that
# is the point — attribution requires the block_until_ready delta).
_pipeline_profiler: StepProfiler | None = None
_env_checked = False


def enable_pipeline_profiling(profiler: StepProfiler | None = None
                              ) -> StepProfiler:
    """Turn on per-stage host/device attribution for every
    ``PipelineModel.transform`` (also via MMLSPARK_TPU_PROFILE_PIPELINE=1).
    Costs one device sync per stage — measurement, not a free lunch."""
    global _pipeline_profiler
    _pipeline_profiler = profiler if profiler is not None \
        else step_profiler
    return _pipeline_profiler


def disable_pipeline_profiling() -> None:
    global _pipeline_profiler, _env_checked
    _pipeline_profiler = None
    _env_checked = True  # an explicit disable beats the env default


def pipeline_profiler() -> StepProfiler | None:
    """The active pipeline profiler or None (the hot-path check)."""
    global _env_checked
    if _pipeline_profiler is None and not _env_checked:
        _env_checked = True
        if os.environ.get("MMLSPARK_TPU_PROFILE_PIPELINE") == "1":
            enable_pipeline_profiling()
    return _pipeline_profiler


# ----------------------------------------------------- XProf device traces
# (folded in from utils/profiling.py — the duplicate timing path PR 1
# left behind; that module now shims here with a DeprecationWarning)
def start_device_trace(log_dir: str, *, host_tracer_level: int = 0) -> None:
    """Start the JAX profiler the way this chip can bear: device planes
    only unless asked otherwise. With the host tracer on, at any level,
    the TPU runtime's threads write over a million events and a 1 s
    transform takes 14 s (PERF.md section 6). Every capture path of the
    program starts the profiler here."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, create_perfetto_link=False,
                             profiler_options=options)


def profile_ping(x):
    """The ping's program; a capture has it as ``timeline.PING_PROGRAM``."""
    return x + 1


@functools.cache
def _pinger():
    """The ping's program and its input, compiled once a process."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(profile_ping)
    x = jax.block_until_ready(jnp.zeros((8, 128), jnp.float32))
    fn(x).block_until_ready()
    return fn, x


def _ping() -> None:
    """One tiny program run to its end while the device is quiet, under
    a ``launch`` and a ``fetch`` span: it starts within the launch call
    and its result is back within microseconds of its end, so it pins
    the device clock to the spans' (``timeline.clock_offset``)."""
    fn, x = _pinger()
    with _tracer.span(PING, parent=None):
        with _tracer.span(PING + ".launch"):
            y = fn(x)
        with _tracer.span(PING + ".fetch"):
            y.block_until_ready()


@contextlib.contextmanager
def profile_trace(log_dir: str, *, host_tracer_level: int = 0):
    """Capture a device trace of the enclosed region and yield its
    :class:`~mmlspark_tpu.obs.timeline.Capture`. After the block the
    handle has the capture's ``.xplane.pb`` path and the tracer ring's
    spans of the stretch, and puts the two on one clock:
    ``device_programs()``, ``clock_offset()``, ``idle_by_span()``. Start
    and end it between whole operations, while the device is quiet: the
    capture runs one tiny program (``profile.ping``) before and after
    the block, and those two alone pin the clocks together."""
    import jax
    capture = Capture(log_dir)
    _pinger()                            # compiled outside the capture
    start_device_trace(log_dir, host_tracer_level=host_tracer_level)
    trace_ns = start_ns = end_ns = now_ns()
    try:
        _ping()
        start_ns = now_ns()
        try:
            yield capture
        finally:
            end_ns = now_ns()
        _ping()
    finally:
        jax.profiler.stop_trace()
        capture.close(start_ns, end_ns, trace_ns)


def profiled(name: str | None = None):
    """Decorator: annotate a function (``jax.profiler.TraceAnnotation``)
    for a capture taken with the host tracer on; a device-only capture,
    the default here, drops it."""
    def wrap(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax
            with jax.profiler.TraceAnnotation(label):
                return fn(*args, **kwargs)
        return inner
    return wrap
