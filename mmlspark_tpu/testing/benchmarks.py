"""Benchmark regression harness.

Reference ``core/test/benchmarks/Benchmarks.scala:16-130``: named metric
values with explicit tolerance recorded in CSVs
(``src/test/resources/benchmarks/benchmarks_<Suite>.csv``); the test
recomputes each metric and ``compareBenchmark`` asserts it matches within
precision. Same CSV format here: ``name,value,precision`` rows.

Timings come through the obs subsystem, not private stopwatches: a
``timed(...)`` region records into the process-wide registry
(``benchmark_seconds{name=...}``) and the benchmark row reads the value
back from that same histogram, so a benchmark timing is always also a
scrapeable series (``/metrics``, ``registry.snapshot()``) — one
measurement surface for benches, serving, and training alike.
"""

from __future__ import annotations

import contextlib
import csv
import os
import threading
import time
from math import ceil as _ceil

from ..obs.metrics import registry as _registry


class Benchmarks:
    """Accumulate metrics, then compare (or regenerate) the CSV."""

    def __init__(self, csv_path: str):
        self.csv_path = csv_path
        self.recorded: list[tuple[str, float, float]] = []

    def add(self, name: str, value: float, precision: float) -> None:
        """Reference ``addBenchmark``."""
        self.recorded.append((name, float(value), float(precision)))

    @contextlib.contextmanager
    def timed(self, name: str, precision: float):
        """Time a region through the obs registry and record the row.

        The wall seconds land in the process-wide
        ``benchmark_seconds{name=...}`` histogram (scrapeable alongside
        serving/training series) and THIS region's duration becomes the
        CSV row — not an aggregate over the labeled series, which would
        fold warmup passes and prior in-process runs into the value."""
        hist = _registry.histogram(
            "benchmark_seconds", "benchmark timed-region wall seconds")
        with hist.time(name=name) as t:
            yield
        self.add(name, t.seconds, precision)

    def add_from_registry(self, name: str, sample: str,
                          precision: float, registry=None) -> None:
        """Record a registry sample (a ``snapshot()`` key, e.g.
        ``serving_requests_total{route="/"}``) as a benchmark row."""
        snap = (registry if registry is not None else _registry) \
            .snapshot()
        if sample not in snap:
            raise KeyError(
                f"registry sample {sample!r} not found; known samples "
                f"include {sorted(snap)[:8]}...")
        self.add(name, snap[sample], precision)

    def _load(self) -> dict[str, tuple[float, float]]:
        out = {}
        with open(self.csv_path) as f:
            for row in csv.reader(f):
                if not row or row[0].startswith("#"):
                    continue
                out[row[0]] = (float(row[1]), float(row[2]))
        return out

    def _write(self) -> None:
        os.makedirs(os.path.dirname(self.csv_path), exist_ok=True)
        with open(self.csv_path, "w", newline="") as f:
            w = csv.writer(f)
            for name, value, precision in self.recorded:
                w.writerow([name, repr(value), repr(precision)])

    def verify(self, regenerate: bool = False) -> None:
        """Reference ``verifyBenchmarks``: assert every recorded metric is
        within its recorded precision; regenerate=True (or a missing CSV)
        writes the file instead — the reference's workflow for adding new
        benchmark rows."""
        if regenerate or not os.path.exists(self.csv_path):
            self._write()
            return
        expected = self._load()
        errors = []
        for name, value, precision in self.recorded:
            if name not in expected:
                errors.append(f"missing benchmark row {name!r}")
                continue
            exp_val, exp_prec = expected[name]
            if abs(value - exp_val) > exp_prec:
                errors.append(
                    f"{name}: got {value}, expected {exp_val} ± {exp_prec}")
        if errors:
            raise AssertionError("benchmark regressions:\n"
                                 + "\n".join(errors))


class _SynthRequest:
    """A scheduler item for the overload scenario: carries the latch the
    arrival thread waits on plus the attributes the sched subsystem
    decorates (route/deadline/tenant/on_done)."""

    __slots__ = ("submitted", "done_at", "status", "route", "deadline",
                 "tenant", "cost", "on_done", "span", "queue_wait",
                 "_event")

    def __init__(self):
        self.submitted = time.monotonic()
        self.done_at = None
        self.status = None
        self.route = "/"
        self.deadline = None
        self.tenant = ""        # quota/tier bucket (sched.tenancy)
        self.cost = 0.0         # synthetic per-item service seconds
        self.on_done = None
        self.span = None        # request span (tracing scenarios)
        self.queue_wait = None  # stamped by the scheduler at pop
        self._event = threading.Event()

    def reply(self, status: int) -> bool:
        # reply-exactly-once latch, same contract as serving's
        # CachedRequest (the scheduler's expiry shed path calls this)
        if self._event.is_set():
            return False
        self.status = status
        self.done_at = time.monotonic()
        self._event.set()
        cb, self.on_done = self.on_done, None
        if cb is not None:
            cb()
        return True


def overload_scenario(*, service: str = "overload-bench",
                      deadline_s: float = 0.2,
                      item_service_s: float = 0.004,
                      max_queue: int = 64,
                      max_batch: int = 8,
                      rate_factor: float = 2.0,
                      n_requests: int = 400,
                      registry=None) -> dict:
    """Synthetic overload benchmark for the sched subsystem (ISSUE 2
    acceptance): offer load at ``rate_factor``× the sustainable rate
    into a :class:`~mmlspark_tpu.sched.RequestScheduler` backed by a
    deterministic executor (``item_service_s`` seconds per request,
    batched up to ``max_batch``), then read the ``sched_*`` series back
    from the obs registry.

    A correct scheduler under 2× overload must (a) bound the queue —
    admission sheds BEFORE depth runs away, (b) keep the latency of
    requests it chose to admit within the deadline budget — expiry
    sheds fire before execution, never after — and (c) shed the excess
    as 429s rather than timing everyone out. The returned dict carries
    the measured p99/max depth plus the registry readings
    (``sched_admitted_total``, ``sched_shed_total`` by reason,
    ``sched_queue_wait_seconds`` count) so benches can bank and tests
    can assert on either surface.
    """
    from ..obs.metrics import registry as _default
    from ..sched import RequestScheduler, Shed

    reg = registry if registry is not None else _default
    shed_answered: list[_SynthRequest] = []
    sched = RequestScheduler(
        service, max_queue=max_queue, deadline=deadline_s, registry=reg,
        on_shed=lambda item, reason, retry_after:
            (shed_answered.append(item), item.reply(429)))
    done: list[_SynthRequest] = []
    stop = threading.Event()
    depth_high = [0]

    def executor():
        while not stop.is_set() or sched.qsize():
            batch = sched.next_batch(max_batch=max_batch, max_wait=0.05)
            if not batch:
                continue
            t0 = time.monotonic()
            time.sleep(item_service_s * len(batch))  # deterministic work
            sched.estimator.observe(len(batch),
                                    time.monotonic() - t0)
            for item in batch:
                item.reply(200)
                done.append(item)

    worker = threading.Thread(target=executor, daemon=True)
    worker.start()
    interval = item_service_s / rate_factor
    admitted = shed_at_intake = 0
    # prime the service-time EWMA so predictive admission has a model
    # from the first request (a cold registry sheds nothing until the
    # first batch lands)
    sched.estimator.observe(1, item_service_s)
    for _ in range(n_requests):
        req = _SynthRequest()
        try:
            sched.submit(req)
            admitted += 1
        except Shed:
            shed_at_intake += 1
        depth_high[0] = max(depth_high[0], sched.qsize())
        time.sleep(interval)
    stop.set()
    sched.wake()
    worker.join(timeout=10)
    lat = sorted((r.done_at - r.submitted) for r in done
                 if r.done_at is not None)
    snap = reg.snapshot()

    def _series(prefix: str) -> dict:
        return {k: v for k, v in snap.items()
                if k.startswith(prefix) and f'service="{service}"' in k}

    return {
        "offered": n_requests,
        "admitted": admitted,
        "answered_200": len(lat),
        "shed_at_intake": shed_at_intake,
        "shed_after_queueing": len(shed_answered),
        "deadline_s": deadline_s,
        "max_queue": max_queue,
        "max_depth_seen": depth_high[0],
        # nearest-rank percentiles: ceil(q*n)-1 — int(n*0.99)-1 would
        # sit one rank low and hide exactly the tail samples a
        # deadline-SLO acceptance check exists to catch
        "p50_s": lat[max(_ceil(0.50 * len(lat)) - 1, 0)]
        if lat else float("nan"),
        "p99_s": lat[max(_ceil(0.99 * len(lat)) - 1, 0)]
        if lat else float("nan"),
        "sched_admitted_total": _series("sched_admitted_total"),
        "sched_shed_total": _series("sched_shed_total"),
        "sched_queue_wait_count": _series("sched_queue_wait_seconds_count"),
    }


def tracing_overhead_scenario(*, service: str = "tracing-bench",
                              n_requests: int = 200,
                              item_service_s: float = 0.005,
                              max_batch: int = 8,
                              reps: int = 3,
                              registry=None) -> dict:
    """Profiler-overhead guard (ISSUE 8 satellite): the same synthetic
    serving pipeline (RequestScheduler + deterministic executor — no
    HTTP socket, so loopback jitter cannot masquerade as tracing cost)
    measured with the full tracing+profiler stack OFF vs ON, asserting
    the instrumented p99 stays within 5%% of bare.

    ON means everything a traced serving request pays: a request span
    per item, the scheduler's ``sched.queue`` child span, a retroactive
    execute span, a cost-model feature-log record, a ``StepProfiler``
    step around each executor batch, and a flight-recorder
    ``note_request`` per reply. The modes run INTERLEAVED (off, on,
    off, on, ...) and each mode keeps its best-of-``reps`` p99 — the
    same min-of-runs discipline bench.py's loaded rows use: the
    per-rep minimum is the deterministic floor (service time + any
    instrumentation cost), so host contention and sleep jitter — which
    hit both modes but not symmetrically within one rep — cannot
    manufacture or mask overhead. Returns both p99s, ``overhead_pct``,
    and ``within_bound`` (the 5%% contract — asserted by the test AND
    banked in the bench JSON).
    """
    from ..obs.export import flight_recorder
    from ..obs.profile import StepProfiler, feature_log
    from ..obs.metrics import registry as _default
    from ..obs.tracing import tracer
    from ..sched import RequestScheduler

    reg = registry if registry is not None else _default
    profiler = StepProfiler(service=service, registry=reg)
    flight_recorder.install()

    def one_run(traced: bool) -> float:
        sched = RequestScheduler(f"{service}-{'on' if traced else 'off'}",
                                 registry=reg)
        done: list[_SynthRequest] = []
        stop = threading.Event()

        def executor():
            while not stop.is_set() or sched.qsize():
                batch = sched.next_batch(max_batch=max_batch,
                                         max_wait=0.05)
                if not batch:
                    continue
                if traced:
                    with profiler.step("tracing-bench.batch") as h:
                        time.sleep(item_service_s * len(batch))
                        h.done(None)
                else:
                    time.sleep(item_service_s * len(batch))
                for item in batch:
                    span = getattr(item, "span", None)
                    if span is not None:
                        tracer.emit_span(
                            "serving.execute", parent=span,
                            seconds=item_service_s * len(batch),
                            service=service, rows=len(batch))
                        feature_log.record(
                            service=service, route="/",
                            batch=len(batch),
                            queue_ms=(getattr(item, "queue_wait", 0.0)
                                      or 0.0) * 1e3,
                            execute_ms=item_service_s * len(batch)
                            * 1e3, trace_id=span.trace_id)
                    item.reply(200)
                    if span is not None:
                        span.set_attr("status", 200)
                        tracer.end_span(span)
                        flight_recorder.note_request(
                            span.trace_id,
                            time.monotonic() - item.submitted,
                            status=200)
                    done.append(item)

        worker = threading.Thread(target=executor, daemon=True)
        worker.start()
        # pace BELOW saturation: the executor's cost is linear in batch
        # size here, so an overloaded run would measure queue growth —
        # the one thing that is NOT tracing overhead — in both modes
        interval = item_service_s * 1.5
        for _ in range(n_requests):
            req = _SynthRequest()
            if traced:
                req.span = tracer.start_span(
                    "serving.request", parent=None, current=False,
                    service=service, route="/")
            try:
                sched.submit(req)
            except Exception:
                req.reply(503)
            time.sleep(interval)
        stop.set()
        sched.wake()
        worker.join(timeout=20)
        lat = sorted((r.done_at - r.submitted) for r in done
                     if r.done_at is not None and r.status == 200)
        if not lat:
            return float("nan")
        return lat[max(_ceil(0.99 * len(lat)) - 1, 0)]

    offs, ons = [], []
    for _ in range(reps):
        offs.append(one_run(False))
        ons.append(one_run(True))
    p99_off, p99_on = min(offs), min(ons)
    overhead_pct = (p99_on - p99_off) / p99_off * 100.0
    return {
        "n_requests": n_requests,
        "item_service_s": item_service_s,
        "reps": reps,
        "p99_off_s": p99_off,
        "p99_on_s": p99_on,
        "overhead_pct": overhead_pct,
        "bound_pct": 5.0,
        "within_bound": overhead_pct <= 5.0,
        "feature_records": len(feature_log),
    }


# span names a COMPLETE cross-process tree must contain for a request
# answered through the worker mesh (chaos acceptance): the driver-side
# request root + its queue wait, and the compute worker's execute +
# device spans, all under one trace id
COMPLETE_TRACE_SPANS = frozenset({"serving.request", "sched.queue",
                                  "worker.execute", "worker.device"})


def chaos_scenario(*, service: str = "chaos-bench", seed: int = 11,
                   n_requests: int = 40, n_workers: int = 3,
                   error_rate: float = 0.05,
                   latency_spike_s: float = 0.05,
                   latency_rate: float = 0.05,
                   kill_after_leases: int = 1,
                   request_timeout_s: float = 10.0,
                   trace_dir: str | None = None) -> dict:
    """Seeded chaos acceptance for the resilience subsystem (ISSUE 4):
    a real worker mesh (driver registry with heartbeat liveness, one
    ingest server, ``n_workers`` in-thread compute workers) driven under
    an armed fault schedule — one injected worker death mid-lease
    (``worker.death``, after ``kill_after_leases`` healthy leases), 5%%
    injected 503s and latency spikes on the client's ``http.send`` hop —
    while a closed-loop client offers ``n_requests`` through the
    resilience :class:`~mmlspark_tpu.resilience.RetryPolicy`.

    The contract measured: every accepted request is answered 200 (the
    killed worker's leases replay to survivors, injected 503s are
    re-offered per ``Retry-After``) or shed per policy (429/503 only);
    ZERO transport errors (status 0 / connection reset) reach the
    client. The returned dict carries the realized fault ``schedule`` —
    a pure function of the seed and per-point probe order, so re-running
    with the same seed reproduces it — plus the ``resilience_*`` /
    ``serving_lease_replays_total`` registry readings the acceptance
    asserts on.

    Fault decisions are per-point deterministic; the client runs
    single-threaded so the realized schedule is also totally ordered.

    Tracing (ISSUE 8 acceptance): every client request runs under a
    ``client.request`` root span, so the whole run is cross-process
    traced — the result reports, per answered request, whether its span
    tree is COMPLETE (:data:`COMPLETE_TRACE_SPANS` under one trace id)
    and samples one such tree; ``trace_dir`` additionally exports the
    collected spans as Chrome-trace/Perfetto JSON
    (``<trace_dir>/chaos_trace.json``).
    """
    import json as _json
    import os as _os

    import numpy as np

    from ..io.http.clients import send_request
    from ..io.http.schema import HTTPRequestData, HTTPResponseData
    from ..obs.export import SpanCollector, chrome_trace
    from ..obs.tracing import tracer
    from ..resilience import FaultRule, RetryPolicy, faults
    from ..serving import (DistributedServingServer, DriverRegistry,
                           remote_worker_loop)

    def echo(df):
        replies = np.empty(len(df), object)
        replies[:] = [HTTPResponseData(status_code=200,
                                       entity=(r.entity or b"").upper())
                      for r in df["request"]]
        return df.with_column("reply", replies)

    snap_before = _registry.snapshot()
    driver = DriverRegistry(heartbeat_timeout=0.75).start()
    server = DistributedServingServer(
        service, driver.address, lease_timeout=2.0, reply_timeout=15.0,
        load_report_interval=0.2).start()
    stop = threading.Event()
    workers = [threading.Thread(
        target=remote_worker_loop,
        args=(driver.address, service, echo),
        kwargs={"stop_event": stop, "heartbeat_interval": 0.2,
                "max_batch": 4, "worker_id": f"chaos-w{i}"},
        daemon=True) for i in range(n_workers)]
    rules = [
        FaultRule(point="worker.death", kind="kill", p=1.0,
                  after=kill_after_leases, times=1),
        FaultRule(point="http.send", kind="error", p=error_rate,
                  status=503, retry_after=0.05),
        FaultRule(point="http.send", kind="latency", p=latency_rate,
                  latency_s=latency_spike_s),
    ]
    policy = RetryPolicy(seed=seed, base_delay=0.02, max_delay=0.5,
                         max_attempts=5)
    statuses: list[int] = []
    trace_ids: list[str] = []
    url = f"http://{server.address[0]}:{server.address[1]}/"
    try:
        with SpanCollector() as collector, faults(seed, rules) as inj:
            for w in workers:
                w.start()
            for i in range(n_requests):
                # client-side root span: the trace id every downstream
                # hop (ingest, lease, worker, reply) joins
                with tracer.span("client.request", i=i) as sp:
                    trace_ids.append(sp.trace_id)
                    resp = send_request(
                        HTTPRequestData(url=url, method="POST",
                                        headers={},
                                        entity=f"req-{i}".encode()),
                        timeout=request_timeout_s, policy=policy)
                statuses.append(resp.status_code)
            schedule = inj.schedule()
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=5)
        server.stop()
        driver.stop()
    # span-tree completeness per answered request (trace acceptance)
    names = collector.names_by_trace()
    answered_trees = {t: sorted(n for n in names.get(t, set()) if n)
                      for t, s in zip(trace_ids, statuses)
                      if 200 <= s < 300}
    complete = {t for t, ns in answered_trees.items()
                if COMPLETE_TRACE_SPANS <= set(ns)}
    sampled = None
    trace_path = None
    if complete:
        sample_id = sorted(complete)[0]
        sampled = {"trace_id": sample_id,
                   "spans": answered_trees[sample_id]}
        if trace_dir is not None:
            spans = [d for d in collector.spans()
                     if d.get("traceId") in complete]
            trace_path = _os.path.join(trace_dir, "chaos_trace.json")
            with open(trace_path, "w") as f:
                _json.dump(chrome_trace(spans, extra_metadata={
                    "scenario": "chaos", "seed": seed,
                    "sampled_trace_id": sample_id}), f)
    snap = _registry.snapshot()

    def _delta(prefix: str) -> float:
        return sum(v - snap_before.get(k, 0.0)
                   for k, v in snap.items() if k.startswith(prefix))

    answered = sum(1 for s in statuses if 200 <= s < 300)
    policy_sheds = sum(1 for s in statuses if s in (429, 503))
    return {
        "offered": n_requests,
        "answered_200": answered,
        "policy_sheds": policy_sheds,
        "answered_traces": len(answered_trees),
        "complete_traces": len(complete),
        "sampled_trace": sampled,
        "trace_path": trace_path,
        "transport_errors": sum(1 for s in statuses if s == 0),
        "non_policy_errors": sum(
            1 for s in statuses
            if not (200 <= s < 300) and s not in (429, 503)),
        "schedule": schedule,
        "retries_taken": _delta("resilience_retry_total"),
        "faults_injected": _delta("resilience_faults_injected_total"),
        "lease_replays": _delta("serving_lease_replays_total"),
        "worker_deaths_detected": _delta("resilience_worker_deaths_total"),
        "breaker_state_present": any(
            k.startswith("resilience_breaker_state") for k in snap),
        "retry_total_present": any(
            k.startswith("resilience_retry_total") for k in snap),
        "lease_replays_present": any(
            k.startswith("serving_lease_replays_total") for k in snap),
    }


# --------------------------------------------------- mixed-tenant elasticity
# one synthetic tenant per reference workload family: cognitive HTTP
# featurizers (small, latency-sensitive), LightGBM scoring (medium), and
# continuous generation (heavy, throughput-oriented). cost_s is the
# per-item service time the synthetic executors charge; base/swing shape
# the diurnal rate base + swing*(1-cos(2*pi*t/period))/2; burst
# multiplies the rate inside the mid-period burst window (the 2x
# overload the best-effort tier must absorb).
MIXED_TENANTS = {
    "cognitive": dict(tier="gold", cost_s=0.002, base=40.0, swing=80.0,
                      burst=1.0),
    "lightgbm": dict(tier="silver", cost_s=0.005, base=15.0, swing=30.0,
                     burst=1.0),
    "generate": dict(tier="best_effort", cost_s=0.010, base=10.0,
                     swing=30.0, burst=2.0),
}

_BURST_WINDOW = (0.35, 0.65)   # fraction of each period the burst covers


def _diurnal_rate(spec: dict, t: float, period_s: float) -> float:
    import math as _math
    phase = (t % period_s) / period_s
    r = spec["base"] + spec["swing"] * 0.5 * (
        1.0 - _math.cos(2.0 * _math.pi * phase))
    if spec.get("burst", 1.0) > 1.0 and \
            _BURST_WINDOW[0] <= phase <= _BURST_WINDOW[1]:
        r *= spec["burst"]
    return r


def _arrival_schedule(spec: dict, period_s: float,
                      duration_s: float) -> list[float]:
    """Deterministic arrival times for one tenant (pure function of the
    spec — two runs offer the identical request sequence, which is what
    makes the realized fault schedule a pure function of the seed)."""
    out = []
    t = 0.0
    while True:
        t += 1.0 / max(_diurnal_rate(spec, t, period_s), 1e-6)
        if t >= duration_s:
            return out
        out.append(t)


def _pctl(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return float("nan")
    return sorted_vals[max(_ceil(q * len(sorted_vals)) - 1, 0)]


def mixed_tenant_scenario(*, service: str = "tenant-bench",
                          seed: int = 23,
                          period_s: float = 2.5, periods: int = 2,
                          cooloff_s: float = 1.5,
                          max_queue: int = 128, max_batch: int = 8,
                          worker_max: int = 4,
                          gold_slo_s: float = 0.6,
                          silver_slo_s: float = 1.2,
                          be_rate_cap: float = 30.0,
                          utilization_floor: float = 0.15,
                          slow_factor: float = 3.0,
                          predictive: bool = False,
                          registry=None) -> dict:
    """Long-running mixed-workload elasticity acceptance (ISSUE 9).

    Three tenants — cognitive HTTP (gold), LightGBM scoring (silver),
    continuous generation (best-effort) — offer diurnal load into ONE
    tenancy-enabled :class:`~mmlspark_tpu.sched.RequestScheduler`
    (weighted-fair dispatch, tier deadlines, per-tenant quotas), drained
    by an autoscaled pool of synthetic workers while a seeded fault
    schedule runs: one worker killed mid-lease (its batch replayed via
    ``put_front`` — the lease-replay contract), one worker persistently
    degraded (``worker.slow``: sick-but-alive), 5%% injected 503s and
    latency spikes on the client hop. The best-effort tenant doubles its
    offered rate inside each period's burst window (the 2x overload).

    The contract measured (and returned as ``within_*`` flags so the
    test and the bench JSON assert the same surface):

    - **gold p99 <= its SLO tier deadline and ZERO gold sheds** while
      best-effort absorbs the burst as 429s (rate-quota + queue-share
      sheds with Retry-After from ITS bucket's refill time);
    - **silver p99 <= its SLO**;
    - the autoscaler's worker count **tracks the diurnal curve** (up at
      peak, back down after) and **never acts during cooldown**;
    - all in-flight work on killed/drained workers **completes via the
      replay path** — every admitted request reaches a terminal state;
    - **utilization stays above the floor** (busy seconds / alive
      worker seconds): elasticity, not over-provisioning.

    Reproducible by seed: arrivals are precomputed (pure function of
    the specs) and fault decisions are pure functions of per-rule probe
    counts, so two runs realize the same ``schedule`` (compared sorted:
    thread interleaving may reorder firings across points, never change
    them).

    ``predictive=True`` (ISSUE 12) arms the autoscaler's trend-
    extrapolated capacity prediction, priced by the scheduler
    estimator's (cost-model-backed) per-item service time — the result
    additionally reports ``scale_up_lag_s``, the gap between the
    offered load's diurnal rise and the first scale-up (smaller =
    the pool leads the curve), with the gold-tier contract unchanged.
    """
    import queue as _queue

    from ..obs.metrics import registry as _default
    from ..resilience import FaultRule, WorkerKilled, faults
    from ..resilience.faults import injector as _inj
    from ..sched import (RequestScheduler, Shed, Tenancy, TenantQuota)
    from ..serving.autoscale import Autoscaler, AutoscaleConfig

    reg = registry if registry is not None else _default
    duration_s = period_s * periods
    tenancy = Tenancy(
        service,
        quotas={
            "cognitive": TenantQuota(tier="gold"),
            "lightgbm": TenantQuota(tier="silver"),
            "generate": TenantQuota(tier="best_effort",
                                    rate=be_rate_cap,
                                    burst=max(be_rate_cap / 3.0, 1.0),
                                    queue_share=0.25),
        },
        tier_deadlines={"gold": gold_slo_s, "silver": silver_slo_s},
        registry=reg)
    sched = RequestScheduler(
        service, max_queue=max_queue, tenancy=tenancy, registry=reg,
        on_shed=lambda item, reason, retry_after: item.reply(429))
    # prime the estimator so predictive admission has a model from the
    # first request (same rationale as overload_scenario)
    sched.estimator.observe(1, 0.004)
    m_deaths = reg.counter(
        "resilience_worker_deaths_total",
        "workers marked dead by registry heartbeat liveness, by service")
    m_replays = reg.counter(
        "serving_lease_replays_total",
        "requests replayed because their lease expired (worker death)")

    class _Worker:
        __slots__ = ("thread", "stop", "draining", "killed", "busy_s",
                     "items", "started", "ended")

        def __init__(self):
            self.thread = None
            self.stop = threading.Event()
            self.draining = False
            self.killed = False
            self.busy_s = 0.0
            self.items = 0
            self.started = time.monotonic()
            self.ended = None

    class _Pool:
        """Synthetic autoscalable worker pool with the mesh's lease
        semantics: a worker holds a lease on its executing batch; a
        killed worker strands it; the monitor detects the death, counts
        it like the registry's failure detector, and replays unanswered
        items to the FRONT of the queue (put_front — the resilience
        contract). Drained workers finish and reply their batch first."""

        def __init__(self):
            self._lock = threading.Lock()
            self.workers: dict[str, _Worker] = {}
            self.leases: dict[str, list] = {}
            self.replays = 0
            self._seq = 0

        def count(self):
            with self._lock:
                return sum(1 for w in self.workers.values()
                           if w.thread.is_alive() and not w.draining
                           and not w.killed)

        def scale_up(self):
            with self._lock:
                wid = f"w{self._seq}"
                self._seq += 1
                w = _Worker()
                w.thread = threading.Thread(
                    target=self._run, args=(wid, w), daemon=True)
                self.workers[wid] = w
                w.thread.start()
            return wid

        def scale_down(self):
            with self._lock:
                live = [(w.started, wid) for wid, w in
                        self.workers.items()
                        if w.thread.is_alive() and not w.draining
                        and not w.killed]
                if not live:
                    return None
                _, wid = max(live)   # newest first (LIFO)
                self.workers[wid].draining = True
                self.workers[wid].stop.set()
            return wid

        def _run(self, wid, w):
            try:
                while not w.stop.is_set():
                    batch = sched.next_batch(max_batch=max_batch,
                                             max_wait=0.05)
                    if not batch:
                        continue
                    with self._lock:
                        self.leases[wid] = batch
                    # injection points mirror the real compute loop:
                    # a kill strands the lease; a slow rule arms the
                    # persistent sick-but-alive degradation
                    _inj.apply("worker.death", key=wid)
                    _inj.apply("worker.slow", key=wid)
                    cost = sum(i.cost for i in batch) \
                        * _inj.degradation(wid)
                    time.sleep(cost)
                    w.busy_s += cost
                    w.items += len(batch)
                    sched.estimator.observe(len(batch), cost)
                    for item in batch:
                        tenancy.observe_latency(
                            item.tenant,
                            time.monotonic() - item.submitted)
                        item.reply(200)
                    with self._lock:
                        self.leases.pop(wid, None)
            except WorkerKilled:
                w.killed = True   # lease stays: the monitor replays it
            finally:
                w.ended = time.monotonic()

        def monitor(self, stop_ev):
            """The failure detector + lease replayer (what the driver
            registry and ingest lease monitor do in the real mesh)."""
            while not stop_ev.wait(0.05):
                dead = []
                with self._lock:
                    for wid, w in self.workers.items():
                        if wid in self.leases and (
                                w.killed or not w.thread.is_alive()):
                            dead.append((wid, self.leases.pop(wid)))
                for wid, batch in dead:
                    m_deaths.inc(1, service=service)
                    for item in batch:
                        if item._event.is_set():
                            continue
                        self.replays += 1
                        m_replays.inc(1, service=service)
                        try:
                            sched.put_front(item)
                        except _queue.Full:
                            item.reply(503)

        def stop(self):
            with self._lock:
                ws = list(self.workers.values())
            for w in ws:
                w.stop.set()
            sched.wake()
            for w in ws:
                w.thread.join(timeout=5)
                if w.ended is None:
                    w.ended = time.monotonic()

    pool = _Pool()
    auto = Autoscaler(
        service, pool,
        AutoscaleConfig(min_workers=1, max_workers=worker_max,
                        interval=0.1, queue_high=6.0, queue_low=1.5,
                        slo_high=0.8, slo_low=0.4, up_stable=2,
                        down_stable=5, cooldown=0.6,
                        predictive=predictive, lead_ticks=5,
                        history_ticks=8, wait_high=0.25),
        registry=reg, tenancy=tenancy,
        item_seconds=sched.estimator.item_seconds)

    rules = [
        # one worker killed mid-lease: the SECOND worker the autoscaler
        # spawns, a few batches in (match targets its stable id)
        FaultRule(point="worker.death", kind="kill", match="w1",
                  after=4, times=1),
        # one worker persistently degraded from its 4th batch on: the
        # sick-but-alive case capacity planning must absorb
        FaultRule(point="worker.slow", kind="slow", match="w0",
                  after=3, times=1, factor=slow_factor),
        # client-hop chaos: 5% injected 503s + 5% latency spikes
        FaultRule(point="client.send", kind="error", p=0.05,
                  status=503, retry_after=0.05),
        FaultRule(point="client.send", kind="latency", p=0.05,
                  latency_s=0.02),
    ]

    class _TenantResult:
        __slots__ = ("requests", "intake_sheds", "retry_afters",
                     "injected_503")

        def __init__(self):
            self.requests = []
            self.intake_sheds = {}
            self.retry_afters = []
            self.injected_503 = 0

    results = {name: _TenantResult() for name in MIXED_TENANTS}
    arrivals = {name: _arrival_schedule(spec, period_s, duration_s)
                for name, spec in MIXED_TENANTS.items()}
    samples: list[tuple[float, int]] = []
    stop_all = threading.Event()
    t0 = time.monotonic()

    def load(name, spec, res):
        for t_rel in arrivals[name]:
            wait = (t0 + t_rel) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            # the client hop's injection point: latency spikes sleep
            # here; an injected error is a client-visible 503 (counted,
            # not re-offered — re-offers would make the probe count
            # interleaving-dependent and break schedule reproducibility)
            act = _inj.apply("client.send", key=name)
            if act is not None and act.kind == "error":
                res.injected_503 += 1
                continue
            req = _SynthRequest()
            req.cost = spec["cost_s"]
            try:
                sched.submit(req, tenant=name)
                res.requests.append(req)
            except Shed as s:
                res.intake_sheds[s.reason] = \
                    res.intake_sheds.get(s.reason, 0) + 1
                res.retry_afters.append(s.retry_after)

    def sampler():
        while not stop_all.wait(0.05):
            samples.append((time.monotonic() - t0, pool.count()))

    with faults(seed, rules, inj=_inj) as inj:
        auto.start()
        mon = threading.Thread(target=pool.monitor, args=(stop_all,),
                               daemon=True)
        mon.start()
        smp = threading.Thread(target=sampler, daemon=True)
        smp.start()
        loaders = [threading.Thread(target=load, args=(n, s, results[n]),
                                    daemon=True)
                   for n, s in MIXED_TENANTS.items()]
        for th in loaders:
            th.start()
        for th in loaders:
            th.join(timeout=duration_s + 30)
        # drain: every admitted request must reach a terminal state
        # (reply, expiry shed, or replay-then-reply)
        drain_end = time.monotonic() + 10.0
        while time.monotonic() < drain_end:
            if sched.qsize() == 0 and not pool.leases:
                break
            time.sleep(0.05)
        # cool-off with zero offered load: the autoscaler must walk the
        # pool back down the diurnal curve
        time.sleep(cooloff_s)
        schedule = inj.schedule()
        stop_all.set()
        auto.stop()
        pool.stop()
        mon.join(timeout=5)
        smp.join(timeout=5)

    load_end = duration_s
    per_tenant = {}
    for name, res in results.items():
        lat = sorted((r.done_at - r.submitted) for r in res.requests
                     if r.status == 200 and r.done_at is not None)
        expired = sum(1 for r in res.requests if r.status == 429)
        unanswered = sum(1 for r in res.requests if r.status is None)
        sheds = dict(res.intake_sheds)
        if expired:
            sheds["expired"] = expired
        offered = len(arrivals[name])
        total_shed = sum(sheds.values())
        per_tenant[name] = {
            "tier": MIXED_TENANTS[name]["tier"],
            "offered": offered,
            "injected_503": res.injected_503,
            "answered_200": len(lat),
            "sheds": sheds,
            "shed_total": total_shed,
            "shed_rate": total_shed / max(offered, 1),
            "unanswered": unanswered,
            "p50_s": _pctl(lat, 0.50),
            "p99_s": _pctl(lat, 0.99),
            "retry_after_max": max(res.retry_afters, default=0),
        }

    # -- autoscale trajectory ------------------------------------------------
    events = auto.event_log()
    ups = [e for e in events if e.direction == "up"]
    downs = [e for e in events if e.direction == "down"]
    replaces = [e for e in events if e.direction == "replace"]
    acted = sorted([e for e in events if e.direction in ("up", "down")],
                   key=lambda e: e.t)
    cooldown_violations = sum(
        1 for a, b in zip(acted, acted[1:])
        if b.t - a.t < auto.config.cooldown - 0.01)
    in_peak = [c for t, c in samples
               if t < load_end
               and 0.3 <= (t % period_s) / period_s <= 0.8]
    peak_max = max(in_peak, default=0)
    final_count = samples[-1][1] if samples else 0

    # -- scale-up lead/lag vs the diurnal rise (ISSUE 12) --------------------
    # load-rise time: first instant the total offered rate crosses
    # halfway between its trough and peak (pure function of the specs —
    # comparable across runs); lag = first up-event minus that instant.
    # Smaller (or negative) = the pool LEADS the curve.
    grid = [i * 0.01 for i in range(int(period_s * 100) + 1)]
    totals = [sum(_diurnal_rate(spec, t, period_s)
                  for spec in MIXED_TENANTS.values()) for t in grid]
    rise_level = min(totals) + 0.5 * (max(totals) - min(totals))
    load_rise_s = next((t for t, r in zip(grid, totals)
                        if r >= rise_level), 0.0)
    first_up_s = min((e.t - t0 for e in ups), default=None)
    scale_up_lag_s = (first_up_s - load_rise_s
                      if first_up_s is not None else None)

    # -- utilization ---------------------------------------------------------
    busy = sum(w.busy_s for w in pool.workers.values())
    alive = sum((w.ended - w.started) for w in pool.workers.values()
                if w.ended is not None)
    utilization = busy / alive if alive > 0 else 0.0
    per_item = {wid: w.busy_s / w.items
                for wid, w in pool.workers.items() if w.items}
    healthy = [v for wid, v in per_item.items() if wid != "w0"]
    sick_ratio = (per_item.get("w0", 0.0)
                  / (sorted(healthy)[len(healthy) // 2]
                     if healthy else 1.0))

    gold = per_tenant["cognitive"]
    silver = per_tenant["lightgbm"]
    be = per_tenant["generate"]
    total_unanswered = sum(p["unanswered"] for p in per_tenant.values())
    return {
        "seed": seed,
        "period_s": period_s,
        "periods": periods,
        "per_tenant": per_tenant,
        "gold_p99_s": gold["p99_s"],
        "gold_slo_s": gold_slo_s,
        "gold_sheds": gold["shed_total"],
        "silver_p99_s": silver["p99_s"],
        "silver_slo_s": silver_slo_s,
        "be_sheds": be["shed_total"],
        "be_retry_after_max": be["retry_after_max"],
        "within_gold_slo": bool(gold["p99_s"] <= gold_slo_s
                                and gold["shed_total"] == 0),
        "within_silver_slo": bool(silver["p99_s"] <= silver_slo_s),
        "be_absorbed_burst": bool(be["shed_total"] > 0),
        "workers_peak": peak_max,
        "workers_final": final_count,
        "predictive": bool(predictive),
        "load_rise_s": load_rise_s,
        "first_up_s": first_up_s,
        "scale_up_lag_s": scale_up_lag_s,
        "autoscale_ups": len(ups),
        "autoscale_downs": len(downs),
        "autoscale_replaces": len(replaces),
        "cooldown_violations": cooldown_violations,
        "scaled_with_diurnal": bool(peak_max >= 2 and len(ups) >= 1
                                    and len(downs) >= 1
                                    and final_count < peak_max),
        "lease_replays": pool.replays,
        "worker_killed": any(p == "worker.death" for p, *_ in schedule),
        "worker_degraded": any(p == "worker.slow" for p, *_ in schedule),
        "sick_worker_cost_ratio": sick_ratio,
        "unanswered": total_unanswered,
        "drained_completed": bool(total_unanswered == 0),
        "utilization": utilization,
        "utilization_floor": utilization_floor,
        "within_utilization_floor": bool(utilization
                                         >= utilization_floor),
        "count_samples": samples,
        "schedule": sorted(schedule),
    }


# ----------------------------------------------------- fleet telemetry chaos
def fleet_chaos_scenario(*, service: str = "fleet-bench", seed: int = 31,
                         n_workers: int = 3, base_step_s: float = 0.01,
                         slow_factor: float = 6.0, wave_size: int = 6,
                         warmup_waves: int = 4, max_flag_waves: int = 40,
                         max_recover_s: float = 20.0,
                         request_timeout_s: float = 20.0) -> dict:
    """Fleet-plane chaos acceptance (ISSUE 15): a real worker mesh
    (driver registry, one ingest, ``n_workers`` in-thread compute
    workers whose transform sleeps ``base_step_s`` per batch — a
    deterministic service time the slow-factor stretch is visible
    against) driven in waves while the fleet health plane watches.

    The trajectory measured, phase by phase:

    1. **healthy warmup** — ``GET /healthz`` (via
       :meth:`~mmlspark_tpu.obs.fleet.FleetHealth.healthz_payload`, the
       exact body the route serves) answers ``ok``;
    2. **injected straggler** — a ``worker.slow`` rule arms a
       persistent ``slow_factor`` degradation on one worker; the
       scenario counts waves (one health tick per wave) until
       ``fleet_straggler{worker=...}`` flips — the detection latency —
       and the :class:`~mmlspark_tpu.serving.autoscale.Autoscaler`,
       ticked on the same cadence, must record a ``replace`` event
       sourced from the straggler signal (``reason="straggler
       flagged"``). Healthz now answers ``degraded`` (still HTTP 200:
       a slow fleet must not be drained by its load balancer);
    3. **replacement** — a ``worker.death`` kill takes the flagged
       worker mid-lease: the lease monitor detects, replays its
       stranded batch to survivors, and evicts its fleet source (the
       ``remove_matching`` sweep also clears its step series from the
       shared registry), after which the detector unflags and healthz
       returns to ``ok``.

    Tenant traffic rides along under a :class:`~mmlspark_tpu.sched.\
Tenancy` (gold ``search`` / best-effort ``batch``) so the burn-rate
    side of the verdict is live: gold takes zero sheds (burn 0, below
    the page threshold throughout — the acceptance bound) while one
    controlled ``batch`` shed keeps ``slo_burn_rate`` visibly nonzero
    without ever crossing the degraded threshold at the final tick.

    Scenario isolation: worker ids are per-scenario, so any
    worker-labelled step series / fleet sources lingering from earlier
    scenarios in this process are scrubbed first — the straggler
    median must only see THIS run's ranks. On exit the scenario evicts
    its own sources the same way.
    """
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from ..io.http.clients import send_request
    from ..io.http.schema import HTTPRequestData, HTTPResponseData
    from ..obs.fleet import FleetHealth, fleet_aggregator, parse_sample
    from ..obs.memory import device_memory_stats
    from ..obs.tracing import tracer
    from ..resilience import FaultRule, faults
    from ..sched import Shed, Tenancy, TenantQuota
    from ..serving import (DistributedServingServer, DriverRegistry,
                           remote_worker_loop)
    from ..serving.autoscale import Autoscaler, AutoscaleConfig

    # -- scenario isolation: scrub residue from earlier runs ----------------
    for src in list(fleet_aggregator.sources()):
        fleet_aggregator.evict(src, reason="scenario_reset")
    stale = {labels["worker"] for k in _registry.snapshot()
             for _, labels in (parse_sample(k),) if "worker" in labels}
    for prefix in ("profile_step_seconds", "fleet_"):
        for m in _registry.metrics(prefix):
            for w in stale:
                m.remove_matching(worker=w)

    def stepped(df):
        time.sleep(base_step_s)   # deterministic per-batch service time
        replies = np.empty(len(df), object)
        replies[:] = [HTTPResponseData(status_code=200,
                                       entity=(r.entity or b"").upper())
                      for r in df["request"]]
        return df.with_column("reply", replies)

    ten = Tenancy(service, quotas={
        "search": TenantQuota(tier="gold"),
        "batch": TenantQuota(tier="best_effort"),
    })
    health = FleetHealth(fleet_aggregator, service=service)
    health.attach_tenancy(ten)

    class _FakePool:
        """Synthetic capacity counter: the autoscaler's straggler path
        only needs count/scale_up (real pools are chaos_scenario's
        business)."""

        def __init__(self, n):
            self.n = n

        def count(self):
            return self.n

        def scale_up(self):
            self.n += 1
            return f"replacement-{self.n}"

        def scale_down(self):
            self.n -= 1

    pool = _FakePool(n_workers)
    auto = Autoscaler(
        service, pool,
        AutoscaleConfig(min_workers=n_workers, max_workers=n_workers + 2,
                        interval=0.05, queue_high=1e9, queue_low=-1.0,
                        slo_high=1e9, slo_low=-1.0, cooldown=0.0),
        registry=_registry, tenancy=ten)

    straggler_spans: list = []

    def _sink(sp):
        if sp.name == "fleet.straggler":
            straggler_spans.append(sp)

    wids = [f"fleet-w{i}" for i in range(n_workers)]
    w0 = wids[0]
    driver = DriverRegistry(heartbeat_timeout=0.75).start()
    server = DistributedServingServer(
        service, driver.address, lease_timeout=2.0, reply_timeout=15.0,
        load_report_interval=0.2).start()
    stops = [threading.Event() for _ in wids]
    workers = [threading.Thread(
        target=remote_worker_loop,
        args=(driver.address, service, stepped),
        kwargs={"stop_event": stops[i], "heartbeat_interval": 0.1,
                "max_batch": 4, "worker_id": wids[i]},
        daemon=True) for i in range(n_workers)]
    url = f"http://{server.address[0]}:{server.address[1]}/"
    pump = ThreadPoolExecutor(max_workers=wave_size)
    shed_at = wave_size * warmup_waves   # first post-baseline request
    statuses: list[int] = []
    sheds: dict = {}
    seq = [0]

    def send_wave(count, tenant_for=None):
        futs = []
        for _ in range(count):
            i = seq[0]
            seq[0] += 1
            tenant = tenant_for or ("batch" if i % 4 == 0 else "search")
            if i == shed_at:
                # ONE controlled best-effort shed: slo_burn_rate gets
                # a visible numerator without the trajectory depending
                # on quota timing
                ten.count_shed("batch", "tenant_rate")
                sheds["batch"] = sheds.get("batch", 0) + 1
                continue
            try:
                ten.try_admit(tenant, "/", 0, 128)
            except Shed as s:
                sheds[tenant] = sheds.get(tenant, 0) + 1
                sheds[s.reason] = sheds.get(s.reason, 0) + 1
                continue
            t0 = time.monotonic()
            futs.append((tenant, t0, pump.submit(
                send_request,
                HTTPRequestData(url=url, method="POST", headers={},
                                entity=f"req-{i}".encode()),
                timeout=request_timeout_s)))
        for tenant, t0, f in futs:
            resp = f.result()
            statuses.append(resp.status_code)
            ten.release(tenant)
            ten.observe_latency(tenant, time.monotonic() - t0)

    ticks_to_flag = None
    recovered = False
    recover_waves = 0
    evicted = False
    schedule_a: list = []
    schedule_b: list = []
    tracer.add_sink(_sink)
    try:
        for w in workers:
            w.start()
        # phase 1: healthy warmup → baseline tick → verdict must be ok
        for _ in range(warmup_waves):
            send_wave(wave_size)
        status_start, _ = health.healthz_payload()
        verdict_start = health.verdict()
        auto.tick()
        # phase 2: arm the persistent degradation; tick per wave until
        # the flag flips (detection latency, in waves)
        with faults(seed, [FaultRule(point="worker.slow", kind="slow",
                                     match=w0, times=1,
                                     factor=slow_factor)]) as inj:
            for t_i in range(max_flag_waves):
                send_wave(wave_size)
                health.tick()
                auto.tick()
                if ("worker", w0) in health.stragglers.flagged():
                    ticks_to_flag = t_i + 1
                    break
            schedule_a = inj.schedule()
            status_flag, _ = health.healthz_payload()
            verdict_flag = health.verdict()
            auto.tick()
        # phase 3: kill the flagged worker mid-lease — the real death
        # path replays its batch, evicts its fleet source, and the
        # remove_matching sweep clears its series; verdict walks home
        with faults(seed + 1, [FaultRule(point="worker.death",
                                         kind="kill", match=w0,
                                         times=1)]) as inj2:
            deadline = time.monotonic() + max_recover_s
            while time.monotonic() < deadline:
                send_wave(wave_size)
                recover_waves += 1
                health.tick()
                auto.tick()
                gone = f"worker:{w0}" not in fleet_aggregator.sources()
                if gone and ("worker", w0) not in \
                        health.stragglers.flagged():
                    recovered = True
                    break
            schedule_b = inj2.schedule()
        evicted = f"worker:{w0}" not in fleet_aggregator.sources()
        # settle: batch-heavy traffic bounds the final burn ratio
        # (1 shed / >20 admits) well under the degraded threshold
        for _ in range(2):
            send_wave(10, tenant_for="batch")
        status_end, _ = health.healthz_payload()
        verdict_end = health.verdict()
    finally:
        tracer.remove_sink(_sink)
        for ev in stops:
            ev.set()
        for w in workers:
            w.join(timeout=5)
        server.stop()
        driver.stop()
        pump.shutdown(wait=False)
        for wid in wids:
            fleet_aggregator.evict(f"worker:{wid}",
                                   reason="scenario_end")

    burns = health.burn.latest()
    gold_burn = max(burns.get("search", {}).values(), default=0.0)
    be_burn = max(burns.get("batch", {}).values(), default=0.0)
    replaces = [e for e in auto.event_log()
                if e.direction == "replace"
                and e.reason == "straggler flagged"]
    verdicts = [verdict_start, verdict_flag, verdict_end]
    return {
        "seed": seed,
        "workers": n_workers,
        "slow_worker": w0,
        "slow_factor": slow_factor,
        "offered": seq[0],
        "answered_200": sum(1 for s in statuses if 200 <= s < 300),
        "transport_errors": sum(1 for s in statuses if s == 0),
        "sheds": dict(sheds),
        "ticks_to_flag": ticks_to_flag,
        "flagged": bool(ticks_to_flag is not None),
        "straggler_spans": len(straggler_spans),
        "verdicts": verdicts,
        "healthz_statuses": [status_start, status_flag, status_end],
        "healthz_flipped": bool(verdicts == ["ok", "degraded", "ok"]),
        "straggler_replaces": len(replaces),
        "workers_after_replace": pool.count(),
        "recovered": recovered,
        "recover_waves": recover_waves,
        "evicted": evicted,
        "worker_degraded": any(p == "worker.slow"
                               for p, *_ in schedule_a),
        "worker_killed": any(p == "worker.death"
                             for p, *_ in schedule_b),
        "gold_burn": gold_burn,
        "be_burn": be_burn,
        "page_burn": health.page_burn,
        "gold_under_page": bool(gold_burn < health.page_burn),
        "hbm_devices": len(device_memory_stats()),
        "mem_gauges_present": any(k.startswith("mem_hbm_")
                                  for k in _registry.snapshot()),
    }


# --------------------------------------------------- whole-pipeline fusion
def _fusion_pipelines(n_rows: int, width: int, seed: int = 7):
    """The two benchmark pipelines of the whole-pipeline-compilation
    acceptance (ISSUE 10): a featurize→infer→postproc chain shaped like
    the image-featurizer serving path (dense feature matrix through a
    model head), and a text featurize→encoder chain whose tokenizer is
    genuinely host-bound (string ops split the fused span)."""
    import numpy as np
    import jax.numpy as jnp

    from ..core import DataFrame, PipelineModel
    from ..featurize import CleanMissingData, VectorAssembler
    from ..stages import SelectColumns, UDFTransformer

    rng = np.random.default_rng(seed)

    # -- featurizer pipeline: clean → assemble → model head → postproc
    feat_df = DataFrame({
        "img": rng.normal(size=(n_rows, width)).astype(np.float32),
        "aux": np.where(rng.random(n_rows) < 0.25, np.nan,
                        rng.normal(size=n_rows)).astype(np.float32),
    })
    w1 = jnp.asarray(rng.normal(size=(width + 1, 128)) * 0.05,
                     jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(128, 10)) * 0.05, jnp.float32)
    clean = CleanMissingData(inputCols=["aux"],
                             cleaningMode="Mean").fit(feat_df)
    feat_pm = PipelineModel([
        clean,
        VectorAssembler(inputCols=["img", "aux"], outputCol="features",
                        handleInvalid="keep"),
        UDFTransformer(inputCol="features", outputCol="logits",
                       jitSafe=True,
                       udf=lambda f: jnp.tanh(f @ w1) @ w2),
        UDFTransformer(inputCol="logits", outputCol="pred", jitSafe=True,
                       udf=lambda z: jnp.argmax(z, axis=-1)
                       .astype(jnp.float32)),
        SelectColumns(cols=["pred"]),
    ])

    # -- text pipeline: host tokenizer → embed+encode (BERT-shaped) → pool
    seq, vocab, dim = 16, 512, 64
    texts = np.empty(n_rows, object)
    texts[:] = [" ".join(rng.choice(["the", "cat", "sat", "on", "mat",
                                     "dog", "ran", "fast", "tpu", "jit"],
                                    size=8)) for _ in range(n_rows)]
    text_df = DataFrame({"text": texts})

    def tokenize(col):
        # genuinely host-bound: python string hashing per token
        ids = np.zeros((len(col), seq), np.int32)
        for i, s in enumerate(col):
            for j, tok in enumerate(str(s).split()[:seq]):
                ids[i, j] = (hash(tok) & 0x7FFFFFFF) % vocab
        return ids

    emb = jnp.asarray(rng.normal(size=(vocab, dim)) * 0.05, jnp.float32)
    wq = jnp.asarray(rng.normal(size=(dim, dim)) * 0.05, jnp.float32)
    wo = jnp.asarray(rng.normal(size=(dim, 8)) * 0.05, jnp.float32)

    def encode(ids):
        x = emb[ids]                     # [n, seq, dim]
        a = jnp.einsum("nsd,de,nte->nst", x, wq, x)
        a = a / jnp.sqrt(jnp.float32(dim))
        x = x + jnp.einsum("nst,ntd->nsd", a, x)
        return jnp.tanh(x.mean(axis=1) @ wo)   # [n, 8]

    text_pm = PipelineModel([
        UDFTransformer(inputCol="text", outputCol="ids", udf=tokenize),
        UDFTransformer(inputCol="ids", outputCol="enc", jitSafe=True,
                       udf=encode),
        UDFTransformer(inputCol="enc", outputCol="score", jitSafe=True,
                       udf=lambda e: e.sum(axis=-1)),
        SelectColumns(cols=["score"]),
    ])
    return (feat_pm, feat_df, "pred"), (text_pm, text_df, "score")


def _bench_pipeline(pm, df, out_col: str, reps: int) -> dict:
    """Median e2e latency of eager per-stage vs compiled execution, the
    fused path's dispatch count, and bit-equivalence of the outputs."""
    import numpy as np

    cp = pm.compile(df)
    eager_out = pm.transform(df)
    fused_out = cp.transform(df)        # warmup = the one compile
    diff = float(np.max(np.abs(
        np.asarray(eager_out[out_col], np.float32)
        - np.asarray(fused_out[out_col], np.float32)))) \
        if len(df) else 0.0

    def _median(fn) -> float:
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(df)
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    eager_s = _median(pm.transform)
    fused_s = _median(cp.transform)
    return {
        "eager_ms": eager_s * 1e3,
        "fused_ms": fused_s * 1e3,
        "speedup": eager_s / max(fused_s, 1e-9),
        "segments": cp.compiled_segments,
        "eager_stages_in_plan": cp.eager_stages,
        # device dispatches for the traced portion + host stages that
        # still run between segments — the per-request dispatch count
        "dispatches": cp.compiled_segments + cp.eager_stages,
        "max_abs_diff": diff,
        "equivalent": bool(diff <= 1e-5),
        "plan": cp.describe(),
    }


def pipeline_fusion_scenario(*, n_rows: int = 64, width: int = 64,
                             reps: int = 30) -> dict:
    """Fused vs per-stage pipeline execution (whole-pipeline XLA
    compilation acceptance): the featurizer pipeline must fuse into ≤ 2
    dispatches per request and run ≥ 3× faster end to end than eager
    per-stage execution, bit-equivalent within 1e-5."""
    feat, text = _fusion_pipelines(n_rows, width)
    feat_r = _bench_pipeline(*feat, reps=reps)
    text_r = _bench_pipeline(*text, reps=reps)
    return {
        "featurizer": feat_r,
        "text": text_r,
        "featurizer_fused_le_2_dispatches": bool(
            feat_r["dispatches"] <= 2),
        "featurizer_speedup_ge_3x": bool(feat_r["speedup"] >= 3.0),
        "all_equivalent": bool(feat_r["equivalent"]
                               and text_r["equivalent"]),
    }


# --------------------------------------------------------------------- AOT
def _aot_bench_spec(n_rows: int, width: int, seed: int = 9):
    """A deterministic, fully param-fingerprintable serving pipeline
    (no callable params — those are AOT-ineligible by design) shaped
    like the featurizer serving path: clean → one-hot → assemble."""
    import numpy as np

    from ..core import DataFrame
    from ..featurize import CleanMissingData, VectorAssembler
    from ..featurize.vector import OneHotEncoderModel

    rng = np.random.default_rng(seed)
    aux = rng.normal(size=n_rows).astype(np.float32)
    aux[::5] = np.nan
    df = DataFrame({
        "img": rng.normal(size=(n_rows, width)).astype(np.float32),
        "aux": aux,
        "cat": rng.integers(0, 8, size=n_rows).astype(np.int32),
    })
    clean = CleanMissingData(inputCols=["aux"],
                             cleaningMode="Median").fit(df)
    stages = [
        clean,
        OneHotEncoderModel(inputCol="cat", outputCol="onehot",
                           categorySize=8, handleInvalid="keep"),
        VectorAssembler(inputCols=["img", "aux", "onehot"],
                        outputCol="features", handleInvalid="keep"),
    ]
    return stages, df


def aot_scale_up_scenario(*, n_rows: int = 64, width: int = 48,
                          reps: int = 80, seed: int = 9,
                          store_root: str | None = None) -> dict:
    """AOT executable-store acceptance (ISSUE 11): an autoscaler-added
    worker's first request must be as fast as its thousandth.

    The scenario builds the store once (the build step), measures a
    warmed worker's steady-state latency, then compares two scale-up
    events — each a FRESH :class:`~..core.compile.CompiledPipeline`
    whose fused segments have cold jit caches, exactly what a new
    worker process has:

    - **cold** (today's behavior, store uninstalled): the first request
      pays the full XLA compile at request latency;
    - **warm** (the tentpole): a real :class:`~..serving.autoscale
      .Autoscaler` decision scales the pool up, the new worker
      warm-loads the store, ``CompileTracker.mark_steady()`` arms the
      zero-runtime-compile assertion, and the first request must land
      within 2× the steady-state p99 with ``profile_runtime_compiles
      _total == 0`` and ≥ 1 store hit.

    Outputs are checked bit-equal (atol 0) between the AOT-loaded and
    runtime-compiled executables — same XLA program, same bits.
    """
    import shutil
    import tempfile

    import numpy as np

    from ..core import aot, compile_pipeline
    from ..obs.metrics import registry as _reg
    from ..obs.profile import compile_tracker
    from ..serving.autoscale import (Autoscaler, AutoscaleConfig,
                                     AutoscaleSignals)

    stages, example = _aot_bench_spec(n_rows, width, seed)

    def fresh_worker():
        """A new worker process's pipeline: fresh FusedSegments, cold
        jit caches (jit keys on the body's identity)."""
        return compile_pipeline(stages, example, service="aot-bench")

    def _sum(prefix):
        return sum(v for k, v in _reg.snapshot().items()
                   if k.startswith(prefix))

    owns_root = store_root is None
    root = store_root or tempfile.mkdtemp(prefix="mmlspark_tpu_aotb_")
    prev_store = aot.active_store()
    try:
        store = aot.AotStore(root)
        # -- the build step -------------------------------------------
        t0 = time.perf_counter()
        build_cp = fresh_worker()
        build_records = aot.build_pipeline(build_cp, example, store)
        build_wall_s = time.perf_counter() - t0

        # -- steady-state worker --------------------------------------
        aot.install(store)
        steady_cp = fresh_worker()
        steady_cp.warm_aot()
        ref = steady_cp.transform(example)  # warmed; also the reference
        lats = []
        for _ in range(reps):
            t0 = time.perf_counter()
            steady_cp.transform(example)
            lats.append(time.perf_counter() - t0)
        lats.sort()
        steady_p99_s = _pctl(lats, 0.99)

        # -- cold scale-up (the "before" picture) ---------------------
        aot.uninstall()
        cold_cp = fresh_worker()
        t0 = time.perf_counter()
        cold_out = cold_cp.transform(example)
        cold_first_s = time.perf_counter() - t0

        # -- warm scale-up through a real autoscaler decision ---------
        aot.install(store)
        hits0, miss0 = _sum("aot_store_hit_total"), \
            _sum("aot_store_miss_total")

        class _Pool:
            def __init__(self):
                self.workers = []

            def count(self):
                return len(self.workers)

            def scale_up(self):
                cp = fresh_worker()
                warmed = cp.warm_aot()
                self.workers.append((cp, warmed))
                return f"w{len(self.workers) - 1}"

            def scale_down(self):
                return self.workers.pop()[0] if self.workers else None

        pool = _Pool()
        scaler = Autoscaler(
            "aot-bench", pool,
            AutoscaleConfig(min_workers=1, max_workers=4, up_stable=1,
                            cooldown=0.0))
        scaler.ensure_min()
        decision = scaler.tick(AutoscaleSignals(queue_depth=1e4))
        new_cp, warmed = pool.workers[-1]
        compile_tracker.mark_steady()
        t0 = time.perf_counter()
        warm_out = new_cp.transform(example)
        warm_first_s = time.perf_counter() - t0
        runtime_compiles = compile_tracker.runtime_compiles()
        runtime_compiled = compile_tracker.runtime_compiled()
        compile_tracker.unmark_steady()
        hits = _sum("aot_store_hit_total") - hits0
        misses = _sum("aot_store_miss_total") - miss0

        equivalent = all(
            np.asarray(ref[c]).shape == np.asarray(warm_out[c]).shape
            and np.array_equal(np.asarray(ref[c]),
                               np.asarray(warm_out[c]))
            and np.array_equal(np.asarray(ref[c]),
                               np.asarray(cold_out[c]))
            for c in ref.columns)
        return {
            "build_wall_s": build_wall_s,
            "build_segments": sum(1 for r in build_records
                                  if r.get("built")),
            "store_entries": store.stats()["entries"],
            "steady_p99_s": steady_p99_s,
            "cold_first_s": cold_first_s,
            "warm_first_s": warm_first_s,
            "cold_over_steady": cold_first_s / max(steady_p99_s, 1e-9),
            "warm_over_steady": warm_first_s / max(steady_p99_s, 1e-9),
            "scale_decision": decision,
            "worker_warm_loaded": int(warmed),
            "store_hits": float(hits),
            "store_misses": float(misses),
            "runtime_compiles": int(runtime_compiles),
            "runtime_compiled": runtime_compiled,
            "equivalent": bool(equivalent),
            "warm_within_2x_steady": bool(
                warm_first_s <= 2.0 * steady_p99_s),
            "zero_runtime_compiles": bool(runtime_compiles == 0),
            "warm_hit_ge_1": bool(hits >= 1),
        }
    finally:
        compile_tracker.unmark_steady()
        if prev_store is not None:
            aot.install(prev_store)
        else:
            aot.uninstall()
        if owns_root:
            shutil.rmtree(root, ignore_errors=True)


# ------------------------------------------------- learned cost model
def synth_feature_rows(n_rows: int = 1200, *, seed: int = 5,
                       service: str = "costmodel-bench") -> list[dict]:
    """Deterministic FeatureLog-shaped rows with a known cost
    structure: three routes whose execute time depends on the padding
    bucket AND the entity size — the per-request signal a per-bucket
    EWMA cannot see, which is exactly where the learned model earns its
    keep. Noise is seeded; two calls produce identical rows."""
    import numpy as np

    from ..obs.profile import FEATURE_SCHEMA_VERSION
    from ..sched.policy import bucket_of

    rng = np.random.default_rng(seed)
    # route -> (base_ms, per-padded-row ms, per-KB ms)
    routes = {"/feat": (0.8, 0.05, 0.030),
              "/gbdt": (2.0, 0.15, 0.004),
              "/gen": (5.0, 0.40, 0.012)}
    names = sorted(routes)
    rows = []
    for i in range(n_rows):
        route = names[int(rng.integers(0, len(names)))]
        base, per_row, per_kb = routes[route]
        batch = int(rng.integers(1, 65))
        bucket = bucket_of(batch)
        entity_kb = float(rng.uniform(0.5, 200.0))
        depth = float(max(rng.normal(8.0, 4.0), 0.0))
        ms = (base + per_row * bucket + per_kb * entity_kb
              + float(rng.normal(0.0, 0.15)))
        rows.append({
            "service": service, "route": route, "batch": batch,
            "bucket": bucket, "padded_batch": bucket,
            "entity_bytes": entity_kb * 1024.0, "queue_depth": depth,
            "queue_ms": depth * 0.5, "execute_ms": max(ms, 0.05),
            "schema_version": FEATURE_SCHEMA_VERSION,
            "platform": "synthetic",
        })
    return rows


def costmodel_scenario(*, n_rows: int = 1200, seed: int = 5,
                       holdout: float = 0.25, registry=None) -> dict:
    """Learned-cost-model acceptance (ISSUE 12): train on the first
    (1 - holdout) of a synthetic FeatureLog stream, score BOTH brains
    on the held-out tail — the model predicts per row (bucket + entity
    bytes + depth), the EWMA baseline is a ``ServiceTimeEstimator`` fed
    the same training stream in arrival order, exactly as the scheduler
    trains it today. Banked: both MAEs and ``model_beats_ewma``."""
    from ..obs.metrics import registry as _default
    from ..perf.costmodel import CostModel
    from ..sched.policy import ServiceTimeEstimator

    reg = registry if registry is not None else _default
    service = "costmodel-bench"
    rows = synth_feature_rows(n_rows, seed=seed, service=service)
    n_train = int(len(rows) * (1.0 - holdout))
    train, held = rows[:n_train], rows[n_train:]

    model = CostModel(min_rows=32, registry=reg)
    used = model.fit(train)

    ewma = ServiceTimeEstimator(service, registry=reg)
    for r in train:
        ewma.observe(r["batch"], r["execute_ms"] / 1e3)

    model_abs, ewma_abs = [], []
    for r in held:
        actual = r["execute_ms"]
        pred = model.predict_batch_ms(
            service, r["batch"], route=r["route"],
            entity_bytes=r["entity_bytes"],
            queue_depth=r["queue_depth"], count=False)
        if pred is not None:
            model_abs.append(abs(pred - actual))
        est = ewma.estimate(r["batch"])
        if est is not None:
            ewma_abs.append(abs(est * 1e3 - actual))
    model_mae = (sum(model_abs) / len(model_abs)
                 if model_abs else float("nan"))
    ewma_mae = (sum(ewma_abs) / len(ewma_abs)
                if ewma_abs else float("nan"))

    # the fallback gate, exercised: a cold model must answer None
    cold = CostModel(min_rows=32, registry=reg)
    cold_pred = cold.predict_batch_ms(service, 8)
    return {
        "n_train": len(train), "n_holdout": len(held),
        "rows_used": used,
        "model_mae_ms": model_mae,
        "ewma_mae_ms": ewma_mae,
        "model_beats_ewma": bool(model_mae < ewma_mae),
        "model_covered": len(model_abs),
        "cold_falls_back": bool(cold_pred is None),
    }


def autoscale_lead_scenario(*, ticks: int = 200, period_ticks: int = 100,
                            base_rate: float = 2.0, swing: float = 30.0,
                            drain_per_worker: float = 4.0,
                            lead_ticks: int = 6,
                            registry=None) -> dict:
    """Predictive-autoscaling lead/lag acceptance (ISSUE 12), fully
    deterministic: a simulated diurnal arrival rate feeds a backlog
    that a synthetic pool drains at ``drain_per_worker`` per tick; the
    SAME simulation drives a reactive and a predictive
    :class:`~..serving.autoscale.Autoscaler` tick by tick. The metric
    is ticks between the load rise (the first tick arrivals exceed the
    minimum pool's drain capacity — when backlog starts building) and
    the first scale-up. Predictive must fire no later than reactive,
    and earlier once the trend is visible — scale-up LEADS the curve
    instead of trailing it."""
    import math as _math

    from ..obs.metrics import registry as _default
    from ..serving.autoscale import (Autoscaler, AutoscaleConfig,
                                     AutoscaleSignals)

    reg = registry if registry is not None else _default

    def rate(i: int) -> float:
        phase = (i % period_ticks) / period_ticks
        return base_rate + swing * 0.5 * (
            1.0 - _math.cos(2.0 * _math.pi * phase))

    def run(predictive: bool) -> dict:
        class _Pool:
            n = 1

            def count(self):
                return self.n

            def scale_up(self):
                self.n += 1

            def scale_down(self):
                self.n -= 1

        pool = _Pool()
        auto = Autoscaler(
            f"lead-{'pred' if predictive else 'react'}", pool,
            AutoscaleConfig(min_workers=1, max_workers=8,
                            queue_high=8.0, queue_low=1.0,
                            up_stable=2, down_stable=10, cooldown=0.0,
                            predictive=predictive,
                            lead_ticks=lead_ticks, history_ticks=8),
            registry=reg)
        backlog = 0.0
        rise_tick = up_tick = None
        for i in range(ticks):
            r = rate(i)
            if rise_tick is None and r > drain_per_worker:
                rise_tick = i   # backlog starts building here
            backlog = max(backlog + r - pool.n * drain_per_worker, 0.0)
            decision = auto.tick(AutoscaleSignals(queue_depth=backlog))
            if decision == "up" and up_tick is None:
                up_tick = i
        return {"rise_tick": rise_tick, "up_tick": up_tick,
                "lag_ticks": (up_tick - rise_tick
                              if up_tick is not None
                              and rise_tick is not None else None)}

    react = run(False)
    pred = run(True)
    both = (react["lag_ticks"] is not None
            and pred["lag_ticks"] is not None)
    return {
        "reactive": react,
        "predictive": pred,
        "lag_reactive_ticks": react["lag_ticks"],
        "lag_predictive_ticks": pred["lag_ticks"],
        "predictive_leads": bool(
            both and pred["lag_ticks"] < react["lag_ticks"]),
    }


def recorder_overhead_scenario(*, service: str = "recorder-bench",
                               n_requests: int = 600,
                               item_service_s: float = 0.002,
                               max_batch: int = 8,
                               reps: int = 3,
                               record_interval_s: float = 1.0,
                               registry_gauges: int = 120,
                               registry=None) -> dict:
    """History-plane overhead guard (ISSUE 16): the same synthetic
    serving pipeline as :func:`tracing_overhead_scenario` (scheduler +
    deterministic executor, no HTTP socket) measured with the
    time-series :class:`~mmlspark_tpu.obs.timeseries.Recorder` thread
    OFF vs ON at its production cadence (1 s), over a registry
    pre-seeded with ``registry_gauges`` extra gauge series so the
    snapshot walks a production-scale sample surface.

    The 1%% verdict is NOT read off the end-to-end p99 delta — a 1%%
    effect (~30 us here) sits below the host's run-to-run p99 drift,
    so an e2e diff would be a coin flip (the tracing guard's 5%% bound
    is already at that noise floor). Instead the bound is decomposed
    into two precisely measurable parts, and the e2e OFF/ON p99s ride
    along as reported context only:

    * ``overhead_pct`` — the recorder's amortized per-request share of
      p99: median synchronous tick cost (timed directly, us
      precision) x ``interarrival / record_interval_s``, over the
      pipeline's best-of-``reps`` bare p99.
    * ``affected_fraction`` — the collision geometry: a tick delays at
      most ~2 in-flight requests, so
      ``2 * interarrival / record_interval_s`` of requests can feel a
      tick at all. Kept below the 1%% tail cut, a colliding tick
      cannot reach the p99 statistic — the p99 request is a
      non-collided one paying only the amortized share."""
    from ..obs.metrics import MetricsRegistry
    from ..obs.timeseries import Recorder, TimeSeriesStore
    from ..sched import RequestScheduler

    reg = registry if registry is not None else MetricsRegistry()
    pad = reg.gauge("profile_bench_pad",
                    "synthetic sample surface for the overhead guard")
    for i in range(max(int(registry_gauges), 0)):
        pad.set(float(i), idx=str(i))

    def one_run(recording: bool) -> float:
        sched = RequestScheduler(
            f"{service}-{'on' if recording else 'off'}", registry=reg)
        rec = None
        if recording:
            rec = Recorder(TimeSeriesStore(reg), reg)
            rec.start(record_interval_s)
        done: list[_SynthRequest] = []
        stop = threading.Event()

        def executor():
            while not stop.is_set() or sched.qsize():
                batch = sched.next_batch(max_batch=max_batch,
                                         max_wait=0.05)
                if not batch:
                    continue
                time.sleep(item_service_s * len(batch))
                for item in batch:
                    item.reply(200)
                    done.append(item)

        worker = threading.Thread(target=executor, daemon=True)
        worker.start()
        interval = item_service_s * 1.5
        try:
            for _ in range(n_requests):
                req = _SynthRequest()
                try:
                    sched.submit(req)
                except Exception:
                    req.reply(503)
                time.sleep(interval)
            stop.set()
            sched.wake()
            worker.join(timeout=20)
        finally:
            if rec is not None:
                rec.stop()
        lat = sorted((r.done_at - r.submitted) for r in done
                     if r.done_at is not None and r.status == 200)
        if not lat:
            return float("nan")
        return lat[max(_ceil(0.99 * len(lat)) - 1, 0)]

    offs, ons = [], []
    for _ in range(reps):
        offs.append(one_run(False))
        ons.append(one_run(True))
    p99_off, p99_on = min(offs), min(ons)

    costs = []
    probe = Recorder(TimeSeriesStore(reg), reg)
    for _ in range(50):
        t0 = time.perf_counter()
        probe.tick()
        costs.append(time.perf_counter() - t0)
    costs.sort()
    tick_cost_s = costs[len(costs) // 2]

    interarrival = item_service_s * 1.5
    amortized_s = tick_cost_s * interarrival / record_interval_s
    overhead_pct = amortized_s / p99_off * 100.0
    affected_fraction = 2.0 * interarrival / record_interval_s
    return {
        "n_requests": n_requests,
        "item_service_s": item_service_s,
        "reps": reps,
        "record_interval_s": record_interval_s,
        "registry_gauges": registry_gauges,
        "p99_off_s": p99_off,
        "p99_on_s": p99_on,
        "tick_cost_s": tick_cost_s,
        "amortized_per_request_s": amortized_s,
        "affected_fraction": affected_fraction,
        "overhead_pct": overhead_pct,
        "bound_pct": 1.0,
        "within_bound": (overhead_pct <= 1.0
                         and affected_fraction <= 0.01),
    }


def regression_chaos_scenario(*, service: str = "regression-bench",
                              seed: int = 23, chaos: bool = True,
                              warmup: int = 8, inject_after: int = 12,
                              max_ticks: int = 40,
                              base_step_s: float = 0.010,
                              slow_factor: float = 6.0,
                              sustain_ticks: int = 3) -> dict:
    """Live perf-regression acceptance (ISSUE 16): a seeded synthetic
    training loop exports ``profile_mfu`` each tick; the recorder
    samples it into a private store and the CUSUM sentinel watches.
    With ``chaos=True`` a ``worker.slow`` fault (the resilience
    plane's persistent-degradation path, ``factor=slow_factor``) arms
    after ``inject_after`` ticks — MFU steps down by that factor and
    the sentinel must flip ``obs_regression_active{series=
    profile_mfu}`` within 20 recorder ticks of the step, after which
    ``FleetHealth`` (sentinel attached) reads DEGRADED. With
    ``chaos=False`` the identical replay must alarm exactly never —
    the detector is a pure fold over the value sequence, so the
    healthy trajectory is bit-identical run to run."""
    from ..obs.fleet import FleetAggregator, FleetHealth
    from ..obs.metrics import MetricsRegistry
    from ..obs.regression import RegressionSentinel, SeriesWatch, _pull_mfu
    from ..obs.timeseries import Recorder, TimeSeriesStore
    from ..resilience import FaultRule, faults

    reg = MetricsRegistry()
    store = TimeSeriesStore(reg)
    recorder = Recorder(store, reg)
    sent = RegressionSentinel(store, reg, watches=[
        SeriesWatch("profile_mfu", _pull_mfu, direction="lower_bad",
                    warmup=warmup)], sustain_ticks=sustain_ticks)
    health = FleetHealth(FleetAggregator(reg), registry=reg,
                         service=service, store=store)
    health.attach_sentinel(sent)
    g_mfu = reg.gauge("profile_mfu", "model FLOP utilization, by stage")
    from ..obs.attribution import peak_spec
    peak_flops = peak_spec("cpu").peak_flops   # the 1 Tflop/s cpu row
    flops_per_step = base_step_s * peak_flops * 0.42   # healthy MFU 0.42

    rules = []
    if chaos:
        rules = [FaultRule(point="worker.slow", kind="slow",
                           match="trainer", times=1, after=inject_after,
                           factor=slow_factor)]
    step_at = None
    alarm_tick = None
    degraded_tick = None
    events = 0
    mfu_trace: list = []
    with faults(seed, rules):
        from ..resilience.faults import injector
        for t in range(max_ticks):
            injector.apply("worker.slow", "trainer")
            slow = injector.degradation("trainer")
            if slow > 1.0 and step_at is None:
                step_at = t
            step_s = base_step_s * slow
            mfu = flops_per_step / (peak_flops * step_s)
            mfu_trace.append(round(mfu, 4))
            g_mfu.set(mfu, stage="train")
            recorder.tick()
            active = sent.tick()
            verdict = health.tick()
            if active and alarm_tick is None:
                alarm_tick = t
            if verdict == "degraded" and degraded_tick is None:
                degraded_tick = t
            if alarm_tick is not None and degraded_tick is not None \
                    and t >= alarm_tick + sustain_ticks:
                break
        snap = reg.snapshot()
        events = int(sum(v for k, v in snap.items()
                         if k.startswith("obs_regression_events_total")))
    return {
        "chaos": chaos,
        "seed": seed,
        "mfu_healthy": mfu_trace[0] if mfu_trace else None,
        "mfu_degraded": mfu_trace[-1] if mfu_trace else None,
        "step_at_tick": step_at,
        "alarm_tick": alarm_tick,
        "ticks_to_alarm": (alarm_tick - step_at
                           if alarm_tick is not None and step_at is not None
                           else None),
        "degraded_tick": degraded_tick,
        "events": events,
        "verdict_end": health.verdict(),
        "mfu_trace": mfu_trace,
    }


def llm_serving_scenario(*, service: str = "llm-bench", slots: int = 2,
                         block_len: int = 4, spec_k: int = 0,
                         n_prompts: int = 4, prompt_len: int = 12,
                         max_new_tokens: int = 6, vocab: int = 64,
                         seed: int = 17, registry=None) -> dict:
    """Generation benchmark for the LLM serving engine (ISSUE 17
    acceptance): warm a tiny causal LM's prefill+decode programs, serve
    a repeated-prefix workload through
    :class:`~mmlspark_tpu.serving.llm.LLMEngine`, and read the
    ``gen_*``/``kv_*`` series back from the obs registry.

    Three rounds over the SAME ``n_prompts`` prompts (shared
    ``block_len``-aligned prefix, distinct tails). Rounds 1-2 submit
    one sequence at a time and drain — TTFT is pure prefill, no
    slot-queue wait folded in: round 1 prefills cold, round 2 must hit
    the refcounted prefix cache, and the quantile split by the
    ``reuse`` label separates ``ttft_cold_p50_ms`` from
    ``ttft_warm_p50_ms`` (the measured TTFT improvement the paged
    cache exists to buy — a full-prompt hit prefills a 1-token
    suffix). TTFT quantiles are read BEFORE round 3 — the batched
    throughput round (all prompts at once, continuous batching), whose
    queue waits would otherwise pollute the warm column — which is
    what ``tokens_per_s`` measures. The whole serving run executes
    inside CompileTracker steady state, so a single runtime compile on
    a warmed worker fails the scenario rather than hiding in the
    latency columns.

    Returns tokens/sec, TTFT percentiles (registry
    ``gen_ttft_seconds`` quantiles split by the ``reuse`` label),
    prefix hit rate, spec-acceptance ratio (``spec_k > 0``), AOT
    fingerprint count, and the per-sequence outputs — callers bank the
    numbers and tests assert on either surface.
    """
    import jax.numpy as jnp
    import numpy as np

    from ..dl import MaskedLMModel, TextEncoder
    from ..dl.text_encoder import make_attention_fn
    from ..obs.metrics import registry as _default
    from ..obs.profile import compile_tracker
    from ..serving.llm import LLMEngine, _bucket_window

    import jax

    reg = registry if registry is not None else _default
    enc = TextEncoder(vocab=vocab, width=32, depth=1, heads=2,
                      mlp_dim=64, dtype=jnp.float32,
                      attention_fn=make_attention_fn("dense",
                                                     causal=True))
    module = MaskedLMModel(encoder=enc)
    variables = module.init(jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    rng = np.random.default_rng(seed)
    # shared prefix covering whole blocks (reuse is whole-chunk only),
    # distinct per-prompt tails
    shared = rng.integers(2, vocab, size=prompt_len - block_len)
    prompts = [list(map(int, np.concatenate(
        [shared, rng.integers(2, vocab, size=block_len)])))
        for _ in range(n_prompts)]

    engine = LLMEngine(
        module, variables,
        draft_module=module if spec_k else None,
        draft_variables=variables if spec_k else None,
        slots=slots, block_len=block_len,
        max_seq_len=prompt_len + max_new_tokens + block_len,
        spec_k=spec_k, service=service, registry=reg)
    windows = sorted({_bucket_window(len(p)) for p in prompts}
                     | {_bucket_window(block_len)} | {1})
    fps = engine.warm(prefill_windows=tuple(windows), mark_steady=True)
    try:
        outputs = {}
        # rounds 1-2: one sequence in flight at a time, so the TTFT
        # histogram holds pure submit→prefill→first-token latencies
        for rnd, reuse in ((0, "cold"), (1, "warm")):
            for i, p in enumerate(prompts):
                engine.submit(f"r{rnd}-s{i}", p, max_new_tokens)
                outputs.update(engine.run_until_drained())
        h = reg.metrics("gen_ttft_seconds")[0]
        ttft_ms = {
            "ttft_cold_p50_ms": h.quantile(0.5, service=service,
                                           reuse="cold") * 1e3,
            "ttft_warm_p50_ms": h.quantile(0.5, service=service,
                                           reuse="warm") * 1e3,
            "ttft_p99_ms": max(h.quantile(0.99, service=service,
                                          reuse=r) for r in
                               ("cold", "warm")) * 1e3,
        }
        # round 3: everything at once — continuous batching throughput
        t0 = time.monotonic()
        for i, p in enumerate(prompts):
            engine.submit(f"rt-s{i}", p, max_new_tokens)
        batch_out = engine.run_until_drained()
        wall_s = time.monotonic() - t0
        outputs.update(batch_out)
        compile_tracker.assert_steady_state()
        steady_ok = True
    finally:
        compile_tracker.unmark_steady()

    kv = engine.kv.stats()
    snap = reg.snapshot()

    def _sum(prefix: str) -> float:
        return sum(v for k, v in snap.items()
                   if k.startswith(prefix)
                   and f'service="{service}"' in k)

    hits = _sum("kv_prefix_hits_total")
    misses = _sum("kv_prefix_misses_total")
    # throughput counts round 3's committed tokens (decode commits plus
    # the prefill-produced first token per sequence) over round 3 wall
    batch_tokens = sum(len(v) for v in batch_out.values()) \
        - sum(len(p) for p in prompts)
    gen_tokens = int(_sum("gen_tokens_total")) \
        + len(outputs)   # + the prefill-produced first tokens
    return {
        "sequences": len(outputs),
        "gen_tokens": gen_tokens,
        "wall_s": wall_s,
        "tokens_per_s": batch_tokens / max(wall_s, 1e-9),
        **ttft_ms,
        "prefix_hits": int(hits),
        "prefix_misses": int(misses),
        "prefix_hit_rate": hits / max(hits + misses, 1),
        "tokens_reused": int(_sum("kv_prefix_tokens_reused_total")),
        "spec_accept_ratio": _sum("gen_spec_accept_ratio")
        if spec_k else None,
        "decode_steps": int(_sum("gen_decode_steps_total")),
        "kv_blocks": kv["blocks"],
        "kv_cached": kv["cached"],
        "aot_fingerprints": len(fps),
        "steady_state_ok": steady_ok,
        "outputs": {k: [int(t) for t in v] for k, v in outputs.items()},
    }


def llm_decode_scenario(*, service: str = "llm-decode-bench",
                        context_tokens: int = 4096,
                        block_len: int = 128,
                        max_new_tokens: int = 32, slots: int = 1,
                        vocab: int = 64, seed: int = 23,
                        registry=None) -> dict:
    """Long-context decode-throughput bench (ISSUE 18 acceptance):
    steady-state tokens/sec of the decode executor at ``context_tokens``
    of resident KV — the regime the paged-attention kernel exists for.

    One sequence fills ``context_tokens - max_new_tokens`` prompt
    tokens, then the timed window covers ONLY the drained decode steps
    (the first engine boundary — prefill + first decode step — runs
    before the clock starts, so prefill cost never pollutes the decode
    number). Runs inside CompileTracker steady state: a runtime compile
    mid-decode fails the scenario."""
    import jax.numpy as jnp
    import numpy as np

    from ..dl import MaskedLMModel, TextEncoder
    from ..dl.text_encoder import make_attention_fn
    from ..obs.metrics import registry as _default
    from ..obs.profile import compile_tracker
    from ..serving.llm import LLMEngine

    import jax

    reg = registry if registry is not None else _default
    enc = TextEncoder(vocab=vocab, width=32, depth=1, heads=2,
                      mlp_dim=64, dtype=jnp.float32,
                      attention_fn=make_attention_fn("dense",
                                                     causal=True))
    module = MaskedLMModel(encoder=enc)
    variables = module.init(jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    rng = np.random.default_rng(seed)
    prompt_len = int(context_tokens) - int(max_new_tokens)
    prompt = [int(t) for t in rng.integers(2, vocab, size=prompt_len)]

    engine = LLMEngine(module, variables, slots=slots,
                       block_len=block_len, max_seq_len=context_tokens,
                       service=service, registry=reg)
    # the prompt is wider than the kernel's widest window: warm() takes
    # its length and compiles every chunk window it will be fed through
    fps = engine.warm(prefill_windows=(prompt_len, 1), mark_steady=True)
    try:
        engine.submit("ctx0", prompt, max_new_tokens)
        engine.step()            # admit + prefill + first decode step
        snap0 = reg.snapshot()

        def _sum(snapshot, prefix):
            return sum(v for k, v in snapshot.items()
                       if k.startswith(prefix)
                       and f'service="{service}"' in k)

        tok0 = _sum(snap0, "gen_tokens_total")
        t0 = time.monotonic()
        outputs = engine.run_until_drained()
        decode_wall_s = time.monotonic() - t0
        compile_tracker.assert_steady_state()
        steady_ok = True
    finally:
        compile_tracker.unmark_steady()

    snap = reg.snapshot()
    decode_tokens = _sum(snap, "gen_tokens_total") - tok0
    attn_decode_s = sum(
        v for k, v in snap.items()
        if k.startswith("gen_decode_attn_seconds_sum")
        and f'service="{service}"' in k and 'phase="decode"' in k)
    steps = _sum(snap, "gen_decode_steps_total")
    return {
        "context_tokens": int(context_tokens),
        "context_blocks": -(-int(context_tokens) // int(block_len)),
        "decode_tokens": int(decode_tokens),
        "decode_wall_s": decode_wall_s,
        "tokens_per_s": decode_tokens / max(decode_wall_s, 1e-9),
        "attn_ms_per_step": (attn_decode_s / max(steps, 1)) * 1e3,
        "decode_steps": int(steps),
        "aot_fingerprints": len(fps),
        "steady_state_ok": steady_ok,
        "outputs": {k: [int(t) for t in v] for k, v in outputs.items()},
    }


# ------------------------------------------------- zero-downtime deploy
def rollout_scenario(*, service: str = "rollout-bench", seed: int = 29,
                     period_s: float = 2.0, periods: int = 2,
                     max_queue: int = 128, max_batch: int = 8,
                     worker_max: int = 4,
                     canary_share: float = 0.25,
                     stage_at: float = 0.25, flip_at: float = 0.40,
                     canary_at: float = 0.55,
                     bad_batches: int = 1,
                     gold_slo_s: float = 0.6, silver_slo_s: float = 1.2,
                     burn_windows: dict | None = None,
                     tick_s: float = 0.05,
                     max_rollback_ticks: int = 80,
                     registry=None) -> dict:
    """Zero-downtime model-lifecycle acceptance (ISSUE 19).

    The mixed-tenant fleet from :func:`mixed_tenant_scenario` — diurnal
    gold/silver/best-effort load into one tenancy-enabled scheduler,
    drained by an autoscaled synthetic worker pool with the mesh's
    lease-replay semantics — while a model update rolls through the
    deploy plane's full lifecycle:

    1. **Blue/green flip under load.** ``v1`` serves; ``v2`` is
       registered, warmed and staged beside it, then promoted by ONE
       :meth:`~mmlspark_tpu.serving.VersionRouter.flip` mid-load while
       a seeded ``worker.death`` kills a worker holding a lease.
       Contract: zero non-canary 5xx, zero dropped admitted requests
       (kill included — the replay path completes them), every request
       answered **byte-identically by the version that admitted it**
       (pre-flip admissions complete on draining ``v1``), and
       ``deploy_draining_inflight`` reaches 0.
    2. **Seeded-bad canary auto-rollback.** ``v3`` is staged with a
       canary slice; a seeded ``model.bad`` rule makes it answer
       injected 500s. Those 500s land on the CANARY tenant's error
       budget (the router re-tenants the slice), the
       :class:`~mmlspark_tpu.obs.fleet.BurnRateMonitor` sees the burn,
       and the :class:`~mmlspark_tpu.serving.RolloutController` rolls
       back from burn rate alone — within a bounded number of ticks,
       with zero gold-tier sheds or 5xx (the blast radius IS the
       slice).

    Runs inside CompileTracker steady state end to end: the deploy
    plane itself (register/warm/stage/flip/rollback) must never
    trigger a runtime compile.

    Reproducible by seed: arrivals are precomputed pure functions of
    the tenant specs; the ``worker.death`` rule fires at a fixed
    matching-probe count and the ``model.bad`` rule is bounded to
    ``bad_batches`` firings (probes 1..N always fire) — so two runs
    realize the identical sorted ``schedule`` even though thread
    interleaving decides WHICH admissions land in the canary slice.
    """
    import queue as _queue

    from ..obs.fleet import BurnRateMonitor
    from ..obs.metrics import registry as _default
    from ..obs.profile import compile_tracker
    from ..resilience import FaultRule, WorkerKilled, faults
    from ..resilience.faults import injector as _inj
    from ..sched import RequestScheduler, Shed, Tenancy, TenantQuota
    from ..serving.autoscale import Autoscaler, AutoscaleConfig
    from ..serving.deploy import (ModelRegistry, RolloutConfig,
                                  RolloutController, VersionRouter)

    reg = registry if registry is not None else _default
    duration_s = period_s * periods
    tenancy = Tenancy(
        service,
        quotas={
            "cognitive": TenantQuota(tier="gold"),
            "lightgbm": TenantQuota(tier="silver"),
            "generate": TenantQuota(tier="best_effort", rate=30.0,
                                    burst=10.0, queue_share=0.25),
            # the canary slice's OWN budget bucket: injected 5xx burn
            # here, never on the gold tier the request arrived under
            "canary": TenantQuota(tier="silver"),
        },
        tier_deadlines={"gold": gold_slo_s, "silver": silver_slo_s},
        registry=reg)
    sched = RequestScheduler(
        service, max_queue=max_queue, tenancy=tenancy, registry=reg,
        on_shed=lambda item, reason, retry_after: item.reply(429))
    sched.estimator.observe(1, 0.004)
    m_t5 = reg.counter(
        "serving_tenant_requests_total",
        "requests answered, by service/tenant/status code")

    # -- the deploy plane ----------------------------------------------
    def _make_model(name: str):
        def fn(payload: bytes) -> bytes:
            return name.encode() + b":" + payload
        return fn

    mreg = ModelRegistry(service=service, registry=reg)
    router = VersionRouter(mreg, service=service, canary_tenant="canary",
                           metrics=reg)
    mreg.register("v1", transform=_make_model("v1"))
    router.set_active("v1")

    monitor = BurnRateMonitor(
        registry=reg, service=service,
        windows=dict(burn_windows) if burn_windows
        else {"fast": 0.5, "slow": 1.5},
        budget_for=tenancy.error_budget_for)
    ctl = RolloutController(
        router, burn=monitor, metrics=reg,
        config=RolloutConfig(interval=tick_s, burn_threshold=2.0,
                             slow_threshold=1.0, rollback_windows=2,
                             promote_windows=10 ** 6, cooldown=1.0,
                             flap_s=1.0))

    class _DeployRequest(_SynthRequest):
        """Carries the admission-stamped version and releases its
        router inflight slot on the first terminal reply — the same
        exactly-once contract ``_finish_request`` wires for real
        serving (the scheduler owns ``on_done`` for admission
        accounting, so the release can't ride there)."""

        __slots__ = ("version", "assigned_tenant", "payload", "result")

        def __init__(self):
            super().__init__()
            self.version = ""
            self.assigned_tenant = ""
            self.payload = b""
            self.result = None

        def reply(self, status):
            first = super().reply(status)
            if first and self.version:
                router.release(self.version)
            return first

    class _Worker:
        __slots__ = ("thread", "stop", "draining", "killed", "busy_s",
                     "items", "started", "ended")

        def __init__(self):
            self.thread = None
            self.stop = threading.Event()
            self.draining = False
            self.killed = False
            self.busy_s = 0.0
            self.items = 0
            self.started = time.monotonic()
            self.ended = None

    class _Pool:
        """mixed_tenant_scenario's lease-replay pool, version-aware:
        the executor groups each batch by the version stamped at
        admission (the serving executor's ``_transform_groups``
        contract) and probes ``model.bad`` once per version group."""

        def __init__(self):
            self._lock = threading.Lock()
            self.workers: dict[str, _Worker] = {}
            self.leases: dict[str, list] = {}
            self.replays = 0
            self._seq = 0

        def count(self):
            with self._lock:
                return sum(1 for w in self.workers.values()
                           if w.thread.is_alive() and not w.draining
                           and not w.killed)

        def scale_up(self):
            with self._lock:
                wid = f"w{self._seq}"
                self._seq += 1
                w = _Worker()
                w.thread = threading.Thread(
                    target=self._run, args=(wid, w), daemon=True)
                self.workers[wid] = w
                w.thread.start()
            return wid

        def scale_down(self):
            with self._lock:
                live = [(w.started, wid) for wid, w in
                        self.workers.items()
                        if w.thread.is_alive() and not w.draining
                        and not w.killed]
                if not live:
                    return None
                _, wid = max(live)
                self.workers[wid].draining = True
                self.workers[wid].stop.set()
            return wid

        def _run(self, wid, w):
            try:
                while not w.stop.is_set():
                    batch = sched.next_batch(max_batch=max_batch,
                                             max_wait=0.05)
                    if not batch:
                        continue
                    with self._lock:
                        self.leases[wid] = batch
                    _inj.apply("worker.death", key=wid)
                    _inj.apply("worker.slow", key=wid)
                    cost = sum(i.cost for i in batch) \
                        * _inj.degradation(wid)
                    time.sleep(cost)
                    w.busy_s += cost
                    w.items += len(batch)
                    sched.estimator.observe(len(batch), cost)
                    groups: dict[str, list] = {}
                    for item in batch:
                        groups.setdefault(item.version, []).append(item)
                    for ver, members in groups.items():
                        act = _inj.apply("model.bad", key=ver) \
                            if ver else None
                        if act is not None and act.kind == "error":
                            for item in members:
                                # mirror _finish_request's per-tenant
                                # status counting: the burn monitor
                                # reads 5xx from this family
                                m_t5.inc(1, service=service,
                                         tenant=item.assigned_tenant,
                                         code=str(act.status))
                                item.reply(act.status)
                            continue
                        fn = router.transform_for(ver)
                        for item in members:
                            out = fn(item.payload) if fn is not None \
                                else bytes(item.payload)
                            if act is not None and act.kind == "corrupt":
                                out = bytes(b ^ 0xFF for b in out)
                            item.result = out
                            tenancy.observe_latency(
                                item.assigned_tenant,
                                time.monotonic() - item.submitted)
                            item.reply(200)
                    with self._lock:
                        self.leases.pop(wid, None)
            except WorkerKilled:
                w.killed = True
            finally:
                w.ended = time.monotonic()

        def monitor(self, stop_ev):
            while not stop_ev.wait(0.05):
                dead = []
                with self._lock:
                    for wid, w in self.workers.items():
                        if wid in self.leases and (
                                w.killed or not w.thread.is_alive()):
                            dead.append((wid, self.leases.pop(wid)))
                for wid, batch in dead:
                    for item in batch:
                        if item._event.is_set():
                            continue
                        self.replays += 1
                        try:
                            sched.put_front(item)
                        except _queue.Full:
                            item.reply(503)

        def stop(self):
            with self._lock:
                ws = list(self.workers.values())
            for w in ws:
                w.stop.set()
            sched.wake()
            for w in ws:
                w.thread.join(timeout=5)
                if w.ended is None:
                    w.ended = time.monotonic()

    pool = _Pool()
    auto = Autoscaler(
        service, pool,
        AutoscaleConfig(min_workers=2, max_workers=worker_max,
                        interval=0.1, queue_high=6.0, queue_low=1.5,
                        slo_high=0.8, slo_low=0.4, up_stable=2,
                        down_stable=5, cooldown=0.6),
        registry=reg, tenancy=tenancy,
        item_seconds=sched.estimator.item_seconds)

    rules = [
        # the flip-under-chaos worker: killed mid-lease once it is
        # deep into the run (~the flip window, at this fleet's batch
        # rate) — the replayed batch must still complete on whatever
        # version each request was ADMITTED under
        FaultRule(point="worker.death", kind="kill", match="w1",
                  after=60, times=1),
        # a persistently sick first worker: builds the queue pressure
        # that makes the autoscaler spawn w1 (same dynamics as
        # mixed_tenant_scenario, which this fleet is)
        FaultRule(point="worker.slow", kind="slow", match="w0",
                  after=3, times=1, factor=3.0),
        # the bad canary: v3 answers injected 500s. Bounded to
        # bad_batches firings so the realized schedule is identical
        # across same-seed runs (probes 1..N always fire; batching
        # jitter only moves WHEN probe N happens, never whether —
        # and one bad batch keeps the fast burn window hot long
        # enough for the rollback streak, so the default is 1)
        FaultRule(point="model.bad", kind="error", match="v3",
                  status=500, times=bad_batches),
    ]

    class _TenantResult:
        __slots__ = ("requests", "intake_sheds")

        def __init__(self):
            self.requests = []
            self.intake_sheds = {}   # {(assigned_tenant, reason): n}

    results = {name: _TenantResult() for name in MIXED_TENANTS}
    arrivals = {name: _arrival_schedule(spec, period_s, duration_s)
                for name, spec in MIXED_TENANTS.items()}
    samples: list[tuple[float, int]] = []
    deploy_log: list[tuple] = []
    staged_v3 = threading.Event()
    stop_all = threading.Event()
    t0 = time.monotonic()

    def load(name, spec, res):
        for i, t_rel in enumerate(arrivals[name]):
            wait = (t0 + t_rel) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            req = _DeployRequest()
            req.cost = spec["cost_s"]
            req.payload = f"{name}/{i}".encode()
            # admission-time routing: the version is stamped BEFORE the
            # scheduler sees the request (ServingServer._admit order),
            # and a canary pick re-tenants it onto the canary budget
            ver, override = router.assign(name)
            req.version = ver
            req.assigned_tenant = override or name
            try:
                sched.submit(req, tenant=req.assigned_tenant)
                res.requests.append(req)
            except Shed as s:
                router.release(ver)   # never admitted: undo the slot
                k = (req.assigned_tenant, s.reason)
                res.intake_sheds[k] = res.intake_sheds.get(k, 0) + 1

    def sampler():
        while not stop_all.wait(0.05):
            samples.append((time.monotonic() - t0, pool.count()))

    def driver():
        # phase 1: blue/green — build v2 beside v1, stage, one flip
        _sleep_until(t0 + stage_at * duration_s)
        mreg.register("v2", transform=_make_model("v2"))
        try:
            mreg.warm("v2")      # AOT warm standby (no-op for synth fns)
        except Exception:
            pass
        router.stage("v2")
        deploy_log.append(("stage", "v2",
                           round(time.monotonic() - t0, 3)))
        _sleep_until(t0 + flip_at * duration_s)
        router.flip()
        deploy_log.append(("flip", "v2",
                           round(time.monotonic() - t0, 3)))
        # phase 2: canary v3 — the seeded model.bad rule makes it burn
        _sleep_until(t0 + canary_at * duration_s)
        mreg.register("v3", transform=_make_model("v3"))
        router.stage("v3", canary_share=canary_share)
        deploy_log.append(("stage", "v3",
                           round(time.monotonic() - t0, 3)))
        staged_v3.set()

    def _sleep_until(t):
        d = t - time.monotonic()
        if d > 0:
            time.sleep(d)

    compile_tracker.mark_steady()
    try:
        with faults(seed, rules, inj=_inj) as inj:
            auto.start()
            mon = threading.Thread(target=pool.monitor,
                                   args=(stop_all,), daemon=True)
            mon.start()
            smp = threading.Thread(target=sampler, daemon=True)
            smp.start()
            drv = threading.Thread(target=driver, daemon=True)
            drv.start()
            loaders = [threading.Thread(target=load,
                                        args=(n, s, results[n]),
                                        daemon=True)
                       for n, s in MIXED_TENANTS.items()]
            for th in loaders:
                th.start()

            # the control loop: tick until the bad canary is rolled
            # back (bounded) and the offered load has ended
            rollback_ticks = None
            ticks_after_stage = 0
            while True:
                time.sleep(tick_s)
                r = ctl.tick()
                if staged_v3.is_set() and rollback_ticks is None:
                    ticks_after_stage += 1
                    if r == "rollback":
                        rollback_ticks = ticks_after_stage
                    elif ticks_after_stage > max_rollback_ticks:
                        break    # bounded: give up, report not rolled
                if not any(th.is_alive() for th in loaders) and (
                        rollback_ticks is not None
                        or not staged_v3.is_set()
                        or ticks_after_stage > max_rollback_ticks):
                    break
            for th in loaders:
                th.join(timeout=duration_s + 30)
            drv.join(timeout=duration_s + 30)
            # drain: every admitted request reaches a terminal state
            # and every flipped-away version empties
            drain_end = time.monotonic() + 10.0
            while time.monotonic() < drain_end:
                if sched.qsize() == 0 and not pool.leases \
                        and router.draining_inflight() == 0:
                    break
                time.sleep(0.05)
            draining_final = router.draining_inflight()
            schedule = inj.schedule()
            stop_all.set()
            auto.stop()
            pool.stop()
            mon.join(timeout=5)
            smp.join(timeout=5)
        runtime_compiles = compile_tracker.runtime_compiles()
    finally:
        compile_tracker.unmark_steady()

    # -- per-ASSIGNED-tenant outcomes ----------------------------------
    per_tenant: dict = {}
    mismatches = 0
    total_unanswered = 0
    for name, res in results.items():
        for req in res.requests:
            bucket = per_tenant.setdefault(
                req.assigned_tenant,
                {"answered_200": 0, "status_5xx": 0, "expired": 0,
                 "unanswered": 0, "sheds": {}, "lat": []})
            if req.status == 200:
                bucket["answered_200"] += 1
                if req.done_at is not None:
                    bucket["lat"].append(req.done_at - req.submitted)
                expected = req.version.encode() + b":" + req.payload
                if req.result != expected:
                    mismatches += 1
            elif req.status is not None and req.status >= 500:
                bucket["status_5xx"] += 1
            elif req.status == 429:
                bucket["expired"] += 1
            elif req.status is None:
                bucket["unanswered"] += 1
                total_unanswered += 1
        for (assigned, reason), n in res.intake_sheds.items():
            bucket = per_tenant.setdefault(
                assigned,
                {"answered_200": 0, "status_5xx": 0, "expired": 0,
                 "unanswered": 0, "sheds": {}, "lat": []})
            bucket["sheds"][reason] = bucket["sheds"].get(reason, 0) + n
    for name, b in per_tenant.items():
        lat = sorted(b.pop("lat"))
        b["p50_s"] = _pctl(lat, 0.50)
        b["p99_s"] = _pctl(lat, 0.99)
        b["shed_total"] = sum(b["sheds"].values()) + b["expired"]

    gold = per_tenant.get("cognitive", {})
    canary = per_tenant.get("canary", {})
    non_canary_5xx = sum(b["status_5xx"] for t, b in per_tenant.items()
                         if t != "canary")
    gold_sheds = gold.get("shed_total", 0)
    rollbacks = [e for e in ctl.events if e["kind"] == "rollback"]
    peak = max((c for _, c in samples), default=0)
    return {
        "seed": seed,
        "service": service,
        "duration_s": duration_s,
        "per_tenant": per_tenant,
        "deploy_log": deploy_log,
        # phase 1 contract: the flip is invisible to clients
        "non_canary_5xx": non_canary_5xx,
        "rollout_zero_5xx": bool(non_canary_5xx == 0),
        "unanswered": total_unanswered,
        "drained_completed": bool(total_unanswered == 0),
        "version_mismatches": mismatches,
        "byte_identical": bool(mismatches == 0),
        "draining_inflight_final": draining_final,
        "drained_to_zero": bool(draining_final == 0),
        "runtime_compiles": int(runtime_compiles),
        "zero_runtime_compiles": bool(runtime_compiles == 0),
        "worker_killed": any(p == "worker.death"
                             for p, *_ in schedule),
        "lease_replays": pool.replays,
        # phase 2 contract: burn-rate rollback, bounded, sliced blast
        "rollback_ticks": rollback_ticks,
        "rolled_back": bool(rollback_ticks is not None),
        "rollback_reason": rollbacks[-1]["reason"] if rollbacks
        else None,
        "active_after": router.active,
        "candidate_after": router.candidate,
        "canary_5xx": canary.get("status_5xx", 0),
        "canary_gold_sheds": gold_sheds,
        "gold_5xx": gold.get("status_5xx", 0),
        "gold_unharmed": bool(gold_sheds == 0
                              and gold.get("status_5xx", 0) == 0),
        "workers_peak": peak,
        "autoscaled": bool(peak >= 2),
        "schedule": sorted(schedule),
    }


# ---------------------------------------------- cost attribution plane
def synth_attribution_rows(n_rows: int = 1200, *, seed: int = 29,
                           service: str = "attr-bench") -> list[dict]:
    """Schema-v6 FeatureLog-shaped rows where part of the cost rides
    the ANALYTIC columns: each row's ``analytic_flops``/``analytic_
    bytes`` vary with the program variant that served it (seeded,
    independent of the other features), and ``execute_ms`` includes a
    per-Tflop term — the signal only a v6-aware model can price.
    Deterministic: two calls with one seed produce identical rows."""
    import numpy as np

    from ..obs.profile import FEATURE_SCHEMA_VERSION
    from ..sched.policy import bucket_of

    rng = np.random.default_rng(seed)
    routes = {"/feat": (0.8, 0.05), "/gen": (5.0, 0.40)}
    names = sorted(routes)
    ms_per_tflop = 2.5
    rows = []
    for _ in range(n_rows):
        route = names[int(rng.integers(0, len(names)))]
        base, per_row = routes[route]
        batch = int(rng.integers(1, 65))
        bucket = bucket_of(batch)
        depth = float(max(rng.normal(8.0, 4.0), 0.0))
        tflops = float(rng.uniform(0.2, 6.0))
        gbytes = tflops * float(rng.uniform(0.05, 0.15))
        ms = (base + per_row * bucket + ms_per_tflop * tflops
              + float(rng.normal(0.0, 0.15)))
        rows.append({
            "service": service, "route": route, "batch": batch,
            "bucket": bucket, "padded_batch": bucket,
            "entity_bytes": 1024.0, "queue_depth": depth,
            "execute_ms": max(ms, 0.05),
            "analytic_flops": tflops * 1e12,
            "analytic_bytes": gbytes * 1e9,
            "schema_version": FEATURE_SCHEMA_VERSION,
            "platform": "synthetic",
        })
    return rows


def attribution_scenario(*, seed: int = 29, n_rows: int = 1200,
                         holdout: float = 0.25, ticks: int = 12,
                         registry=None) -> dict:
    """Cost-attribution acceptance (ISSUE 20), three banked pieces:

    1. **Roofline placement** — two real programs compiled on the
       analytic path (a 256x256 matmul and a wide elementwise add),
       cost-analyzed and placed against the CPU :class:`PeakSpec`: the
       matmul must read compute-bound, the add memory-bound, and every
       utilization share <= 1.0 by construction.
    2. **Goodput under seeded chaos** — a private registry is driven
       through a deterministic tick schedule (useful step seconds
       every tick; seeded waste bursts: spec rejects, eager fallbacks,
       sheds, expirations, a runtime compile, a straggler window) and
       a :class:`~..obs.goodput.GoodputLedger` prices it. Banked: the
       final ratio, the itemized waste taxonomy, and the per-tick
       ratio trace (bit-identical per seed).
    3. **v6 model value** — the ridge cost model trained on rows whose
       cost partly rides the analytic columns must beat (or match) the
       SAME model trained with those columns stripped (the v5
       baseline) on held-out MAE.
    """
    import numpy as np

    from ..obs.attribution import CostAttribution, peak_spec
    from ..obs.goodput import GoodputLedger, WASTE_CAUSES
    from ..obs.metrics import MetricsRegistry
    from ..perf.costmodel import CostModel

    # -- 1: roofline placement off real compiled programs ---------------
    reg = registry if registry is not None else MetricsRegistry()
    attr = CostAttribution(registry=reg)
    rooflines: dict[str, dict] = {}
    import jax
    import jax.numpy as jnp

    a = jnp.ones((256, 256), jnp.float32)
    big = jnp.ones((4, 1 << 20), jnp.float32)
    programs = {
        "attr_matmul_256": jax.jit(lambda m: m @ m).lower(a).compile(),
        "attr_add_wide": jax.jit(lambda v: v + 1.0).lower(big).compile(),
    }
    for name, compiled in programs.items():
        info = attr.record_compiled(name, compiled,
                                    service="attr-bench",
                                    platform="cpu")
        if info is not None:
            rooflines[name] = {
                "bound": info["bound"],
                "flops": info["flops"],
                "bytes": info["bytes"],
                "utilization_compute": round(
                    info["compute_seconds"]
                    / max(info["roofline_seconds"], 1e-18), 6),
                "utilization_memory": round(
                    info["memory_seconds"]
                    / max(info["roofline_seconds"], 1e-18), 6),
            }

    # -- 2: goodput ledger under a seeded chaos schedule -----------------
    rng = np.random.default_rng(seed)
    greg = MetricsRegistry()
    ledger = GoodputLedger(registry=greg)
    h_step = greg.histogram("profile_step_seconds", "synthetic steps")
    h_decode = greg.histogram("gen_decode_attn_seconds", "synthetic")
    h_compile = greg.histogram("profile_compile_seconds", "synthetic")
    c_tokens = greg.counter("gen_tokens_total", "synthetic")
    c_spec = greg.counter("gen_spec_rejected_total", "synthetic")
    c_fallback = greg.counter("pipeline_fused_fallback_total",
                              "synthetic")
    c_shed = greg.counter("sched_shed_total", "synthetic")
    c_expired = greg.counter("sched_continuous_expired_total",
                             "synthetic")
    c_compiles = greg.counter("profile_runtime_compiles_total",
                              "synthetic")
    g_straggler = greg.gauge("fleet_straggler_score", "synthetic")
    ledger.tick()  # baseline
    ratio_trace = []
    for t in range(ticks):
        h_step.observe(0.010, stage="train")
        for _ in range(8):
            h_decode.observe(0.002, service="attr-bench")
            c_tokens.inc(1, service="attr-bench")
        if rng.random() < 0.5:
            c_spec.inc(int(rng.integers(1, 6)), service="attr-bench")
        if rng.random() < 0.3:
            c_fallback.inc(1, segment="seg0")
        if rng.random() < 0.3:
            c_shed.inc(int(rng.integers(1, 4)), reason="backpressure")
        if rng.random() < 0.2:
            c_expired.inc(1, service="attr-bench")
        if t == ticks // 2:
            c_compiles.inc(1, fn="late_fn")
            h_compile.observe(0.5, fn="late_fn")
        g_straggler.set(3.0 if t >= ticks - 3 else 0.0, worker="w1")
        payload = ledger.tick()
        ratio_trace.append(round(payload["goodput_ratio"], 6))
    waste = {c: round(payload["waste_seconds"][c], 6)
             for c in WASTE_CAUSES}

    # -- 3: v6 analytic columns vs the v5 baseline on held-out MAE -------
    service = "attr-bench"
    rows = synth_attribution_rows(n_rows, seed=seed, service=service)
    n_train = int(len(rows) * (1.0 - holdout))
    train, held = rows[:n_train], rows[n_train:]
    stripped = [{k: v for k, v in r.items()
                 if k not in ("analytic_flops", "analytic_bytes")}
                for r in train]
    m_v6 = CostModel(min_rows=32, registry=MetricsRegistry())
    m_v6.fit(train)
    m_v5 = CostModel(min_rows=32, registry=MetricsRegistry())
    m_v5.fit(stripped)
    v6_abs, v5_abs = [], []
    for r in held:
        actual = r["execute_ms"]
        for model, acc in ((m_v6, v6_abs), (m_v5, v5_abs)):
            pred = model.predict_batch_ms(
                service, r["batch"], route=r["route"],
                entity_bytes=r["entity_bytes"],
                queue_depth=r["queue_depth"], count=False)
            if pred is not None:
                acc.append(abs(pred - actual))
    v6_mae = sum(v6_abs) / len(v6_abs) if v6_abs else float("nan")
    v5_mae = sum(v5_abs) / len(v5_abs) if v5_abs else float("nan")

    return {
        "seed": seed,
        "platform_spec": {
            "platform": peak_spec("cpu").platform,
            "peak_flops": peak_spec("cpu").peak_flops,
            "hbm_bytes_per_s": peak_spec("cpu").hbm_bytes_per_s,
        },
        "rooflines": rooflines,
        "matmul_compute_bound": bool(
            rooflines.get("attr_matmul_256", {}).get("bound")
            == "compute"),
        "add_memory_bound": bool(
            rooflines.get("attr_add_wide", {}).get("bound")
            == "memory"),
        "utilization_max": max(
            [u for r in rooflines.values()
             for u in (r["utilization_compute"],
                       r["utilization_memory"])], default=0.0),
        "goodput_ratio": ratio_trace[-1] if ratio_trace else None,
        "goodput_ratio_trace": ratio_trace,
        "goodput_waste_seconds": waste,
        "goodput_waste_itemized": bool(
            sum(1 for v in waste.values() if v > 0) >= 4),
        "v6_mae_ms": v6_mae,
        "v5_mae_ms": v5_mae,
        "v6_no_worse": bool(v6_mae <= v5_mae * 1.001),
    }
