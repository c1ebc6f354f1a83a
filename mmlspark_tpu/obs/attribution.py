"""Device cost-attribution plane: per-program analytic rooflines.

The real-silicon campaign needs to know, per compiled program, whether
it is compute- or memory-bound and how far measured MFU sits from the
analytic ceiling. This module owns both halves of that comparison:

- :class:`PeakSpec` — the per-platform peak table (flops + HBM
  bandwidth) that replaces the hardcoded ``DEFAULT_PEAK_FLOPS``
  constant everywhere a peak is divided by (StepProfiler MFU, bench
  MFU columns, the regression sentinel's synthetic steps). Resolution
  order: explicit argument > ``MMLSPARK_TPU_PEAK_FLOPS`` /
  ``MMLSPARK_TPU_PEAK_BYTES_PER_S`` env overrides > the detected TPU
  generation (``device_kind``). A device with no row raises.

- :class:`CostAttribution` — records each compiled program's analytic
  cost (XLA ``cost_analysis()`` flops / bytes accessed, normalized by
  ``parallel.compat.cost_analysis``) and exports the roofline gauges:

  - ``profile_analytic_flops{program}`` — flops per execution,
  - ``profile_analytic_bytes{program}`` — HBM bytes per execution,
  - ``profile_roofline_utilization{program,bound=compute|memory}`` —
    each resource's share of the roofline-critical time
    (``max(flops/peak_flops, bytes/peak_bw)``). The dominant resource
    reads 1.0 and names the program's placement; the other reads its
    arithmetic-intensity headroom. Both are always <= 1.0 by
    construction, so a matmul-bound program pins
    ``{bound="compute"} == 1.0`` on every platform.

Feeding happens at AOT build/warm time (``core/aot.py`` persists the
pair into each entry's ``meta.json`` and re-exports on warm load
without re-running analysis) and at LLM warm time (``serving/llm.py``).
The recorded pair also rides FeatureLog schema v6 rows
(``analytic_flops`` / ``analytic_bytes``) that the ridge cost model
trains on.

Import is stdlib-only and side-effect-free beyond registering the
gauges; jax is only touched behind the same no-init guards
``profile.device_platform`` uses.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
from dataclasses import dataclass, replace

from .metrics import registry as _registry

_LOG = logging.getLogger(__name__)

#: env overrides — an operator pinning the peak for an unlisted part
#: (or a derated clock) wins over the table, whatever the platform.
ENV_PEAK_FLOPS = "MMLSPARK_TPU_PEAK_FLOPS"
ENV_PEAK_BYTES = "MMLSPARK_TPU_PEAK_BYTES_PER_S"


@dataclass(frozen=True)
class PeakSpec:
    """One platform's analytic ceilings: peak FLOP/s and HBM B/s."""

    platform: str
    peak_flops: float
    hbm_bytes_per_s: float

    def roofline_seconds(self, flops: float, bytes_: float) -> float:
        """Analytic lower bound on execution time: the slower of the
        compute and memory pipes (the classic roofline)."""
        return max(float(flops) / self.peak_flops,
                   float(bytes_) / self.hbm_bytes_per_s)


#: Per-platform peaks. TPU rows are bf16 per-chip peaks with the
#: published HBM bandwidths (Google Cloud documentation); the ``cpu``
#: row is the host-only scenarios' 1 Tflop/s reference point with a
#: DDR-class bandwidth — it prices CPU test runs only and never stands
#: in for a device that has no row.
PEAK_SPECS: dict[str, PeakSpec] = {
    "tpu-v5e": PeakSpec("tpu-v5e", 197e12, 819e9),
    "tpu-v4": PeakSpec("tpu-v4", 275e12, 1228e9),
    "cpu": PeakSpec("cpu", 1.0e12, 100e9),
}

def _device_kind() -> str | None:
    """The live first device's ``device_kind``, with the same
    never-initialize guard as ``profile.device_platform``: only ask a
    backend that already exists."""
    mod = sys.modules.get("jax")
    if mod is None:
        return None
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not getattr(xb, "_backends", None):
        return None
    return str(mod.devices()[0].device_kind)


def _tpu_generation(kind: str | None) -> str | None:
    """``device_kind`` → the table's generation key, or None."""
    kind = (kind or "").lower()
    if "v5 lite" in kind or "v5e" in kind or "v5litepod" in kind:
        return "tpu-v5e"
    if "v4" in kind:
        return "tpu-v4"
    return None


def peak_spec(platform: str | None = None) -> PeakSpec:
    """Resolve the :class:`PeakSpec` for ``platform`` (default: the
    live ``device_platform()``): a table key as-is, a bare ``"tpu"``
    through the live ``device_kind``. A device that is not in the table
    is an error, not a default — a roofline or an MFU priced against
    another part's peaks is a wrong number under the right name."""
    from .profile import device_platform
    key = (platform or device_platform() or "").strip().lower()
    spec = PEAK_SPECS.get(key)
    if spec is None and key.startswith("tpu"):
        kind = _device_kind()
        gen = _tpu_generation(kind)
        if gen is None:
            raise LookupError(
                f"no PeakSpec row for TPU device_kind {kind!r}; add its "
                "published peaks to obs.attribution.PEAK_SPECS")
        spec = PEAK_SPECS[gen]
    if spec is None:
        raise LookupError(
            f"no PeakSpec row for platform {key!r} (known: "
            f"{sorted(PEAK_SPECS)})")
    flops_env = os.environ.get(ENV_PEAK_FLOPS)
    bytes_env = os.environ.get(ENV_PEAK_BYTES)
    try:
        if flops_env:
            spec = replace(spec, peak_flops=float(flops_env))
        if bytes_env:
            spec = replace(spec, hbm_bytes_per_s=float(bytes_env))
    except (TypeError, ValueError):
        pass  # a junk override must not take the metrics plane down
    return spec


_m_peak_missing = _registry.counter(
    "profile_peak_spec_missing_total",
    "roofline/MFU exports skipped because the device has no PeakSpec "
    "row, by platform")
_warned_missing: set = set()


def telemetry_peak_spec(platform: str | None = None) -> PeakSpec | None:
    """:func:`peak_spec` for the telemetry sinks on serving and build
    paths (``CostAttribution.record_program``, ``StepProfiler.step``):
    a device without a row is counted, logged once, and answered with
    None — the sink then skips what it would have priced. A missing row
    never prices a number against another part's peaks, and never stops
    a warm-up, an AOT build or a served request either."""
    try:
        return peak_spec(platform)
    except LookupError as e:
        from .profile import device_platform
        key = platform or device_platform() or "unknown"
        _m_peak_missing.inc(1, platform=key)
        if key not in _warned_missing:
            _warned_missing.add(key)
            _LOG.warning("%s — roofline and MFU gauges are skipped", e)
        return None


class CostAttribution:
    """The per-program analytic-cost table + its gauge exports."""

    def __init__(self, registry=None):
        reg = registry if registry is not None else _registry
        self._lock = threading.Lock()
        self._costs: dict[str, dict] = {}
        self._g_flops = reg.gauge(
            "profile_analytic_flops",
            "XLA cost_analysis flops per execution, by compiled program")
        self._g_bytes = reg.gauge(
            "profile_analytic_bytes",
            "XLA cost_analysis HBM bytes accessed per execution, by "
            "compiled program")
        self._g_roofline = reg.gauge(
            "profile_roofline_utilization",
            "each resource's share of the roofline-critical time per "
            "program (the bound that reads 1.0 is the program's "
            "placement; the other is its headroom)")

    def record_program(self, program: str, flops: float, bytes_: float,
                       *, service: str = "",
                       platform: str | None = None) -> dict:
        """Record one compiled program's analytic cost and export its
        roofline placement against the resolved :class:`PeakSpec`.
        Returns the stored info dict (also what ``meta.json`` and the
        bench bank). On a device without a PeakSpec row the flops and
        bytes are still recorded and exported; the placement fields
        are None and no roofline gauge is set."""
        spec = telemetry_peak_spec(platform)
        flops = max(float(flops), 0.0)
        bytes_ = max(float(bytes_), 0.0)
        self._g_flops.set(flops, program=program)
        self._g_bytes.set(bytes_, program=program)
        info = {"program": program, "service": service,
                "flops": flops, "bytes": bytes_}
        if spec is None:
            info.update(platform=platform, bound=None,
                        roofline_seconds=None, compute_seconds=None,
                        memory_seconds=None)
        else:
            t_compute = flops / spec.peak_flops
            t_memory = bytes_ / spec.hbm_bytes_per_s
            critical = max(t_compute, t_memory, 1e-18)
            self._g_roofline.set(t_compute / critical, program=program,
                                 bound="compute")
            self._g_roofline.set(t_memory / critical, program=program,
                                 bound="memory")
            info.update(
                platform=spec.platform,
                bound="compute" if t_compute >= t_memory else "memory",
                roofline_seconds=spec.roofline_seconds(flops, bytes_),
                compute_seconds=t_compute, memory_seconds=t_memory)
        with self._lock:
            self._costs[program] = info
        return info

    def record_compiled(self, program: str, compiled, *,
                        service: str = "",
                        platform: str | None = None) -> dict | None:
        """``cost_analysis`` a ``jax.stages.Compiled`` (through the
        compat normalizer — misses are counted, never raised) and
        record it. Returns None when the backend yields nothing."""
        from ..parallel.compat import cost_analysis
        cost = cost_analysis(compiled)
        if cost is None:
            return None
        return self.record_program(program, cost["flops"],
                                   cost["bytes"], service=service,
                                   platform=platform)

    # -- read surface ------------------------------------------------------
    def program_cost(self, program: str) -> dict | None:
        with self._lock:
            info = self._costs.get(program)
        return dict(info) if info is not None else None

    def programs(self) -> dict[str, dict]:
        """Copy of the whole table (bench banking / debug payloads)."""
        with self._lock:
            return {k: dict(v) for k, v in self._costs.items()}

    def service_cost(self, service: str) -> tuple[float, float]:
        """Summed (flops, bytes) across the service's recorded
        programs — the FeatureLog v6 row values a served request
        carries. (0.0, 0.0) until something compiled for the service."""
        flops = bytes_ = 0.0
        with self._lock:
            for info in self._costs.values():
                if info.get("service") == service:
                    flops += info["flops"]
                    bytes_ += info["bytes"]
        return flops, bytes_

    def clear(self) -> None:
        with self._lock:
            self._costs.clear()


#: THE process-wide attribution table (AOT build/warm, LLM warm, and
#: the serving executor's feature rows all share it).
cost_attribution = CostAttribution()
