"""From a profiler trace to numbers: device busy union, idle share, time
by op name, one kernel's share, and the idle gaps by the device program
they precede.

The trace is taken with the host tracer OFF: with it on, the TPU
runtime's own threads write tens of millions of events and a one-second
transform takes fourteen (PERF.md section 6, PR 25). So the trace holds
device planes only; the traced stretch's length comes from the host
clock, and a gap is named by the program that ran after it, not by a
host span.

``load`` turns an ``.xplane.pb`` into plain data (``{plane: {line:
[(name, start_ns, duration_ns), ...]}}``) with nothing but JAX;
``reduce`` is pure Python over that data, so the tests check it on a
hand-made trace and every PR computes the same numbers the same way.
"""

from __future__ import annotations

import glob
import os
import re

# Which planes are devices and which of their lines hold one event per
# executed device operation (seen by hand in a v5e trace, PERF.md §5).
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"      # one event per executed program
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """A device event carries its whole HLO instruction as its name
    (``%fusion.14 = bf16[...] fusion(...)``): keep the instruction's own
    name."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def load(path: str) -> dict:
    """``{plane name: {line name: [(event name, start_ns, dur_ns)]}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((short_name(ev.name), float(ev.start_ns),
                               float(ev.duration_ns)))
    return out


def _busy_intervals(intervals):
    """Merged ``(start, end)`` intervals, in order."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    return sum(e - s for s, e in _busy_intervals(intervals))


def _name_gap(start: float, end: float, modules) -> str:
    """What a gap on the device belongs to: the program it lies inside,
    else the next program to start."""
    for name, s, d in modules:
        if s <= start and s + d >= end:
            return f"inside {name}"
    later = [(s, name) for name, s, d in modules if s >= end - 1.0]
    return f"before {min(later)[1]}" if later else "after the last program"


def reduce(trace: dict, *, window_s: float,
           kernel_pattern: str | None = None, device_plane=DEVICE_PLANE,
           op_lines=OP_LINES, module_line=MODULE_LINE) -> dict | None:
    """Reduce one trace. ``window_s`` is the traced stretch's length on
    the host clock; whatever of it no device operation covers is idle.
    Returns None when the trace has no device plane (nothing to read)."""
    devices = [lines for pname, lines in trace.items()
               if device_plane.match(pname)]
    if not devices or window_s <= 0:
        return None
    kernel_re = re.compile(kernel_pattern) if kernel_pattern else None
    busy = []                        # per device, ns
    by_name: dict = {}
    kernel_ns = 0.0
    kernel_calls = 0
    gaps_by: dict = {}
    longest = 0.0
    for n, lines in enumerate(devices):
        ops = [ev for ln in op_lines for ev in lines.get(ln, ())]
        merged = _busy_intervals((s, s + d) for _, s, d in ops)
        busy.append(sum(e - s for s, e in merged))
        for name, _, d in ops:
            by_name[name] = by_name.get(name, 0.0) + d
            if kernel_re is not None and kernel_re.search(name):
                kernel_ns += d
                kernel_calls += 1
        if n == 0:                   # gaps of the first device
            modules = lines.get(module_line, ())
            for (_, e0), (s1, _) in zip(merged, merged[1:]):
                name = _name_gap(e0, s1, modules)
                gaps_by[name] = gaps_by.get(name, 0.0) + (s1 - e0)
                longest = max(longest, s1 - e0)
            edges = window_s * 1e9 - (merged[-1][1] - merged[0][0]) \
                if merged else window_s * 1e9
            gaps_by["(before the first and after the last op)"] = edges
    n_dev = len(devices)
    busy_s = sum(busy) / n_dev / 1e9
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "kernel_s": kernel_ns / n_dev / 1e9 if kernel_re else None,
        "kernel_calls": kernel_calls,
        "device_ops": [[n, ns / n_dev / 1e9] for n, ns in device_ops],
        "idle_gaps": [[n, ns / 1e9] for n, ns in sorted(
            gaps_by.items(), key=lambda kv: -kv[1])[:TOP]],
        "planes": {pname: {ln: len(ev) for ln, ev in lines.items()}
                   for pname, lines in trace.items()},
        "longest_gap_s": longest / 1e9,
    }
