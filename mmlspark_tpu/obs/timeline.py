"""Device events and host spans on one clock.

On the TPU the profiler is usable only with its host tracer off (PERF.md
section 6), so a capture holds the device's events and nothing of the
host, and the tracer's spans (``obs.tracing``) hold the host and nothing
of the device. A :class:`Capture` — what ``obs.profile.profile_trace``
yields — joins them: it keeps the capture's ``.xplane.pb`` and the ring's
spans of the captured stretch, estimates the offset between the two
clocks FROM THE CAPTURE (:func:`clock_offset`), and puts each idle gap of
the device down to the host span it falls under (:func:`idle_by_span`).

The two clocks are pinned together by ``profile_trace``'s own pings
alone: one tiny program run to its end under a ``profile.ping.launch``
and a ``profile.ping.fetch`` span, before and after the stretch, while
the device is quiet. The stretch's own programs and spans take no part
(a loop's programs wait for their inputs, 77-91 ms on the v5e, and bound
nothing), so the block may run any device work under any spans, or none.

The arithmetic is pure Python over plain lists, so the tests check it on
hand-made captures; only :meth:`Capture.device_programs` touches JAX.
"""

from __future__ import annotations

import glob
import os
import re

from .tracing import tracer as _tracer

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"      # one event per executed program
OP_LINE = "XLA Ops"              # one event per executed instruction

NO_SPAN = "(no span)"
BETWEEN = "(between operations)"
UNRESOLVED = "(unresolved)"

PING = "profile.ping"            # profile_trace's span around one ping
PING_PROGRAM = "jit_profile_ping"    # and the program it runs


def merge(intervals) -> list:
    """Merged ``(start, end)`` intervals, in order."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clock_offset(ping_programs, ping_spans) -> dict:
    """The offset δ that puts the capture's device clock onto the spans'
    clock (host = device + δ), as the interval the pings allow.

    ``ping_programs`` are the pings' executions ``(name, start_ns,
    end_ns)`` on the device clock, ``ping_spans`` their spans; the i-th
    program is the i-th ping's. It cannot have started before its launch
    call began, and its result cannot have been on the host before it
    ended, so ``max_i(launch_i.start - dev_i.start) <= δ <=
    min_i(fetch_i.end - dev_i.end)``. A count mismatch or an empty
    interval is an error, not a guess. ``offset_ns`` is 0 when 0 lies
    inside (the device's timestamps are already on the spans' clock),
    else the midpoint; ``by_ping`` has each ping's own interval, which
    shows how far the clocks drift in a stretch."""
    programs = sorted(ping_programs, key=lambda p: p[1])
    by_start = sorted(ping_spans, key=lambda s: s.start_ns)
    launches = [s for s in by_start if s.name == PING + ".launch"]
    fetches = [s for s in by_start if s.name == PING + ".fetch"]
    if not programs or not len(programs) == len(launches) == len(fetches):
        raise ValueError(
            f"{len(programs)} ping program(s) in the capture against "
            f"{len(launches)} launch and {len(fetches)} fetch span(s) of "
            "a ping: nothing pins the device clock to the spans'")
    by_ping = [[l.start_ns - p[1], f.end_ns - p[2]]
               for p, l, f in zip(programs, launches, fetches)]
    lo = max(b[0] for b in by_ping)
    hi = min(b[1] for b in by_ping)
    if lo > hi:
        raise ValueError(
            f"empty offset interval [{lo}, {hi}] ns: the ping programs "
            "and the ping spans do not belong together")
    zero_inside = lo <= 0 <= hi
    return {"lo_ns": int(lo), "hi_ns": int(hi), "width_ns": int(hi - lo),
            "zero_inside": zero_inside,
            "offset_ns": 0 if zero_inside else int((lo + hi) // 2),
            "by_ping": by_ping}


def _split_by_span(intervals, spans, width_ns: int) -> list:
    """``[(name, seconds, pieces)]``: each ``(start, end)`` on the spans'
    clock split by overlap among the innermost spans that cover it."""
    ids = {s.span_id: s for s in spans}

    def depth(s):
        d = 0
        while s.parent_id in ids:
            s, d = ids[s.parent_id], d + 1
        return d

    depths = {s.span_id: depth(s) for s in spans}
    roots = [s for s in spans if s.parent_id not in ids]
    first_root = min((s.start_ns for s in roots), default=None)
    last_root = max((s.end_ns for s in roots), default=None)
    total: dict = {}

    def add(name, ns):
        got = total.setdefault(name, [0, 0])
        got[0] += ns
        got[1] += 1

    for a, b in intervals:
        if b <= a:
            continue
        if b - a < width_ns:
            add(UNRESOLVED, b - a)
            continue
        over = [s for s in spans if s.start_ns < b and s.end_ns > a]
        cuts = sorted({a, b} | {min(max(t, a), b) for s in over
                               for t in (s.start_ns, s.end_ns)})
        for p, q in zip(cuts, cuts[1:]):
            cover = [s for s in over if s.start_ns <= p and s.end_ns >= q]
            if cover:
                name = max(cover, key=lambda s: (depths[s.span_id],
                                                 s.start_ns)).name
            elif roots and first_root <= p and q <= last_root:
                name = BETWEEN
            else:
                name = NO_SPAN
            add(name, q - p)
    return sorted(([n, ns / 1e9, k] for n, (ns, k) in total.items()),
                  key=lambda row: -row[1])


def idle_by_span(busy, spans, offset: dict, stretch) -> dict:
    """Where the device's idle and busy time fell on the host.

    ``busy`` are the device's merged op intervals on the device clock,
    ``stretch`` the captured stretch ``(start_ns, end_ns)`` on the spans'
    clock. Every idle gap (between busy intervals, and the stretch's two
    edges) is moved to the spans' clock by ``offset`` and split by
    overlap among the innermost spans of the stretch's trees (children by
    ``parent_id``) that cover it. What no span covers is ``(no span)``, or
    ``(between operations)`` where it lies between two trees; a gap
    shorter than the offset interval's width is ``(unresolved)``. Busy
    intervals are split the same way: what the host did meanwhile.
    Returns ``{"idle": [[name, seconds, gaps]], "busy": [[name, seconds,
    intervals]]}``, largest first."""
    d = offset["offset_ns"]
    on_host = [(max(s + d, stretch[0]), min(e + d, stretch[1]))
               for s, e in busy]
    on_host = [(s, e) for s, e in on_host if s < e]    # inside the stretch
    edges = [stretch[0]] + [t for iv in on_host for t in iv] + [stretch[1]]
    gaps = list(zip(edges[0::2], edges[1::2]))
    return {"idle": _split_by_span(gaps, spans, offset["width_ns"]),
            "busy": _split_by_span(on_host, spans, 0)}


class Capture:
    """One profiler capture and the spans of its stretch. Filled by
    ``profile_trace``: ``path`` (the ``.xplane.pb``, None when the
    profiler wrote none), ``spans``, ``pings`` and ``stretch`` exist
    after the block."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.path: str | None = None
        self.spans: list = []       # of the stretch: the block's own
        self.pings: list = []       # profile_trace's, around the stretch
        self.stretch = (0, 0)
        self._device = None

    def close(self, start_ns: int, end_ns: int, trace_ns: int) -> None:
        self.stretch = (start_ns, end_ns)
        for s in _tracer.recent(since=trace_ns):
            inside = start_ns <= s.start_ns and s.end_ns <= end_ns
            (self.spans if inside else self.pings).append(s)
        paths = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        self.path = paths[-1] if paths else None

    def device_programs(self):
        """``(programs, busy)`` of the first device plane, on the device
        clock: ``[(name, start_ns, end_ns)]`` from ``XLA Modules`` and the
        merged ``XLA Ops`` intervals. Both empty when the capture has no
        device plane (a CPU capture)."""
        if self._device is None:
            lines = {}
            if self.path is not None:
                from jax.profiler import ProfileData
                data = ProfileData.from_file(self.path)
                plane = next((p for p in data.planes
                              if DEVICE_PLANE.match(p.name)), None)
                lines = {ln.name: ln for ln in plane.lines} if plane else {}

            def events(line):
                return [(ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in lines[line].events] if line in lines else []

            self._device = (sorted(events(MODULE_LINE), key=lambda p: p[1]),
                            merge((s, e) for _, s, e in events(OP_LINE)))
        return self._device

    def clock_offset(self) -> dict:
        return clock_offset([p for p in self.device_programs()[0]
                             if p[0].startswith(PING_PROGRAM)], self.pings)

    def idle_by_span(self) -> dict:
        return idle_by_span(self.device_programs()[1], self.spans,
                            self.clock_offset(), self.stretch)
