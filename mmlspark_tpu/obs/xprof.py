"""On-demand device profiler capture behind the serving debug surface.

``POST /debug/xprof?duration_ms=500`` on either serving front captures
a bounded-duration device trace (``profile.start_device_trace``: host
and Python tracers off, the one setting usable on the TPU) into a
rank-suffixed directory under the capture root;
``GET /debug/xprof`` lists finished captures and
``GET /debug/xprof?fetch=<name>`` returns one as a zip archive. The
distributed server adds pod fanout on top (one POST captures every
rank over the ``__fleet__`` mesh route — ``serving/distributed.py``).

Contracts the serving plane depends on:

- **one capture at a time** — a second POST while a trace is open
  answers 409 (the profiler is a process-global singleton; overlapping
  sessions corrupt each other),
- **bounded duration** — ``duration_ms`` is clamped to
  [1, ``MMLSPARK_TPU_XPROF_MAX_MS``] (default 30 s) so a fat-fingered
  request cannot leave tracing on,
- **no-JAX-safe degradation** — a host-only process answers
  503-with-reason without EVER importing jax (same never-initialize
  guard as ``profile.device_platform``); merely asking for a capture
  must not drag backend bring-up into a serving process.

Import is stdlib-only; jax is touched only inside a capture, and only
when it is already live in the process.
"""

from __future__ import annotations

import io
import json
import os
import sys
import tempfile
import threading
import time
import urllib.parse
import zipfile

from .metrics import registry as _registry

#: duration ceiling (ms) — env-overridable for long captures
ENV_MAX_MS = "MMLSPARK_TPU_XPROF_MAX_MS"
#: capture root override (default: a per-process dir under /tmp)
ENV_DIR = "MMLSPARK_TPU_XPROF_DIR"

_DEFAULT_MAX_MS = 30_000.0


def _jax_ready() -> tuple[bool, str]:
    """Whether a capture can run NOW, without importing jax or
    initializing a backend. The reason string is the 503 body's
    payload when not."""
    mod = sys.modules.get("jax")
    if mod is None:
        return False, "jax not imported in this process"
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not getattr(xb, "_backends", None):
        return False, "jax backend not initialized"
    return True, ""


class XprofCaptures:
    """The per-process capture manager both fronts route through."""

    def __init__(self, root: str | None = None, registry=None):
        reg = registry if registry is not None else _registry
        self._root = root or os.environ.get(ENV_DIR) \
            or os.path.join(tempfile.gettempdir(),
                            f"mmlspark_tpu_xprof_{os.getpid()}")
        self._lock = threading.Lock()
        self._active: str | None = None
        self._seq = 0
        self._c_captures = reg.counter(
            "profile_xprof_captures_total",
            "on-demand device-trace capture attempts, by outcome "
            "(ok | busy | unavailable | error)")

    @property
    def root(self) -> str:
        return self._root

    def _max_ms(self) -> float:
        try:
            return float(os.environ.get(ENV_MAX_MS, _DEFAULT_MAX_MS))
        except (TypeError, ValueError):
            return _DEFAULT_MAX_MS

    def _rank(self) -> str:
        from .profile import process_label
        return process_label() or "0"

    # -- capture -----------------------------------------------------------
    def capture(self, duration_ms: float, tag: str = "") -> dict:
        """Run one bounded capture, blocking for its duration. Raises
        :class:`CaptureUnavailable` (-> 503) when jax is absent and
        :class:`CaptureBusy` (-> 409) when a capture is already open."""
        ok, reason = _jax_ready()
        if not ok:
            self._c_captures.inc(1, outcome="unavailable")
            raise CaptureUnavailable(reason)
        duration_ms = min(max(float(duration_ms), 1.0), self._max_ms())
        with self._lock:
            if self._active is not None:
                self._c_captures.inc(1, outcome="busy")
                raise CaptureBusy(self._active)
            self._seq += 1
            name = f"capture-{self._seq:04d}"
            if tag:
                name += f"-{_clean(tag)}"
            name += f"-r{self._rank()}"
            self._active = name
        log_dir = os.path.join(self._root, name)
        import jax
        from .profile import start_device_trace
        try:
            os.makedirs(log_dir, exist_ok=True)
            start_device_trace(log_dir)
            try:
                time.sleep(duration_ms / 1e3)
            finally:
                jax.profiler.stop_trace()
        except Exception:
            self._c_captures.inc(1, outcome="error")
            raise
        finally:
            with self._lock:
                self._active = None
        self._c_captures.inc(1, outcome="ok")
        return {"capture": name, "dir": log_dir,
                "duration_ms": duration_ms,
                "files": _count_files(log_dir)}

    # -- read surface ------------------------------------------------------
    def list_captures(self) -> dict:
        captures = []
        if os.path.isdir(self._root):
            for name in sorted(os.listdir(self._root)):
                d = os.path.join(self._root, name)
                if os.path.isdir(d):
                    captures.append({"capture": name,
                                     "files": _count_files(d)})
        ok, reason = _jax_ready()
        with self._lock:
            active = self._active
        return {"root": self._root, "active": active,
                "available": ok, "reason": reason,
                "captures": captures}

    def fetch(self, name: str) -> bytes | None:
        """One finished capture as zip bytes (None when unknown). The
        name is sanitized against traversal — only direct children of
        the root are fetchable."""
        name = os.path.basename(name)
        d = os.path.join(self._root, name)
        if not name or not os.path.isdir(d):
            return None
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            for base, _dirs, files in os.walk(d):
                for f in files:
                    full = os.path.join(base, f)
                    z.write(full, os.path.relpath(full, d))
        return buf.getvalue()

    # -- the /debug/xprof route adapter ------------------------------------
    def handle_query(self, query: str, body: bytes) -> tuple[int, bytes]:
        """Both fronts' ``/debug/xprof`` handler: ``duration_ms=`` in
        the query runs a capture, ``fetch=<name>`` returns an archive,
        anything else lists. (Method is not part of the shared route
        signature; the query carries the intent, like
        ``/debug/timeline``.)"""
        q = urllib.parse.parse_qs(query or "")
        if "duration_ms" in q:
            try:
                duration = float(q["duration_ms"][0])
            except (TypeError, ValueError, IndexError):
                return 400, b'{"error": "bad duration_ms"}'
            tag = (q.get("tag") or [""])[0]
            try:
                out = self.capture(duration, tag=tag)
            except CaptureUnavailable as e:
                return 503, json.dumps(
                    {"error": "xprof unavailable",
                     "reason": str(e)}).encode()
            except CaptureBusy as e:
                return 409, json.dumps(
                    {"error": "capture in flight",
                     "active": str(e)}).encode()
            except Exception as e:
                return 500, json.dumps(
                    {"error": "capture failed",
                     "reason": repr(e)}).encode()
            return 200, json.dumps(out, indent=1).encode()
        if "fetch" in q:
            blob = self.fetch((q.get("fetch") or [""])[0])
            if blob is None:
                return 404, b'{"error": "unknown capture"}'
            return 200, blob
        return 200, json.dumps(self.list_captures(),
                               indent=1).encode()


class CaptureUnavailable(RuntimeError):
    """No live jax backend in this process -> HTTP 503."""


class CaptureBusy(RuntimeError):
    """A capture is already open -> HTTP 409."""


def _clean(tag: str) -> str:
    return "".join(c if c.isalnum() or c in "-_" else "_"
                   for c in str(tag))[:48]


def _count_files(d: str) -> int:
    return sum(len(files) for _b, _d, files in os.walk(d))


#: THE process-wide capture manager (both fronts route through it).
xprof_captures = XprofCaptures()
