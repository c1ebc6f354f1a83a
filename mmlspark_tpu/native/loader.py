"""NativeLoader — compile-on-first-use + ctypes binding.

Reference ``core/env/NativeLoader.java``: resources → temp dir →
``System.load``; one load per JVM, thread-safe. Here: source → cached .so
keyed by source hash → ``ctypes.CDLL``; one per process.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
# under the temp dir, never the checkout: the build is -march=native,
# and a checkout is copied between machines
_CACHE_DIR = os.environ.get(
    "MMLSPARK_TPU_NATIVE_CACHE",
    os.path.join(tempfile.gettempdir(), "mmlspark_tpu_native"))
_LOG = logging.getLogger("mmlspark_tpu.native")


class NativeLoader:
    """Build + load one shared library from shipped C++ source."""

    _lock = threading.Lock()
    _loaded: dict[str, ctypes.CDLL] = {}

    def __init__(self, name: str, sources: list[str],
                 extra_flags: tuple[str, ...] = ()):
        self.name = name
        self.sources = [os.path.join(_SRC_DIR, s) for s in sources]
        self.extra_flags = extra_flags

    def _so_path(self) -> str:
        h = hashlib.sha256()
        for s in self.sources:
            with open(s, "rb") as f:
                h.update(f.read())
        h.update(" ".join(self.extra_flags).encode())
        return os.path.join(_CACHE_DIR,
                            f"lib{self.name}_{h.hexdigest()[:16]}.so")

    def _build(self, so_path: str) -> None:
        os.makedirs(_CACHE_DIR, exist_ok=True)
        # per-process temp name so concurrent builders never share an
        # artifact; os.replace publishes whichever finishes atomically
        tmp = f"{so_path}.{os.getpid()}.build"
        cmd = ["g++", "-O3", "-march=native", "-shared", "-fPIC",
               "-std=c++17", "-pthread", *self.extra_flags,
               *self.sources, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, so_path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)

    def load(self) -> ctypes.CDLL:
        with NativeLoader._lock:
            if self.name in NativeLoader._loaded:
                return NativeLoader._loaded[self.name]
            so = self._so_path()
            if not os.path.exists(so):
                self._build(so)
            lib = ctypes.CDLL(so)
            NativeLoader._loaded[self.name] = lib
            return lib


_libs: dict[str, ctypes.CDLL | None] = {}


def _lazy_native(name: str, sources: list[str], configure):
    """Shared lazy loader: one build+load per process, honoring the
    ``MMLSPARK_TPU_DISABLE_NATIVE=1`` kill-switch; returns None when the
    library cannot be built or loaded (callers fall back to Python
    paths). The reason is logged once at WARNING, compiler stderr
    included, so "no g++" can be told from "source no longer builds"."""
    if name in _libs:
        return _libs[name]
    if os.environ.get("MMLSPARK_TPU_DISABLE_NATIVE", "") == "1":
        _libs[name] = None
        return None
    try:
        lib = NativeLoader(name, sources).load()
        configure(lib)
    except Exception as e:
        stderr = getattr(e, "stderr", None)
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        _LOG.warning("native library %s unavailable: %r%s", name, e,
                     f"\n{stderr.strip()[-4000:]}" if stderr else "")
        _libs[name] = None
        return None
    _libs[name] = lib
    return lib


def get_vwhash():
    """The batch VW-hashing library (vwhash.cpp), or None."""
    def configure(lib):
        i64 = ctypes.c_int64
        u32 = ctypes.c_uint32
        lib.vw_murmur3_32.argtypes = [ctypes.c_char_p, i64, u32]
        lib.vw_murmur3_32.restype = u32
        lib.vw_hash_strings.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(i64), i64,   # buf, offsets, n
            ctypes.c_char_p, i64, u32,                   # prefix, len, seed
            ctypes.c_int, ctypes.c_int,                  # bits, mode
            ctypes.POINTER(i64),                         # out CSR offsets
            ctypes.c_int,                                # sum_collisions
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32)]
        lib.vw_hash_strings.restype = None

    return _lazy_native("vwhash", ["vwhash.cpp"], configure)


def get_httpfront():
    """The native epoll HTTP serving front (httpfront.cpp), or None."""
    def configure(lib):
        i64 = ctypes.c_int64
        u64 = ctypes.c_uint64
        lib.hf_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                 ctypes.POINTER(ctypes.c_int)]
        lib.hf_start.restype = i64
        lib.hf_poll.argtypes = [i64, ctypes.POINTER(u64), i64,
                                ctypes.c_int]
        lib.hf_poll.restype = i64
        lib.hf_req_info.argtypes = [i64, u64, ctypes.c_char_p, i64,
                                    ctypes.c_char_p, i64,
                                    ctypes.POINTER(i64),
                                    ctypes.POINTER(i64)]
        lib.hf_req_info.restype = ctypes.c_int
        lib.hf_req_body.argtypes = [i64, u64, ctypes.c_char_p]
        lib.hf_req_body.restype = i64
        lib.hf_req_headers.argtypes = [i64, u64, ctypes.c_char_p]
        lib.hf_req_headers.restype = i64
        lib.hf_reply.argtypes = [i64, u64, ctypes.c_int, ctypes.c_char_p,
                                 ctypes.c_char_p, i64]
        lib.hf_reply.restype = ctypes.c_int
        lib.hf_stop.argtypes = [i64]
        lib.hf_stop.restype = None

    return _lazy_native("httpfront", ["httpfront.cpp"], configure)


def get_fastio():
    """The fastio library with argtypes configured, or None."""
    def configure(lib):
        i64 = ctypes.c_int64
        lib.csv_dims.argtypes = [ctypes.c_char_p, i64, ctypes.c_int,
                                 ctypes.POINTER(i64), ctypes.POINTER(i64)]
        lib.csv_dims.restype = ctypes.c_int
        lib.csv_parse.argtypes = [ctypes.c_char_p, i64, ctypes.c_int, i64,
                                  i64, ctypes.POINTER(ctypes.c_float),
                                  ctypes.c_int]
        lib.csv_parse.restype = ctypes.c_int
        lib.read_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p, i64]
        lib.read_file.restype = i64
        lib.file_size.argtypes = [ctypes.c_char_p]
        lib.file_size.restype = i64

    return _lazy_native("fastio", ["fastio.cpp"], configure)
