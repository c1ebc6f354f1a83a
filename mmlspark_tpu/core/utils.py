"""Core utilities: fault tolerance, timing, device topology, schema helpers.

Covers the reference's ``core/utils`` + ``downloader/ModelDownloader.scala``
fault-tolerance wrapper + ``core/utils/ClusterUtil.scala`` cluster-topology
discovery. On TPU, "cluster topology" = the JAX device/mesh view: number of
local devices, hosts, and a default mesh over which stages shard work.
"""

from __future__ import annotations

import concurrent.futures
import time
from typing import Callable, Sequence, TypeVar

import numpy as np

T = TypeVar("T")

# Reference downloader/ModelDownloader.scala:37-60 backoff sequence.
DEFAULT_BACKOFFS_MS: tuple[int, ...] = (0, 100, 200, 500)


def retry_with_timeout(fn: Callable[[], T],
                       timeout_s: float | None = None,
                       backoffs_ms: Sequence[int] = DEFAULT_BACKOFFS_MS) -> T:
    """Retry ``fn`` over a backoff schedule; optional per-attempt timeout.

    Caveat (same semantics as the reference's ``Await.result``-based wrapper):
    a timed-out attempt's thread keeps running in the background, so with
    ``timeout_s`` the ``fn`` must tolerate concurrent invocations.
    """
    if not backoffs_ms:
        raise ValueError("backoffs_ms must contain at least one entry")
    last: Exception | None = None
    for i, backoff in enumerate(backoffs_ms):
        if backoff:
            time.sleep(backoff / 1000.0)
        try:
            if timeout_s is None:
                return fn()
            # No `with`: __exit__ would join the worker and defeat the timeout.
            ex = concurrent.futures.ThreadPoolExecutor(max_workers=1)
            try:
                return ex.submit(fn).result(timeout=timeout_s)
            finally:
                ex.shutdown(wait=False)
        except Exception as e:  # noqa: BLE001 — retry wrapper by design
            last = e
    assert last is not None  # loop ran ≥ once since backoffs_ms is non-empty
    raise last


class StopWatch:
    """Nanosecond accumulator (reference ``core/utils/StopWatch.scala``)."""

    def __init__(self):
        self.elapsed_ns = 0
        self._start: int | None = None

    def start(self) -> None:
        self._start = time.perf_counter_ns()

    def stop(self) -> None:
        if self._start is not None:
            self.elapsed_ns += time.perf_counter_ns() - self._start
            self._start = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def measure(self, fn: Callable[[], T]) -> T:
        with self:
            return fn()


class ClusterUtil:
    """Device-topology discovery — the TPU analogue of executor counting.

    Reference ``core/utils/ClusterUtil.scala:13-291`` asks Spark how many
    executors × cores are available to size the LightGBM worker mesh; here we
    ask JAX for devices/hosts and size shard counts the same way.
    """

    @staticmethod
    def get_num_devices() -> int:
        import jax
        return jax.device_count()

    @staticmethod
    def get_num_local_devices() -> int:
        import jax
        return jax.local_device_count()

    @staticmethod
    def get_num_hosts() -> int:
        import jax
        return jax.process_count()

    @staticmethod
    def get_host_index() -> int:
        import jax
        return jax.process_index()

    @staticmethod
    def default_mesh(axis_name: str = "dp"):
        import jax
        from jax.sharding import Mesh
        devices = np.asarray(jax.devices())
        return Mesh(devices, (axis_name,))

    @staticmethod
    def get_jvm_cpus() -> int:
        import os
        return os.cpu_count() or 1


def find_unused_column_name(prefix: str, df) -> str:
    """Reference ``core/schema/DatasetExtensions.findUnusedColumnName``."""
    name = prefix
    i = 0
    while name in df.columns:
        i += 1
        name = f"{prefix}_{i}"
    return name


def cpu_child_env(n_devices: int | None = None,
                  extra_path: str | None = None) -> dict:
    """Environment for a child process that runs on the host CPU
    (optionally with ``n_devices`` virtual devices) and so never takes
    the accelerator: a chip belongs to one process at a time, and a
    parent that has touched JAX holds it."""
    import os
    env = dict(os.environ)
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if extra_path:
        parts.insert(0, extra_path)
    env["PYTHONPATH"] = os.pathsep.join(parts)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    # compile-cache placement (core/aot.jax_cache_dir): a value set
    # from outside is kept; otherwise the child shares the checkout's
    # CPU cache — apart from the parent's, which may hold accelerator
    # executables
    from .aot import jax_cache_dir
    env["JAX_COMPILATION_CACHE_DIR"] = jax_cache_dir("cpu")
    return env


def stable_sigmoid(x: np.ndarray) -> np.ndarray:
    """Overflow-free logistic: exp is only ever taken of a non-positive
    argument."""
    x = np.asarray(x)
    e = np.exp(-np.abs(x))
    return np.where(x >= 0, 1.0 / (1.0 + e), e / (1.0 + e))


def as_2d_features(df, features_col: str) -> np.ndarray:
    """Features column → dense float32 [n, d] matrix."""
    arr = df[features_col]
    if arr.dtype == object:
        arr = np.stack([np.asarray(v, dtype=np.float32) for v in arr])
    if arr.ndim == 1:
        arr = arr[:, None]
    return np.ascontiguousarray(arr, dtype=np.float32)


def using(resources: Sequence, fn: Callable):
    """RAII helper (reference ``core/env/StreamUtilities.using``)."""
    try:
        return fn(*resources)
    finally:
        for r in resources:
            close = getattr(r, "close", None)
            if close:
                close()
