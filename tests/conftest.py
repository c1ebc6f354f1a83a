"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's test strategy (SURVEY §4.4): distributed behavior is
exercised without a real cluster — there, multi-partition DataFrames on
local[*]; here, a virtual 8-device CPU platform so every sharding/collective
path runs the real SPMD code.
"""

import os

# Force CPU: tests never take an accelerator, whatever the session's
# JAX_PLATFORMS says (a plain setdefault would keep it).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Isolate the learned-performance store (perf/): a leftover autotune
# winner registry or persisted cost model in the per-user /tmp default
# would change kernel tile configs and scheduler pricing under tests —
# ambient machine state must not steer deterministic suites.
import tempfile  # noqa: E402

os.environ["MMLSPARK_TPU_PERF_STORE"] = tempfile.mkdtemp(
    prefix="mmlspark_tpu_perf_tests_")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Persistent XLA compilation cache: the GBDT/DL kernels recompile per
# hyperparameter set; caching keeps repeat test runs fast.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the tests' own fixed subdirectory of the compile cache
# (core/aot.place_jax_cache; JAX_COMPILATION_CACHE_DIR, when set, is the
# whole answer): CPU executables cached on one machine and loaded on
# another shift float results (a knife-edge statistical test failed
# deterministically from this) and risk SIGILL per the cpu_aot_loader
# warning, so they stay apart from what a script run elsewhere caches
from mmlspark_tpu.core.aot import place_jax_cache  # noqa: E402

place_jax_cache("tests")
# cache aggressively: the suite compiles hundreds of sub-second SPMD
# programs (8-device shard_map bodies recompile per hyperparameter set)
# whose compile time dominates some files — at 1.0s threshold most of
# them re-compiled every run
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def eight_device_mesh():
    import jax
    from jax.sharding import Mesh
    devices = np.asarray(jax.devices())
    assert devices.size == 8, f"expected 8 virtual devices, got {devices.size}"
    return Mesh(devices, ("dp",))


@pytest.fixture
def grouped_kernels(monkeypatch):
    """Route every expert layer's grouped products through the Pallas
    kernels (interpreted), where the platform switch picks the
    ``ragged_dot`` passes on the CPU: steered from the test, not through an
    option of the program."""
    from mmlspark_tpu.dl import pallas_grouped
    from mmlspark_tpu.models import moe

    def through_kernels(*args, **kw):
        return pallas_grouped.grouped_expert_products(
            *args, **{**kw, "impl": "pallas", "interpret": True})

    monkeypatch.setattr(moe, "grouped_expert_products", through_kernels)
    return monkeypatch          # ``.undo()``: the platform's path again
