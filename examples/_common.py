"""Shared example bootstrap: force the virtual CPU platform so examples
run anywhere (the notebooks' 'works on a laptop' property), keep sizes
small, and give each example a PASS/FAIL contract the runner checks."""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

if os.environ.get("MMLSPARK_TPU_EXAMPLES_CPU", "1") != "0":
    jax.config.update("jax_platforms", "cpu")
from mmlspark_tpu.core.aot import place_jax_cache  # noqa: E402

place_jax_cache("cpu")

import numpy as np  # noqa: E402


def binary_table(n=400, f=8, seed=0):
    """Adult-census-shaped synthetic: mixed numeric + categorical."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    cat = rng.choice(["blue", "green", "red"], size=n)
    y = ((x[:, 0] + (cat == "red") * 1.5 + 0.3 * x[:, 1]) > 0.4)
    return x, cat, y.astype(np.float32)


def done(name: str):
    print(f"EXAMPLE_OK {name}")
