"""The one general generator: a cell's inputs from its workload file's
``inputs`` entry and ``--seed``. Every cell of this benchmark is a closed
loop of one caller (a batch job runs its next ``transform`` or ``fit``
when the last one returned), so a traffic mix is a table of sizes; the
same seed gives the same inputs, and every seed the same sizes."""

from __future__ import annotations

import numpy as np


def uint8_images(spec: dict, seed: int) -> dict:
    rng = np.random.default_rng(int(seed))
    shape = (int(spec["count"]), int(spec["size"]), int(spec["size"]),
             int(spec["channels"]))
    return {"images": rng.integers(0, 256, size=shape, dtype=np.uint8)}


def higgs_rows(spec: dict, seed: int) -> dict:
    from benchmark.references.gbdt import make_data
    rows, held = int(spec["rows"]), int(spec["held_out_rows"])
    x, y = make_data(seed, rows + held, int(spec["features"]))
    return {"x": x[:rows], "y": y[:rows], "held_out": x[rows:]}


def float_image_batches(spec: dict, seed: int) -> dict:
    """A ring of host batches, every row different: float32 images and
    int32 labels."""
    rng = np.random.default_rng(int(seed))
    shape = (int(spec["batch"]), int(spec["size"]), int(spec["size"]),
             int(spec["channels"]))
    return {"batches": [
        (rng.standard_normal(shape, dtype=np.float32),
         rng.integers(0, int(spec["classes"]), size=shape[0],
                      dtype=np.int32))
        for _ in range(int(spec["ring"]))]}


KINDS = {"uint8_images": uint8_images, "higgs_rows": higgs_rows,
         "float_image_batches": float_image_batches}


def make_inputs(spec: dict, seed: int) -> dict:
    if spec["kind"] not in KINDS:
        raise KeyError(f"unknown inputs kind {spec['kind']!r} "
                       f"(known: {sorted(KINDS)})")
    return KINDS[spec["kind"]](spec, seed)


def sample_rows(seed: int, count: int, n: int, always=()) -> np.ndarray:
    """``n`` distinct row indices below ``count`` drawn from the seed,
    with ``always`` among them."""
    rng = np.random.default_rng([int(seed), 0x5A])
    fixed = sorted({int(i) % count for i in always})
    rest = [int(i) for i in rng.permutation(count) if int(i) not in fixed]
    return np.asarray(sorted(fixed + rest[:max(min(n, count) - len(fixed),
                                               0)]), np.int64)
