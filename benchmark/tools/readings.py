"""Readings for setting a comparison's limits: a cell's numbers over
many seeds in ONE process (set-up paid per seed, compilation once), for
the program as the configuration states it or, with ``--control``, for
the cell's lower-precision control put in the program's place. Not part
of a benchmark run; needs the chip the cell asks for.

    python3 benchmark/tools/readings.py --workload higgs.fit --seeds 1,2,3 [--control]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--ops", type=int, default=1)
    ap.add_argument("--proposed", default=None,
                    help="also read benchmark/proposed/<name>.json")
    args = ap.parse_args(argv)

    from benchmark import run
    import jax
    run.place_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return run.NO_CHIP
    _, wl, cfg, params = run.load_cell(args.workload,
                                       run.load_bench(args.proposed))
    driver = run._load_module("drivers", wl["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if args.control:
            checks = driver.control_checks(cfg, params, seed)
        else:
            ctx = driver.setup(cfg, params, seed)
            driver.warm(ctx)
            for _ in range(args.ops):
                driver.step(ctx)
            outputs = driver.outputs_for_check(ctx)
            del ctx
            checks = driver.check(outputs, cfg, params, seed)
            del outputs
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control": args.control,
            "seconds": time.perf_counter() - t0,
            "device_kind": jax.devices()[0].device_kind,
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
