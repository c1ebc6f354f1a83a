"""Where a cell's device idle time falls on the host: the cell's set-up
and warm-up as ``run.py`` makes them, then whole operations under
``mmlspark_tpu.obs.profile.profile_trace`` for ``--seconds`` or more, and
one JSON line with the device-to-host clock offset interval the capture
allows and the device's idle (and busy) seconds by the program span they
fall under. Not part of a benchmark run; needs the chip the cell asks
for. Run it before touching a cell's host path.

    python3 benchmark/tools/idle_by_span.py --workload resnet50.featurize --seed 7 --seconds 4

``--dump FILE`` also writes the capture's raw material (programs, busy
intervals, spans, pings) as JSON, to look at or to run the arithmetic on again
without the chip. ``--tiny`` is the rehearsal on the CPU (the workload file's tiny sizes, no
look for a chip): a CPU capture has no device plane, so its line holds
the spans' totals and no table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def span_totals(spans) -> list:
    """``[[name, seconds, count]]`` over the stretch's spans."""
    total: dict = {}
    for s in spans:
        got = total.setdefault(s.name, [0.0, 0])
        got[0] += s.seconds
        got[1] += 1
    return sorted(([n, sec, k] for n, (sec, k) in total.items()),
                  key=lambda row: -row[1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)

    from benchmark import run
    import jax
    from mmlspark_tpu.obs.profile import profile_trace
    from mmlspark_tpu.obs import timeline

    if not args.tiny:
        run.place_compile_cache()
        if jax.devices()[0].platform != "tpu":
            print("idle_by_span: needs a TPU", file=sys.stderr)
            return run.NO_CHIP
    _, wl, cfg, params = run.load_cell(args.workload, run.load_bench(),
                                       args.tiny)
    driver = run._load_module("drivers", wl["driver"])
    ctx = driver.setup(cfg, params, args.seed)
    driver.warm(ctx)

    ops = 0
    with tempfile.TemporaryDirectory() as log_dir:
        with profile_trace(log_dir) as capture:
            t0 = time.perf_counter()
            while not ops or time.perf_counter() - t0 < args.seconds:
                driver.step(ctx)
                ops += 1
        programs, busy = capture.device_programs()
        line = {"workload": args.workload, "seed": args.seed,
                "device_kind": jax.devices()[0].device_kind,
                "operations": ops,
                "stretch_s": (capture.stretch[1] - capture.stretch[0]) / 1e9,
                "device_programs": len(programs),
                "spans": span_totals(capture.spans)}
        if args.dump:
            os.makedirs(os.path.dirname(os.path.abspath(args.dump)),
                        exist_ok=True)
            with open(args.dump, "w") as f:
                json.dump({"programs": programs, "busy": busy,
                           "stretch": capture.stretch,
                           **{key: [{**s.to_dict(), "start_ns": s.start_ns,
                                     "end_ns": s.end_ns} for s in got]
                              for key, got in (("spans", capture.spans),
                                               ("pings", capture.pings))}},
                          f)
        if programs:
            table = capture.idle_by_span()
            idle_s = sum(row[1] for row in table["idle"])
            unnamed = sum(row[1] for row in table["idle"]
                          if row[0] in (timeline.NO_SPAN, timeline.UNRESOLVED))
            line.update(
                offset=capture.clock_offset(),
                busy_s=sum(e - s for s, e in busy) / 1e9, idle_s=idle_s,
                idle_named_share=1.0 - unnamed / idle_s if idle_s else None,
                idle_by_span=table["idle"], busy_by_span=table["busy"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
