"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip. It knows no cell by name: the cell's
file ``workloads/<cell>.json`` names its configuration
(``configs/<config>.json``), its driver (``drivers/<driver>.py``) and its
parameters; ``BENCHMARK.json`` says which metrics the cell reports, and
each per-layer metric is read by ``layer_metrics/<metric>.py``. A later PR
adds a cell, a configuration, a driver or a metric by adding files and one
entry each to ``BENCHMARK.json``.

A driver module has ``setup(cfg, params, seed) -> ctx``, ``warm(ctx)``,
``step(ctx) -> work units`` (one closed-loop operation),
``outputs_for_check(ctx)`` (hands over what the window produced and drops
the program's state), ``check(outputs, cfg, params, seed) -> [(name,
value, limit)]`` and optionally ``after_window(ctx, trace)``.

The last line of standard output is the result; without a TPU (or with
fewer chips than the cell asks for) the exit code is 3 and there is none.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()        # process start, as near as Python gets

import argparse                      # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0                  # least length of the traced stretch
NO_CHIP = 3


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_bench(proposed: str | None = None) -> dict:
    """``BENCHMARK.json``; with ``proposed`` the entries of
    ``proposed/<name>.json`` appended (a cell that is built and measured
    but not among the benchmark's cells: tests and readings only)."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    if proposed:
        extra = _load_json(HERE, "proposed", f"{proposed}.json")
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + extra[key]
    return bench


def load_cell(workload: str, bench: dict, tiny: bool = False):
    """``(BENCHMARK.json entry, workload file, configuration, params)``
    of one cell; ``tiny`` takes the workload file's sizes for the CPU
    tests."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    wl = _load_json(HERE, "workloads", f"{workload}.json")
    cfg = _load_json(HERE, "configs", f"{cell['config']}.json")
    params = dict(wl["params"])
    if tiny:
        params.update(wl["tiny"]["params"])
        cfg = {**cfg, **wl["tiny"].get("config", {})}
    return cell, wl, cfg, params


def cell_metrics(bench: dict, cell: str):
    """The end-to-end and per-layer metric entries the cell reports."""
    def of(entries):
        return [m for m in entries
                if "workloads" not in m or cell in m["workloads"]]
    return of(bench["end_to_end"]), of(bench["per_layer"])


def place_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` already places it."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class _CompileCount:
    """Programs JAX built: compiled, or loaded from the persistent
    cache (the event fires for both). Either way the window was not
    steady if one happened inside it."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def _start_trace(trace_dir: str) -> None:
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    # device planes only: with the host tracer on, at any level, the TPU
    # runtime's threads write tens of millions of futex events and a
    # one-second transform takes fourteen
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def measure(driver, ctx: dict, seconds: float, trace_dir: str | None):
    """The window: back-to-back operations until ``seconds`` have
    passed, all the work over all the time. With ``trace_dir`` a stretch
    of whole operations (the second onward, ``TRACE_SECONDS`` or more) is
    traced; the profiler's own start and stop are left out of the time.
    Returns ``(work, elapsed_s, operations, traced_s)``."""
    import jax
    work = ops = 0
    overhead = traced_s = 0.0
    tracing = traced = False
    trace_t0 = 0.0
    t0 = time.perf_counter()
    while True:
        if trace_dir and not tracing and not traced and (
                ops >= 1 or seconds <= 0):
            t = time.perf_counter()
            _start_trace(trace_dir)
            tracing, trace_t0 = True, time.perf_counter()
            overhead += trace_t0 - t
        work += driver.step(ctx)
        ops += 1
        now = time.perf_counter()
        done = now - t0 - overhead >= seconds
        if tracing and (now - trace_t0 >= TRACE_SECONDS or done):
            traced_s = now - trace_t0
            jax.profiler.stop_trace()
            tracing, traced = False, True
            overhead += time.perf_counter() - now
        if done and (traced or not trace_dir):
            break
    return work, time.perf_counter() - t0 - overhead, ops, traced_s


def _per_layer_metrics(bench: dict, workload: str, reader_ctx: dict) -> dict:
    """Each of the cell's per-layer metrics through its own reader; a
    reader that finds nothing to read leaves its metric out."""
    metrics = {}
    for m in cell_metrics(bench, workload)[1]:
        value = _load_module("layer_metrics", m["name"]).read(reader_ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             tiny: bool = False, params_override: dict | None = None,
             bench: dict | None = None, out=sys.stdout,
             err=sys.stderr) -> int:
    """Run one cell and print its result line. ``tiny`` is the tests'
    CPU mode: the workload file's ``tiny`` sizes, no look for a chip."""
    bench = bench or load_bench()
    cell, wl, cfg, params = load_cell(workload, bench, tiny)
    params.update(params_override or {})

    import jax
    if not tiny:
        place_compile_cache()
        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
            print(f"benchmark: needs {cell['chips']} TPU chip(s), found "
                  f"{len(devices)} x {devices[0].platform}", file=err)
            return NO_CHIP
    devices = jax.devices()[:cell["chips"]]
    on_chip = devices[0].platform == "tpu"

    from benchmark import trace_reduce, work
    compiles = _CompileCount()
    driver = _load_module("drivers", wl["driver"])
    t_imported = time.perf_counter()
    ctx = driver.setup(cfg, params, seed)
    t_ready = time.perf_counter()
    driver.warm(ctx)
    t_warm = time.perf_counter()
    compiled_in_setup = compiles.count
    setup_s = t_warm - T_START
    setup_parts = {"import_s": t_imported - T_START,
                   "inputs_and_weights_s": t_ready - t_imported,
                   "warm_s": t_warm - t_ready}

    trace_dir = os.path.join(ROOT, ".bench_out", "trace", workload) \
        if trace else None
    done, elapsed, ops, traced_s = measure(driver, ctx, seconds, trace_dir)
    compiled_in_window = compiles.count - compiled_in_setup
    rate = done / elapsed
    memory_peak = _memory_peak()
    if hasattr(driver, "after_window"):
        driver.after_window(ctx, trace)

    reduced = None
    if trace:
        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)),
            window_s=traced_s, kernel_pattern=wl.get("kernel_pattern"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if on_chip and (reduced is None or reduced["busy_s"] <= 0):
            raise RuntimeError(
                "the trace shows no operation on the device inside the "
                "traced window: nothing to report, and no result line")

    stats = ctx.get("stats", [])
    if trace:
        metrics = _per_layer_metrics(bench, workload, {
            "cfg": cfg, "params": params, "stats": stats,
            "driver_ctx": ctx, "trace": reduced, "rate": rate,
            "elapsed_s": elapsed, "operations": ops, "work": work,
            "on_chip": on_chip,
            "peaks": work.peaks(devices[0].device_kind) if on_chip else None})
    else:
        values = {wl["rate_metric"]: rate, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, workload)[0]}

    outputs = driver.outputs_for_check(ctx)
    del ctx
    t_check = time.perf_counter()
    checks = driver.check(outputs, cfg, params, seed)
    check_s = time.perf_counter() - t_check
    correct = all(value <= limit for _, value, limit in checks)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": ops, "failed": 0,
              "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}

    info = {"workload": workload, "seed": seed, "window_s": elapsed,
            "operations": ops, "rate": rate, "setup_s": setup_s,
            "setup_parts": setup_parts, "check_s": check_s,
            "compiles_in_setup": compiled_in_setup,
            "compiles_in_window": compiled_in_window,
            "steady": compiled_in_window == 0, "last_op_stats": stats[-1:],
            "platform": device["platform"], "device_kind": device["kind"],
            "device_count": device["count"]}
    if reduced:
        info["trace"] = {k: reduced[k] for k in (
            "planes", "kernel_s", "kernel_calls", "longest_gap_s")}
    print(json.dumps({"info": info}), file=out)
    if compiled_in_window:
        print(f"benchmark: {compiled_in_window} compilation(s) inside the "
              "window: this run is not steady", file=err)
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r}) "
              f"{'ok' if value <= limit else 'NOT CORRECT'}", file=err)
    print(json.dumps(result), file=out)
    out.flush()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace))


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
