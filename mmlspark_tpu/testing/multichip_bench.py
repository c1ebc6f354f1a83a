"""Multi-device throughput bench body (left ``bench.py`` with PR 23;
kept for the benchmark PR to lift).

The partition-rule-sharded BERT train step and the shard_map'd
LightGBM histogram build run on ALL local devices and on one device,
and the ratio is the scaling story the pod-scale roadmap items build on.

A CPU rehearsal by design (like ``__graft_entry__.dryrun_multichip``),
and no part of ``bench.py``, whose one process holds the chip: run it in
a process of its own on a virtual n-device CPU platform,

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m mmlspark_tpu.testing.multichip_bench

Its timings are CPU timings — liveness and ratios, never device speeds;
the four-chip proof is ``chip_smoke.py --chips 4``. :func:`main` prints
ONE JSON line on stdout.

Scaling efficiency is weak-scaling (fixed PER-DEVICE batch):
``ips_n / (n * ips_1)`` — 1.0 means the n-device step is n× the
1-device step. Per-device MFU is achieved FLOP/s per device over the
v5e bf16 peak; on the CPU harness that is a liveness-scale number (the
honest read there is the efficiency ratio), and the JSON says which
platform produced it.
"""

from __future__ import annotations

import json
import time

from ..obs.attribution import peak_spec as _peak_spec

# per-chip bf16 peak from the shared PeakSpec table (bench.py's —
# env-overridable via MMLSPARK_TPU_PEAK_FLOPS)
V5E_PEAK_BF16_FLOPS = _peak_spec("tpu-v5e").peak_flops


def _min_time(fn, reps: int = 3) -> float:
    """Best-of-reps wall seconds of one blocking call."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _bert_step_ips(devices, per_device_batch: int, iters: int = 4):
    """(images/sec, flops_per_image) of the rule-sharded BERT train
    step over a dp mesh on ``devices``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ..dl.bert import BertEncoder
    from ..dl.train import (init_train_state, make_partitioned_train_step,
                            partition_train_state)
    from ..parallel import MeshSpec, build_mesh
    from ..parallel.partition import partition_rules_for

    n = len(devices)
    mesh = build_mesh(MeshSpec(dp=n, tp=1), devices=np.asarray(devices))
    # bf16 like every other *_mfu row in bench.py: the per-device MFU
    # normalizes by the bf16 chip peak, so an f32 model would read ~2x
    # low on real chips
    module = BertEncoder(vocab=1024, width=128, depth=2, heads=4,
                         mlp_dim=256, max_len=64, pooler=False,
                         dtype=jnp.bfloat16)
    tx = optax.adamw(1e-3)
    rng = np.random.default_rng(0)
    B, T = per_device_batch * n, 48
    ids = jnp.asarray(rng.integers(1, 1024, size=(B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 128, size=B), jnp.int32)

    state = init_train_state(module, jax.random.PRNGKey(0), ids[:1], tx)
    state, shardings = partition_train_state(
        state, mesh, partition_rules_for("BertEncoder"))
    step = make_partitioned_train_step(module, tx, mesh, shardings,
                                       fetch="pooled")
    flops_per_image = 0.0
    try:
        compiled = step.lower(state, ids, labels).compile()
    except Exception:
        compiled = None
    if compiled is not None:
        from ..parallel.compat import cost_analysis
        cost = cost_analysis(compiled)
        if cost is not None:
            # sharded programs report per-device flops: scale back to
            # the global batch so flops/image is mesh-size-independent
            flops_per_image = cost["flops"] * n / B
    box = {"s": state}

    def run():
        s, loss = box["s"], None
        for _ in range(iters):
            s, loss = step(s, ids, labels)
        jax.block_until_ready(loss)
        box["s"] = s

    run()  # warm (and the donated state threads through the box)
    secs = _min_time(run)
    return B * iters / secs, flops_per_image


def _gbdt_hist_rows_per_sec(devices, rows_per_device: int,
                            iters: int = 3):
    """rows/sec of the shard_map'd tree grow (histogram build + psum
    tree all-reduce) over a dp mesh on ``devices``."""
    import jax
    import numpy as np

    from ..lightgbm.engine import TreeParams
    from ..lightgbm.trainer import make_grower
    from ..parallel import MeshSpec, build_mesh

    n = len(devices)
    mesh = build_mesh(MeshSpec(dp=n, tp=1), devices=np.asarray(devices))
    rng = np.random.default_rng(1)
    N, F = rows_per_device * n, 32
    tp = TreeParams(num_leaves=31, max_bin=63, min_data_in_leaf=5)
    bins = rng.integers(0, 64, size=(N, F)).astype(np.uint8)
    g = rng.normal(size=N).astype(np.float32)
    h = np.ones(N, np.float32)
    fm = np.ones(F, bool)
    rm = np.ones(N, np.float32)
    grow = make_grower(mesh=mesh, mesh_axis="dp", tp=tp, multi=False,
                       num_features=F, dense_bins=bins)

    def run():
        out = None
        for _ in range(iters):
            out = grow(g, h, fm, rm)
        jax.block_until_ready(out)

    run()  # warm
    secs = _min_time(run)
    return N * iters / secs


def run(n_devices: int = 8) -> dict:
    """The bench body: returns the multichip extras dict."""
    import jax

    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"multichip bench needs {n_devices} devices, have "
            f"{len(devices)} — run under the virtual-mesh env "
            "(see the module docstring)")
    devices = devices[:n_devices]
    out: dict = {
        "multichip_devices": n_devices,
        "multichip_platform": devices[0].platform,
    }

    per_dev_batch = 16
    ips_n, flops_per_image = _bert_step_ips(devices, per_dev_batch)
    ips_1, _ = _bert_step_ips(devices[:1], per_dev_batch)
    out["sharded_train_images_per_sec"] = round(ips_n, 1)
    out["sharded_train_images_per_sec_1dev"] = round(ips_1, 1)
    out["sharded_scaling_efficiency"] = round(
        ips_n / (n_devices * ips_1), 4) if ips_1 else 0.0
    if flops_per_image:
        out["sharded_train_flops_per_image"] = flops_per_image
        out["sharded_train_per_device_flops_per_sec"] = round(
            ips_n * flops_per_image / n_devices, 1)
        out["sharded_train_per_device_mfu"] = round(
            ips_n * flops_per_image / n_devices / V5E_PEAK_BF16_FLOPS, 6)

    rows_per_dev = 8192
    rps_n = _gbdt_hist_rows_per_sec(devices, rows_per_dev)
    rps_1 = _gbdt_hist_rows_per_sec(devices[:1], rows_per_dev)
    out["sharded_gbdt_hist_rows_per_sec"] = round(rps_n, 1)
    out["sharded_gbdt_hist_rows_per_sec_1dev"] = round(rps_1, 1)
    out["sharded_gbdt_scaling_efficiency"] = round(
        rps_n / (n_devices * rps_1), 4) if rps_1 else 0.0
    out.update(crosshost(local_devices=n_devices // 2))
    return out


def crosshost(local_devices: int = 4, timeout: float = 420.0) -> dict:
    """The DCN section: the SAME (2, local) dcn×ici mesh program run as
    a 2-process pod (4 devices per worker, gloo collectives over
    loopback) and as 1 process owning all 8 devices. Identical global
    mesh, identical program, identical data — the throughput ratio
    isolates the process boundary (serialization, gloo hops, per-rank
    dispatch), which is the crosshost scaling-efficiency number the
    pod roadmap items track. Plus: cross-host fused-serving p99 with
    the bit-equality digest checked against the single-process run,
    the instrumented dp-axis allreduce's per-shard byte count, and
    the warmed pod worker's runtime-compile count (must be 0)."""
    from ..parallel.multihost import launch_pod

    scen = "mmlspark_tpu.testing.multihost_scenarios"
    mesh = [2, local_devices]
    total = 2 * local_devices
    # Per-step compute must dominate the per-step process-boundary cost
    # (gloo hops + per-rank dispatch are ~fixed per step) or the ratio
    # measures dispatch overhead, not the data plane: at batch 64 /
    # width 128 the ratio reads ~0.44, at this size ~0.9.
    train_args = {"mesh": mesh, "steps": 2, "batch": 128, "seq_len": 64,
                  "width": 192, "bench_iters": 4, "seed": 0}
    pod = launch_pod(f"{scen}:train_trajectory", num_processes=2,
                     local_devices=local_devices, args=train_args,
                     timeout=timeout)
    solo = launch_pod(f"{scen}:train_trajectory", num_processes=1,
                      local_devices=total, args=train_args,
                      timeout=timeout)
    out: dict = {
        "crosshost_processes": 2,
        "crosshost_mesh": mesh,
        "crosshost_train_images_per_sec": round(pod[0]["ips"], 1),
        "crosshost_train_images_per_sec_1proc": round(
            solo[0]["ips"], 1),
        "crosshost_scaling_efficiency": round(
            pod[0]["ips"] / solo[0]["ips"], 4) if solo[0]["ips"] else 0.0,
        "crosshost_loss_max_abs_diff": max(
            abs(a - b) for a, b in zip(pod[0]["losses"],
                                       solo[0]["losses"])),
        "crosshost_runtime_compiles": sum(
            r["runtime_compiles"] for r in pod),
    }
    serve_args = {"mesh": mesh, "rows": 64, "feats": 16, "requests": 24,
                  "seed": 0}
    spod = launch_pod(f"{scen}:fused_serving", num_processes=2,
                      local_devices=local_devices, args=serve_args,
                      timeout=timeout)
    ssolo = launch_pod(f"{scen}:fused_serving", num_processes=1,
                       local_devices=total, args=serve_args,
                       timeout=timeout)
    out["crosshost_serving_p99_ms"] = max(r["p99_ms"] for r in spod)
    out["crosshost_serving_bit_equal"] = bool(
        all(r["bit_equal"] for r in spod + ssolo)
        and len({r["digest"] for r in spod + ssolo}) == 1)
    cb = launch_pod(f"{scen}:collective_bytes", num_processes=2,
                    local_devices=local_devices,
                    args={"mesh": mesh, "rows": 1024}, timeout=timeout)
    out["crosshost_collective_bytes"] = sum(r["bytes"] for r in cb)
    return out


def main(n_devices: int = 8) -> None:
    """One JSON line on stdout, the last one printed."""
    print(json.dumps(run(n_devices)), flush=True)


if __name__ == "__main__":  # pragma: no cover - manual runs
    main()
