"""Pod-scale SPMD harness: N processes over DCN, one global mesh.

Everything below ``distributed_init`` in this package was built
single-process; this module is the data plane that makes the mesh span
hosts. It has two halves:

- the LAUNCHER (:func:`launch_pod`): spawn N worker processes on this
  machine — a loopback CPU pod for tests that never takes the chip
  (so a parent that holds it can still launch one), each worker pinned
  to the CPU platform with a fixed count
  of virtual local devices, gloo CPU collectives enabled, and the
  ``MMLSPARK_TPU_COORDINATOR``/``NUM_PROCESSES``/``PROCESS_ID`` env
  triple set so :func:`~.mesh.distributed_init` wires the coordination
  service. This is the DCN-style test/bench topology: process
  boundaries are real (separate runtimes, cross-process collectives
  over gloo), only the wire is loopback. On a real pod the same worker
  body runs under the cluster launcher and the coordinator address is
  a real host:port.

- the WORKER surface (:func:`pod_mesh`, :func:`feed_process_local`,
  :func:`this_process`): build the dcn×ici global mesh and feed it
  per-host rows. The mesh convention: the OUTER axis spans processes
  (slow DCN hops — data parallelism lives here, gradients cross hosts
  once per step) and the INNER axis spans each process's local devices
  (fast ICI — tensor parallelism's per-matmul collectives stay
  on-host). Axes keep the framework-wide ``dp``/``tp`` NAMES so every
  registered partition rule applies unchanged; the dcn/ici split is
  the device LAYOUT under those names.

JAX-free at import (CI smoke-checks this) like the rest of the
package's light surface: the launcher is subprocess plumbing, and the
worker helpers import jax inside the call.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

RESULT_MARK = "MULTIHOST_RESULT "

DCN_AXIS = "dp"   # outer mesh axis: spans processes (DCN)
ICI_AXIS = "tp"   # inner mesh axis: spans local devices (ICI)


def free_port() -> int:
    """An OS-assigned free TCP port for the coordinator (the usual
    bind-to-0 race: good enough for a single-machine pod, where the
    window between close and the coordinator's bind is microseconds)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker_env(process_id: int, num_processes: int, coordinator: str,
               local_devices: int, extra_path: str | None = None) -> dict:
    """One pod worker's environment: the CPU pin + virtual device count
    from ``core.utils.cpu_child_env`` (a loopback pod is a CPU
    rehearsal: its workers never take the chip, which belongs to one
    process at a time), plus the coordination triple ``distributed_init`` reads
    and the gloo CPU-collectives switch (belt to the config-level
    braces in ``compat.enable_cpu_multiprocess_collectives`` — either
    alone suffices, both together survive config-API drift)."""
    from ..core.utils import cpu_child_env
    env = cpu_child_env(local_devices, extra_path)
    env["MMLSPARK_TPU_COORDINATOR"] = coordinator
    env["MMLSPARK_TPU_NUM_PROCESSES"] = str(num_processes)
    env["MMLSPARK_TPU_PROCESS_ID"] = str(process_id)
    env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
    # The persistent XLA compile cache is poison on a multi-process CPU
    # pod: a worker that HITS the cache and deserializes an executable
    # whose program embeds gloo collectives segfaults at boot (observed
    # deterministically: rank 0 SIGSEGV on every cache-hit run of a
    # program a previous pod compiled; cold compiles of the same
    # program always pass). Workers always compile fresh — the AOT
    # store (core/aot.py), not the jax cache, is the sanctioned warm
    # path on a pod.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def launch_pod(target: str, *, num_processes: int = 2,
               local_devices: int = 4, args: dict | None = None,
               timeout: float = 300.0,
               extra_path: str | None = None) -> list[dict]:
    """Run ``target`` (a ``"pkg.module:function"`` dotted path) in
    ``num_processes`` CPU-pinned workers over a loopback coordinator.

    Each worker boots jax, calls ``distributed_init`` (env-driven),
    invokes the target with ``args`` (one JSON-serializable dict), and
    prints its returned dict on a ``MULTIHOST_RESULT`` line; the
    launcher collects them rank-ordered. Any worker failing (or the
    pod exceeding ``timeout`` — everything is killed, no orphan
    coordinator) raises RuntimeError carrying every worker's log tail,
    so a wedged collective reports a cause instead of hanging CI.
    """
    if ":" not in target:
        raise ValueError(
            f"target must be 'module:function', got {target!r}")
    coordinator = f"127.0.0.1:{free_port()}"
    payload = json.dumps(args or {})
    procs: list[subprocess.Popen] = []
    deadline = time.monotonic() + timeout
    try:
        for rank in range(num_processes):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "mmlspark_tpu.parallel.multihost",
                 target, payload],
                env=worker_env(rank, num_processes, coordinator,
                               local_devices, extra_path),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs: list[str] = []
        for proc in procs:
            left = deadline - time.monotonic()
            try:
                out, _ = proc.communicate(timeout=max(left, 0.1))
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                out, _ = proc.communicate()
                raise RuntimeError(
                    f"multihost pod timed out after {timeout:.0f}s; "
                    f"rank {len(outs)} tail:\n{out[-2000:]}")
            outs.append(out or "")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results: list[dict] = []
    for rank, (proc, out) in enumerate(zip(procs, outs)):
        parsed = None
        for line in reversed(out.splitlines()):
            if line.startswith(RESULT_MARK):
                parsed = json.loads(line[len(RESULT_MARK):])
                break
        if proc.returncode != 0 or parsed is None:
            tails = "\n".join(
                f"--- rank {r} (rc={p.returncode}) ---\n{o[-2000:]}"
                for r, (p, o) in enumerate(zip(procs, outs)))
            raise RuntimeError(
                f"multihost worker rank {rank} failed "
                f"(rc={proc.returncode}, "
                f"result={'present' if parsed else 'missing'}):\n{tails}")
        results.append(parsed)
    return results


# ------------------------------------------------------ worker surface

def this_process() -> tuple[int, int]:
    """(process_index, process_count) of the live runtime."""
    import jax
    return int(jax.process_index()), int(jax.process_count())


def pod_mesh(data_axis: str = DCN_AXIS, model_axis: str = ICI_AXIS,
             devices=None):
    """The dcn×ici global mesh: ``(process_count, local_device_count)``
    with the OUTER axis walking processes (DCN) and the INNER axis
    walking each process's devices (ICI). Devices sort process-major
    explicitly rather than trusting enumeration order — the outer axis
    spanning DCN is the whole point, and a device order that
    interleaved processes would silently put per-matmul tp collectives
    on the slow links."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    devices = list(jax.devices() if devices is None else devices)
    devices.sort(key=lambda d: (getattr(d, "process_index", 0), d.id))
    nproc = len({getattr(d, "process_index", 0) for d in devices})
    if len(devices) % nproc:
        raise ValueError(
            f"{len(devices)} devices over {nproc} processes is ragged "
            "— every pod worker must contribute the same device count")
    arr = np.asarray(devices).reshape(nproc, len(devices) // nproc)
    return Mesh(arr, (data_axis, model_axis))


def feed_process_local(mesh, local_rows, axis: str = DCN_AXIS):
    """This process's rows → one global array batch-sharded over
    ``axis``. Every process calls this with ITS shard of the global
    batch (rank-ordered: global row ``i`` lives on the process whose
    slice covers it); the result is what the pjit'd train step and the
    dp-sharded fused serving segment take as input. Thin sugar over
    ``compat.make_array_from_process_local_data`` with the pod's
    batch-over-DCN convention baked in."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from .compat import make_array_from_process_local_data
    return make_array_from_process_local_data(
        NamedSharding(mesh, P(axis)), local_rows)


def fleet_result(extra: dict | None = None) -> dict:
    """The standard MULTIHOST_RESULT fleet envelope: this rank's index
    plus its prefix-filtered registry snapshot (and device-memory
    stats when the backend reports them), ready for
    ``obs.fleet.ingest_pod_results`` on the launcher side — the push
    half of pod-scale metric federation rides the result channel the
    harness already has."""
    from ..obs.fleet import local_fleet_snapshot
    from ..obs.memory import memory_profiler
    memory_profiler.update()      # mem_hbm_* into the snapshot, if any
    idx, _ = this_process()
    out = {"process": idx, "snapshot": local_fleet_snapshot()}
    if extra:
        out.update(extra)
    return out


def _worker_main(argv: list[str]) -> int:
    """``python -m mmlspark_tpu.parallel.multihost module:fn json`` —
    the body every :func:`launch_pod` worker runs."""
    target, payload = argv[0], json.loads(argv[1] if len(argv) > 1
                                          else "{}")
    mod_name, fn_name = target.split(":", 1)
    from .compat import enable_cpu_multiprocess_collectives
    if (os.environ.get("JAX_PLATFORMS") or "").startswith("cpu"):
        enable_cpu_multiprocess_collectives()
    from .mesh import distributed_init
    distributed_init()
    import importlib
    fn = getattr(importlib.import_module(mod_name), fn_name)
    out = fn(payload) or {}
    print(RESULT_MARK + json.dumps(out), flush=True)
    import jax
    jax.distributed.shutdown()
    return 0


if __name__ == "__main__":  # pragma: no cover - subprocess entry
    sys.exit(_worker_main(sys.argv[1:]))
