"""The sharding layer's single call sites into JAX.

Written for the one installation there is (JAX 0.9.0): no branch for an
API generation that is not installed. Every in-repo use of
``shard_map``, the sharding constraint, executable serialization and
the Pallas TPU compiler params goes through here, so the next JAX
upgrade is edited in exactly one place.

JAX-free at module scope, like the rest of the package's light
surface.
"""

from __future__ import annotations

from ..obs import registry as _obs

# same series to_shardings demotes into: a constraint the mesh cannot
# honor replicates that dim LOUDLY, wherever the demotion happens
_m_demoted = _obs.counter(
    "parallel_spec_demoted_total",
    "matched specs demoted to fewer axes because a dim does not divide "
    "the mesh axis, by axis")

# cost_analysis() returns None, a list, or partial dicts depending on
# backend/version — every consumer goes through cost_analysis() below,
# and a backend that yields nothing usable is counted here, never
# silently treated as free
_m_cost_missing = _obs.counter(
    "profile_cost_analysis_missing_total",
    "compiled-program cost_analysis() reads that yielded nothing "
    "usable, by reason (error | empty | zero)")


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map``; ``check_vma=None`` means "library default"."""
    import jax
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def jit(fn=None, *, name: str | None = None, **jit_kwargs):
    """``jax.jit`` through the obs :class:`CompileTracker`: identical
    call semantics (decorator or call-form; ``donate_argnums`` /
    ``in_shardings`` / ... pass through), but every retrace is counted
    and every compile's wall time is recorded per function in the
    process-wide registry (``profile_compiles_total{fn=...}`` etc.) —
    the runtime counterpart of graftcheck's static recompile-hazard
    pass. Route jit call sites through here so a production server can
    answer "did anything recompile under load?" from a scrape.

    The returned callable forwards ``lower`` — the ahead-of-time path:
    ``fn.lower(*args).compile()`` plus :func:`serialize_compiled` /
    :func:`deserialize_compiled` is how the AOT executable store
    (``core/aot.py``) turns request-latency compiles into build-step
    artifacts.

    JAX-free until called (the tracker imports jax lazily), like the
    rest of this module's surface."""
    from ..obs.profile import compile_tracker
    return compile_tracker.jit(fn, name=name, **jit_kwargs)


def cost_analysis(compiled) -> dict | None:
    """Normalized XLA analytic cost for a ``jax.stages.Compiled``:
    ``{"flops": float, "bytes": float}`` or None.

    ``Compiled.cost_analysis()`` is backend- and version-dependent: it
    can raise, return None, wrap the dict in a single-element list, or
    omit keys ("bytes accessed" is the HBM-traffic key when present).
    This is THE in-repo call site shape — consumers (the AOT store,
    LLM warm paths, bench harnesses) never touch the raw API, and a
    read that yields nothing usable is counted in
    ``profile_cost_analysis_missing_total`` instead of being silently
    treated as a free program."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        _m_cost_missing.inc(1, reason="error")
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict) or not cost:
        _m_cost_missing.inc(1, reason="empty")
        return None
    try:
        flops = float(cost.get("flops", 0.0) or 0.0)
        bytes_ = float(cost.get("bytes accessed", 0.0) or 0.0)
    except (TypeError, ValueError):
        _m_cost_missing.inc(1, reason="empty")
        return None
    if flops <= 0.0 and bytes_ <= 0.0:
        _m_cost_missing.inc(1, reason="zero")
        return None
    return {"flops": flops, "bytes": bytes_}


def serialize_compiled(compiled) -> bytes:
    """``jax.stages.Compiled`` → one self-contained blob (payload +
    pytree defs pickled together). A backend that refuses raises — the
    AOT store catches it and writes a retrace-tier entry instead."""
    import pickle

    from jax.experimental.serialize_executable import serialize
    payload, in_tree, out_tree = serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree),
                        protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_compiled(blob: bytes, backend=None):
    """Inverse of :func:`serialize_compiled`: blob → a loaded
    ``jax.stages.Compiled`` bound to ``backend`` (default: the
    process's default backend)."""
    import pickle

    from jax.experimental.serialize_executable import deserialize_and_load
    payload, in_tree, out_tree = pickle.loads(blob)
    return deserialize_and_load(payload, in_tree, out_tree,
                                backend=backend)


def tpu_compiler_params(**kwargs):
    """Pallas TPU compiler params (``pltpu.CompilerParams``)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def _context_mesh():
    """The physical mesh an enclosing ``with mesh:`` bound to this
    thread, or None."""
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m.empty else m


def with_sharding_constraint(x, spec, mesh=None):
    """The one call site of ``jax.lax.with_sharding_constraint``.
    graftcheck's collective-audit flags raw constraint call sites
    outside ``parallel/``, so this is THE way model and train-step code
    annotates activations.

    ``spec``: a ``NamedSharding`` (applied as-is), or a
    ``PartitionSpec`` / tuple of axis entries resolved against
    ``mesh``, falling back to the thread's context mesh (an enclosing
    ``with mesh:`` — the partitioned train steps enter it around their
    body so model-internal block-boundary constraints resolve). With no
    mesh anywhere the constraint is meaningless and ``x`` returns
    unchanged — model code runs un-annotated on a single device without
    carrying mesh plumbing.

    Entries the mesh cannot honor (axis absent, or the dim not
    divisible by the axis size) demote to ``None`` per-dim, counted in
    ``parallel_spec_demoted_total{axis=...}`` — the ``to_shardings``
    contract applied to activations, so a batch of 2 under a dp=8 mesh
    replicates loudly instead of failing the compile."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    fn = jax.lax.with_sharding_constraint
    if isinstance(spec, NamedSharding):
        return fn(x, spec)
    if mesh is None:
        mesh = _context_mesh()
        if mesh is None:
            return x
    entries = list(tuple(spec))
    shape = getattr(x, "shape", ())
    if len(entries) > len(shape):
        raise ValueError(
            f"constraint spec {tuple(spec)} has more entries than the "
            f"value has dims (shape {tuple(shape)})")
    for i, entry in enumerate(entries):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in axes:
            size *= mesh.shape.get(a, 0)
        if size == 0 or shape[i] % size:
            _m_demoted.inc(1, axis=",".join(axes))
            entries[i] = None
    return fn(x, NamedSharding(mesh, P(*entries)))


def make_array_from_process_local_data(sharding, local_data):
    """Per-host feeding: each process hands its LOCAL rows and gets
    back one global array sharded per ``sharding``
    (``jax.make_array_from_process_local_data``)."""
    import jax
    return jax.make_array_from_process_local_data(sharding, local_data)


def process_allgather(x, *, tiled: bool = False):
    """Global array → full host numpy value on EVERY process — the
    read-side twin of :func:`make_array_from_process_local_data`, and
    the loud-error escape hatch ``gather_params`` points at when a leaf
    spans processes. Single-process arrays take the plain
    ``device_get`` path (no collective, no coordination service)."""
    import jax
    import numpy as np
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(jax.device_get(x))
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=tiled))


def enable_cpu_multiprocess_collectives() -> None:
    """Switch the CPU backend's collectives to the gloo implementation
    — REQUIRED before ``jax.distributed.initialize`` on a multi-process
    CPU (DCN-style) run: without it initialization succeeds but the
    first cross-process execution fails with "Multiprocess computations
    aren't implemented on the CPU backend"."""
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def axis_size(axis) -> int:
    """STATIC size of a named mesh axis from inside shard_map/pjit
    (``jax.lax.axis_size``): a real int at trace time — ring
    permutation tables and loop bounds need it concrete."""
    import jax
    return jax.lax.axis_size(axis)
