"""Named collectives over mesh axes.

These are the framework's replacement for the reference's three comm
backends (SURVEY §2.13): LightGBM's raw-TCP ring/Bruck allreduce
(``lightgbm/TrainUtils.scala:609-625``), VW's spanning-tree AllReduce
(``vw/VowpalWabbitBase.scala:434-461``), and Spark broadcast/barrier
(``LightGBMBase.scala:256-261``). Inside ``shard_map``/``pjit`` these lower
to XLA collectives that ride ICI within a slice and DCN across slices.

Observability: every collective records into the process-wide obs
registry — ``collective_calls_total{op,axis}`` and
``collective_bytes_total{op,axis}`` (per-shard payload bytes). Because
these helpers run at TRACE time, the counters measure distinct traced
call sites × retraces, not per-step executions (XLA replays the
compiled program without re-entering Python) — the right number for
"what collectives does this program issue, and how big are they".
Per-execution device time comes from the paired ``named_scope``: the
compiler carries the scope into the op's name, so in a capture taken
with ``obs.profile.profile_trace`` (device planes only) the op shows up
labeled — the TPU equivalent of wrapping a socket allreduce in a
stopwatch.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ..obs import registry as _obs
from .compat import axis_size as _axis_size

_m_calls = _obs.counter(
    "collective_calls_total",
    "collective trace-time issue count, by op/axis")
_m_bytes = _obs.counter(
    "collective_bytes_total",
    "per-shard payload bytes at collective issue, by op/axis")
# the parallel_* twin of collective_bytes_total: the partition-engine
# series family (parallel_rule_match_total / parallel_unmatched_leaves
# _total / parallel_collective_bytes_total) lives on one prefix so a
# dashboard for "what is the sharding engine doing" is one glob; the
# legacy collective_* names keep recording for existing consumers
_m_par_bytes = _obs.counter(
    "parallel_collective_bytes_total",
    "per-shard payload bytes at collective issue, by op/axis "
    "(partition-engine series; same numbers as collective_bytes_total)")


@contextlib.contextmanager
def _observed(op: str, x, axis):
    """XProf naming scope; records one collective issue on clean exit —
    a typo'd axis (or any trace error) raises out of the wrapped lax
    call and must not leave a phantom series in the registry."""
    try:
        nbytes = int(x.size) * x.dtype.itemsize
    except Exception:
        nbytes = 0
    label = axis if isinstance(axis, str) else ",".join(axis)
    try:
        scope = jax.named_scope(f"collective.{op}[{label}]")
    except Exception:  # pragma: no cover - named_scope is cosmetic
        scope = contextlib.nullcontext()
    with scope:
        yield
    # pod workers tag the series per-process (obs.profile.process_label
    # is None single-process, so existing sample names stay unchanged)
    from ..obs.profile import process_label
    pl = process_label()
    plab = {"process": pl} if pl is not None else {}
    _m_calls.inc(1, op=op, axis=label, **plab)
    _m_bytes.inc(nbytes, op=op, axis=label, **plab)
    _m_par_bytes.inc(nbytes, op=op, axis=label, **plab)


def allreduce(x, axis: str | tuple[str, ...], op: str = "sum"):
    """psum/pmax/pmin/pmean over a named mesh axis (LightGBM's histogram
    allreduce; VW's weight averaging with op="mean")."""
    fns = {"sum": jax.lax.psum, "mean": jax.lax.pmean,
           "max": jax.lax.pmax, "min": jax.lax.pmin}
    # validated BEFORE recording: a typo'd op must raise, not leave a
    # phantom collective series in the registry for the process lifetime
    if op not in fns:
        raise ValueError(f"unknown op {op!r}")
    with _observed(f"allreduce_{op}", x, axis):
        return fns[op](x, axis)


def allgather(x, axis: str, *, tiled: bool = True, gather_axis: int = 0):
    """Gather shards along a named axis (voting-parallel top-K exchange)."""
    with _observed("allgather", x, axis):
        return jax.lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def psum_scatter(x, axis: str, *, scatter_axis: int = 0):
    """reduce_scatter: each shard gets one slice of the summed tensor."""
    with _observed("psum_scatter", x, axis):
        return jax.lax.psum_scatter(x, axis,
                                    scatter_dimension=scatter_axis,
                                    tiled=True)


def ppermute(x, axis: str, perm):
    """Point-to-point shard permutation with an explicit ``(src, dst)``
    list (ring attention's rotation, the pipeline ring's activation
    hand-off). Same accounting as every other collective here — raw
    ``jax.lax.ppermute`` call sites bypass the obs byte series and are
    flagged by graftcheck's collective-audit pass."""
    with _observed("ppermute", x, axis):
        return jax.lax.ppermute(x, axis, perm)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int,
               tiled: bool = False):
    """Shard-count transpose (Ulysses' sequence↔heads exchange)."""
    with _observed("all_to_all", x, axis):
        return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=tiled)


def axis_index(axis: str):
    """This shard's coordinate along a named axis. Moves no real
    payload; recorded (like :func:`barrier`, as a scalar token) so the
    calls-total series still shows which programs ask for topology."""
    z = jnp.zeros((), jnp.int32)
    with _observed("axis_index", z, axis):
        return jax.lax.axis_index(axis)


def ring_permute(x, axis: str, shift: int = 1):
    """Rotate shards around the ring of a named axis (the building block of
    ring attention / sequence parallelism)."""
    with _observed("ring_permute", x, axis):
        n = _axis_size(axis)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return jax.lax.ppermute(x, axis, perm)


def barrier(axis: str):
    """SPMD barrier: a trivial psum forces all shards to rendezvous.

    The reference uses Spark barrier execution to keep partial stages from
    deadlocking the collective ring (``LightGBMBase.scala:106-137``); in SPMD
    every program step is already a barrier, but this is handy to delimit
    phases explicitly.
    """
    z = jnp.zeros((), jnp.int32)
    with _observed("barrier", z, axis):
        return jax.lax.psum(z, axis)
