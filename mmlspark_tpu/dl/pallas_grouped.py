"""Pallas TPU kernels: the grouped expert products of a dropless expert layer.

``models.moe.dropless_moe`` sorts a layer's token-expert pairs by expert and
hands the rows ``xs`` [M, D] and each held expert's count of pairs
``sizes`` [n] to :func:`grouped_expert_products`, which returns every
pair's ``silu(x W_gate) * (x W_up) @ W_down`` [M, D] float32. Two kernels
compute it, each over ONE schedule of live (expert, row tile) visits:

    gate·up: a visit reads its row tile of ``xs`` ``[tm, D]`` and the
      expert's ``[D, tn]`` blocks of ``w_gate`` and ``w_up`` — the two
      arrays as they lie, in the same pass, never concatenated — and
      writes ``silu(x W_gate) * (x W_up)`` as bfloat16 rows ``[tm, tn]``:
      the two float32 products meet in fast memory, not in HBM;
    down: a visit reads its tile of those rows ``[tm, F]`` and the
      expert's ``[F, tn]`` block of ``w_down`` and writes ``[tm, tn]``
      float32.

The schedule (:func:`tile_schedule`, built on the device from ``sizes``)
walks the experts in order and, for each expert with pairs, the row tiles
its pairs touch, megablox-style: a tile that straddles two experts is
visited by both, each writing its own rows (the other rows of a tile read
zero). The visits ride scalar prefetch and their count is the grid's
dynamic bound, as the paged kernels' live cells do
(``pallas_paged_attention.live_schedule``); an expert with no pair has no
visit and is neither fetched nor computed.

The weights stay in HBM and are copied by hand into one of two buffers:
an expert's blocks ONCE for all its visits, and the NEXT expert's copy is
started at the first visit of the one before it, so that it runs under
every visit of that expert (Pallas's own pipeline fetches a block one grid
step ahead: under one visit only; on a v5e at the short-chat cell's
decode step that was 1.10 ms a layer against 1.00). The contraction axis
is one block (the whole ``D`` or ``F``); the output axis is cut into
``tn``-wide blocks only where a whole block would not fit fast memory, and
the grid walks every visit for one output block before the next
(``[n_out, n_visits]``), so an expert's weights are still read once a call.

The row tile ``tm`` follows the pairs one expert is expected to hold,
``T * k / experts`` (:func:`row_tile`). The rows a call computes —
padding included — are returned beside the products; ``held pairs / rows
computed`` is the tiles' fill.

Both kernels carry a name with ``ragged-dot`` in it (``ragged-dot-gate-up``,
``ragged-dot-down``): the name XLA gives the ``jax.lax.ragged_dot`` passes
they replace, under which a device trace's expert products have always
been found. Off-TPU the same call runs those three ``ragged_dot`` passes
(``impl="lax"``); the platform switch is
``pallas_paged_attention.paged_window_attention``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.compat import tpu_compiler_params as _CompilerParams
from ..utils.platform import target_platform

#: the kernels' names in a device trace
GATE_UP_KERNEL_NAME = "ragged-dot-gate-up"
DOWN_KERNEL_NAME = "ragged-dot-down"

#: row tiles, rows: below 32 a thick group's tiles left the MXU idle, above
#: 128 a thin group's padding did not pay (a v5e, the three expert cells'
#: shapes: ``row_tile``)
_MIN_ROW_TILE = 32
_MAX_ROW_TILE = 128
#: fast memory one weight block may take: the gate·up kernel holds two
#: (gate and up), each in two buffers
_WEIGHT_BLOCK_BYTES = 8 << 20
_VMEM_LIMIT = 64 << 20


# ----------------------------------------------------------------- lax path
def _grouped_reference(xs, sizes, w_gate, w_up, w_down):
    """The three grouped products as ``jax.lax.ragged_dot`` passes: rows
    only where an expert has pairs, float32 accumulation, ``h`` rounded to
    the rows' type before the down product."""
    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
    return grouped(h.astype(xs.dtype), w_down)


# ------------------------------------------------------------- the schedule
def row_tile(pairs: int, experts: int) -> int:
    """Rows of a tile for a call of ``pairs`` token-expert pairs over a
    layer of ``experts`` experts (held or not): the power of two at or
    above twice the pairs one expert is expected to hold, within ``[32,
    128]``. Static: a program's shapes alone choose it. On a v5e this
    picks, at each of the three expert cells' decode step and riding
    window, a tile within 0.7 points of roofline of the best of 16 … 256
    (``PERF.md`` §6, the kernels alone)."""
    expected = 2 * int(pairs) / max(int(experts), 1)
    tm = _MIN_ROW_TILE
    while tm < expected and tm < _MAX_ROW_TILE:
        tm *= 2
    return tm


def out_tile(width_in: int, width_out: int, itemsize: int) -> int:
    """Columns of an output block: the whole ``width_out`` where a weight
    block ``[width_in, width_out]`` fits ``_WEIGHT_BLOCK_BYTES``, else the
    widest part of it, a multiple of 128 lanes that divides it, that
    does."""
    for parts in range(1, int(width_out) + 1):
        tn = int(width_out) // parts
        if int(width_out) % parts or (parts > 1 and tn % 128):
            continue
        if int(width_in) * tn * int(itemsize) <= _WEIGHT_BLOCK_BYTES:
            return tn
    return 128 if int(width_out) % 128 == 0 else int(width_out)


def tile_schedule(sizes, tm: int, rows: int) -> tuple:
    """The (expert, row tile) visits of a call, built on the device from
    ``sizes`` [n] (pairs a held expert; its rows follow the ones before
    it). Returns ``(n_visits, experts, tiles, offsets, lead, rank, nxt,
    live)``: ``n_visits`` the grid's dynamic bound, at least 1 (with no
    pair at all, one visit of tile 0 that writes zeros); ``experts`` and
    ``tiles`` [ceil(rows / tm) + n - 1] int32, expert-major with its tiles
    in order, the first ``n_visits`` real and every later one the last
    real visit again; ``offsets`` [n + 1] each expert's first row and,
    last, the end of the held rows; and what the weights' copies need:
    ``lead`` 1 at an expert's first visit, ``rank`` the visit's expert's
    rank among the experts with pairs (its buffer is the rank's parity),
    ``nxt`` the next expert with pairs (-1: none), ``live`` [1] the count
    of experts with pairs (at least 1)."""
    n = sizes.shape[0]
    V = -(-int(rows) // tm) + n - 1
    with jax.named_scope("expert_schedule"):
        sizes = sizes.astype(jnp.int32)
        ends = jnp.cumsum(sizes)
        first = (ends - sizes) // tm
        count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
        vends = jnp.cumsum(count)
        n_visits = jnp.maximum(vends[-1], 1)
        # visit i belongs to the first expert whose visits end past it; by
        # comparisons alone, as ``live_schedule`` does
        i = jnp.minimum(jnp.arange(V, dtype=jnp.int32), n_visits - 1)
        before = vends[None, :] <= i[:, None]               # [V, n]
        g = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), n - 1)
        own = jnp.arange(n, dtype=jnp.int32)[None, :] == g[:, None]

        def pick(per_expert):               # [n] -> [V], at each visit's
            return jnp.sum(jnp.where(own, per_expert, 0), axis=1,
                           dtype=jnp.int32)

        vstart = pick(vends - count)
        live = count > 0
        ids = jnp.arange(n, dtype=jnp.int32)
        later = jnp.where((ids[None, :] > ids[:, None]) & live[None, :],
                          ids[None, :], n)                  # [n, n]
        nxt = jnp.min(later, axis=1)
        return (n_visits, g, pick(first) + i - vstart,
                jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
                ((i == vstart) & (jnp.arange(V) < n_visits)).astype(
                    jnp.int32),
                pick(jnp.maximum(jnp.cumsum(live, dtype=jnp.int32) - 1, 0)),
                pick(jnp.where(nxt == n, -1, nxt)),
                jnp.maximum(jnp.sum(live, dtype=jnp.int32), 1)[None])


# ------------------------------------------------------------ pallas path
def _gate_up(x, w_gate, w_up):
    a = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    b = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return a * jax.nn.sigmoid(a) * b


def _down(h, w_down):
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32)


def _visit_kernel(e_ref, t_ref, off_ref, lead_ref, rank_ref, nxt_ref,
                  live_ref, a_ref, *refs, body, n_w: int, tm: int, tn: int,
                  split: bool):
    """One visit of the schedule: ``body(a tile, *the expert's weight
    blocks)`` stored as the rows of the visit's expert. The ``n_w`` weight
    arrays are HBM refs; ``wbuf`` [2, n_w, K, tn] holds an expert's blocks
    in the buffer of its rank's parity (per output block ``j``: ranks run
    on across blocks), each filled by one copy that the expert before it
    started at its first visit, and waited for at this expert's first."""
    w_hbm = refs[:n_w]
    o_ref, wbuf, sem = refs[n_w:]
    j, i = pl.program_id(0), pl.program_id(1)
    slot = (j * live_ref[0] + rank_ref[i]) % 2

    def copies(g, jj, s):
        return [pltpu.make_async_copy(
            (w.at[g, :, pl.ds(pl.multiple_of(jj * tn, 128), tn)] if split
             else w.at[g]), wbuf.at[s, m], sem.at[s, m])
            for m, w in enumerate(w_hbm)]

    @pl.when((j == 0) & (i == 0))
    def _first():
        for c in copies(e_ref[0], 0, 0):
            c.start()

    @pl.when(lead_ref[i] == 1)
    def _lead():
        nxt = nxt_ref[i]

        @pl.when(nxt >= 0)
        def _next_expert():
            for c in copies(nxt, j, 1 - slot):
                c.start()

        # the last expert of an output block: the next block's first
        @pl.when((nxt < 0) & (j + 1 < pl.num_programs(0)))
        def _next_block():
            for c in copies(e_ref[0], j + 1, 1 - slot):
                c.start()

        for c in copies(e_ref[i], j, slot):
            c.wait()

    value = body(a_ref[...], *(wbuf[slot, m] for m in range(n_w)))
    # the rows of this visit's expert; a tile's first visit writes zeros
    # over the others, a later visit of it (a straddle) keeps them
    t = t_ref[i]
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, value.shape, 0)
    mine = (row >= off_ref[e_ref[i]]) & (row < off_ref[e_ref[i] + 1])
    fresh = (i == 0) | (t != t_ref[jnp.maximum(i - 1, 0)])
    value = value.astype(o_ref.dtype)

    @pl.when(fresh)
    def _fresh():
        o_ref[...] = jnp.where(mine, value, jnp.zeros_like(value))

    @pl.when(jnp.logical_not(fresh))
    def _straddle():
        o_ref[...] = jnp.where(mine, value, o_ref[...])


def _grouped_call(body, a, weights, schedule, *, tm: int, out_dtype,
                  name: str, interpret: bool):
    """One grouped product over the schedule's visits: ``a`` [rows, K]
    tiled by rows, each of ``weights`` [n, K, N] read a ``[K, tn]`` block
    of the visit's expert; out ``[rows, N]``."""
    n_visits, scalars = schedule[0], schedule[1:]
    rows, K = a.shape
    N = weights[0].shape[2]
    tn = out_tile(K, N, weights[0].dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_visit_kernel, body=body, n_w=len(weights), tm=tm,
                          tn=tn, split=tn != N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(N // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, K), lambda j, i, e, t, *_:
                                   (t[i], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in weights],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, e, t, *_:
                                   (t[i], j)),
            scratch_shapes=[
                pltpu.VMEM((2, len(weights), K, tn), weights[0].dtype),
                pltpu.SemaphoreType.DMA((2, len(weights)))]),
        out_shape=jax.ShapeDtypeStruct((rows, N), out_dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(*scalars, a, *weights)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped_pallas(xs, sizes, w_gate, w_up, w_down, *, tm: int,
                    interpret: bool):
    M = xs.shape[0]
    rows = -(-M // tm) * tm
    xs = jnp.pad(xs, ((0, rows - M), (0, 0)))
    schedule = tile_schedule(sizes, tm, rows)
    h = _grouped_call(_gate_up, xs, (w_gate, w_up), schedule, tm=tm,
                      out_dtype=xs.dtype, name=GATE_UP_KERNEL_NAME,
                      interpret=interpret)
    ys = _grouped_call(_down, h, (w_down,), schedule, tm=tm,
                       out_dtype=jnp.float32, name=DOWN_KERNEL_NAME,
                       interpret=interpret)
    return ys[:M], schedule[0] * tm


# --------------------------------------------------------------- public
def grouped_expert_products(xs, sizes, w_gate, w_up, w_down, *,
                            num_experts: int | None = None,
                            impl: str | None = None,
                            interpret: bool | None = None):
    """Every pair's gated-SiLU expert: ``xs`` [M, D] rows sorted by expert,
    ``sizes`` [n] int32 the pairs of each held expert (rows past their sum
    belong to no expert), ``w_gate``/``w_up`` [n, D, F] and ``w_down`` [n,
    F, D]. Returns ``(ys [M, D] float32, rows computed int32)``: ``ys``
    holds ``silu(x W_gate) * (x W_up)``, rounded to ``xs``'s type, times
    ``W_down`` on the held rows (the rows past them hold zeros or whatever
    the lax path leaves there); the rows computed are the kernels' tile
    rows, padding included (the held pairs on the lax path).

    ``num_experts``: the layer's experts, held or not (None: ``n``), which
    with ``M`` sizes the row tile (:func:`row_tile`). ``impl``: "pallas" |
    "lax" | None (platform switch — TPU backends run the kernels,
    everything else the ``ragged_dot`` passes). ``interpret`` forces the
    Pallas interpreter (tests)."""
    plat = target_platform()
    if impl is None:
        impl = "pallas" if plat == "tpu" else "lax"
    sizes = jnp.asarray(sizes, jnp.int32)
    if impl == "lax":
        return (_grouped_reference(xs, sizes, w_gate, w_up, w_down),
                jnp.sum(sizes))
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} is not one of pallas|lax")
    if interpret is None:
        interpret = plat != "tpu"
    tm = row_tile(xs.shape[0], int(num_experts or sizes.shape[0]))
    return _grouped_pallas(xs, sizes, w_gate, w_up, w_down, tm=tm,
                           interpret=bool(interpret))
