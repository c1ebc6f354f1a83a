"""Pallas TPU kernel: paged decode attention over the block table.

The LLM serving engine's hot op: attention for a window of query rows
per slot over that slot's chain of KV blocks, read where they rest. The
KV working set is resident once and a step's HBM traffic is the chains'
own blocks: no dense copy of a slot's history is made, before the step
or inside it. The kernel reads the fixed per-layer pools
``[num_blocks, block_len, heads*head_dim]`` IN PLACE — a token's heads
side by side, so that in a block positions lie on sublanes and head
``h`` is lanes ``h*hd … (h+1)*hd``: a head's keys are a lane-aligned
slice of the block, loaded as they lie, and no cell shuffles a sublane:

    grid = (slots/slots_tile, slots_tile, max_blocks), blocks innermost
    the int32 block table and the positions ride scalar prefetch (SMEM),
    so each grid cell's BlockSpec index_map streams pool block
    ``rows[s, min(j, last live entry)]`` straight HBM→VMEM — the gather
    IS the block fetch, no dense copy; past the chain's end the index
    repeats, and the pipeline copies nothing (``chain_block``)
    per (slot, chain-position) cell, by ``heads * w``:
      up to ``_BATCHED_ROWS`` rows (a decode row of 16 heads): the
        slot's query laid block-diagonally ``[heads*w, heads*hd]`` (built
        once a slot), ONE q·kᵀ for every head, one online max/denominator
        update over the ``[heads*w, block_kv]`` score tile, ONE
        weights @ v whose diagonal blocks are the heads' outputs
      above it (prefill, a wide verify window): per-head q·kᵀ over the
        head's lane slice, the same update on the head's ``w`` rows,
        acc += softmax-weights @ v
    emit acc / l once per slot on the last chain block.

Masking: table rows pad with ``TRASH_BLOCK`` — those cells, and cells
past the window's last position, are skipped outright (``pl.when``),
and in-block key positions mask against each
query row's global position (``t <= pos + i``), which also covers
positions ≥ the slot's length inside the tail block. A windowed variant
(q = k+1 rows per slot) serves speculative verify with the same kernel.

Off-TPU the SAME call runs a pure-``lax`` reference (``jnp.take`` over
the table inside the jit — no pool-level dense gather round-trip, no
writeback) whose formulation matches ``EncoderBlock.decode_window`` /
``_dense_attention`` bit-for-bit, so CPU tier-1 asserts byte-identical
greedy serving through identical program logic. The platform switch is
the same one ``pallas_attention.flash_attention`` uses.

Tile tuning: ``block_kv`` (key positions per inner VMEM chunk — the
score-block width, same VMEM discipline as ``_resolve_block_k``) and
``slots_tile`` (slots packed per parallel grid row — launch geometry
for tiny per-slot decode work) default to the ``perf.autotune`` winner
for this (context-bucket, platform) when one is registered, keyed
``kernel="paged_attn"``; explicit values always win, and every config
computes identical results (tuning moves time, never tokens).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.compat import tpu_compiler_params as _CompilerParams
from ..utils.platform import target_platform
from .paged_kv import TRASH_BLOCK

_NEG = -1e30  # additive mask value; -inf breaks the running-max algebra


# --------------------------------------------------------------- lax path
@jax.jit
def _paged_reference(q, k_pool, v_pool, rows, pos):
    """Pure-lax paged attention: ``jnp.take`` each slot's chained
    blocks THROUGH the table inside the jit (fused by XLA — no
    materialized dense cache crossing a program boundary, no
    writeback), then the exact ``decode_window`` score formulation:
    f32 einsum × hd^-0.5, -inf outside ``t <= pos + i``, softmax,
    NaN→0 for fully-masked rows, ``p.astype(v.dtype)`` before the
    value einsum. Bit-identical to the dense-cache decode math — the
    byte-identity contract with ``dl.generate`` rides on it. The pools
    rest ``[num_blocks, block_len, kv_heads*hd]``; the gathered keys are
    viewed ``[S, L, kv_heads, hd]``, and with fewer key heads than query
    heads each key head is repeated for the query heads it serves."""
    S, H, w, hd = q.shape
    NB, BL = k_pool.shape[0], k_pool.shape[1]
    G = k_pool.shape[2] // hd
    MB = rows.shape[1]
    L = MB * BL
    idx = (rows[:, :, None] * BL
           + jnp.arange(BL)[None, None, :]).reshape(S, L)
    k = jnp.take(k_pool.reshape(NB * BL, G, hd), idx, axis=0)
    v = jnp.take(v_pool.reshape(NB * BL, G, hd), idx, axis=0)
    k = jnp.transpose(k, (0, 2, 1, 3))                  # [S, G, L, hd]
    v = jnp.transpose(v, (0, 2, 1, 3))
    if G != H:
        k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    allowed = (jnp.arange(L)[None, None, :]
               <= (pos[:, None] + jnp.arange(w)[None, :])[:, :, None])
    s = jnp.where(allowed[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ------------------------------------------------------------ pallas path
#: query rows (``heads * w``) one grid cell may hold as ONE score tile:
#: a bfloat16 tile's sublanes, two float32 registers of scores a chunk.
#: Up to here every head of a slot shares one pair of products and one
#: softmax update (a decode row of 16 heads fills it); above it a head's
#: own ``w`` rows fill the tiles and the heads are looped.
_BATCHED_ROWS = 16


def _last_entry(pos, w: int, block_len: int):
    """The table entry that holds a window's last position ``pos + w -
    1``: no row of the window sees a block past it."""
    return jnp.maximum(pos + (w - 1), 0) // block_len


def chain_block(rows, pos, s, j, *, w: int, block_len: int):
    """The pool block grid cell ``(s, j)`` is handed: table entry ``j``
    of slot ``s`` up to the window's last entry, and that entry again
    for every ``j`` past it, so that the pipeline, which copies only
    when the index changes, fetches nothing for a cell past the chain's
    end. ``rows`` [S, max_blocks] and ``pos`` [S, 1] are the
    scalar-prefetched table and positions."""
    return rows[s, jnp.minimum(j, _last_entry(pos[s, 0], w, block_len))]


def _online_softmax(s, allowed, m_scr, l_scr, R: int):
    """One flash-style update of the running max and denominator of rows
    ``[:R]`` with a chunk's float32 scores ``s``; returns the chunk's
    weights and the factor the accumulator is rescaled by."""
    s = jnp.where(allowed, s, _NEG)
    m_prev = m_scr[:R, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[:R, :1] = l_scr[:R, :1] * corr \
        + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:R, :1] = m_new
    return p, corr


def _paged_kernel(rows_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *qbd_scr, scale: float,
                  heads: int, w: int, block_len: int, block_kv: int,
                  slots_tile: int, group: int = 1):
    """One (slot-group, slot, chain-block) grid cell. The k/v refs
    already hold pool block ``chain_block(s, j)`` as ``[block_len,
    kv_heads*hd]``: positions on sublanes, key head ``h`` in lanes ``h*hd
    …``, so a head's keys are a lane-aligned slice of the ref, loaded as
    they lie. ``group`` = ``heads / kv_heads`` query heads share a key
    head (1: every query head has its own): the ``group * w`` query rows
    of a key head, rows ``kh*group*w …`` of the slot's ``[heads*w, hd]``
    queries, are the rows of ONE product against that head's lane slice.
    ``qbd_scr`` is there in the batched form (``heads * w <=
    _BATCHED_ROWS``): the slot's query rows laid block-diagonally,
    ``[heads*w, kv_heads*hd]`` with row ``h*w + i`` holding ``q[h, i]`` in
    the lanes of head ``h``'s key head and zeros elsewhere, so that ONE
    product scores every head against the block and ONE weights the
    values."""
    g = pl.program_id(0)
    u = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    s_idx = g * slots_tile + u
    R = heads * w
    hd = q_ref.shape[2]
    kv_heads = heads // group

    def key_head(own):
        """The key head of query head ``own``."""
        return own if group == 1 else own // group

    def row_of(shape):
        """Row ``h*w + i`` of the batched form, as (head, window row)."""
        r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return (r, 0) if w == 1 else (r // w, r % w)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        if qbd_scr:
            q = q_ref[0]                                # [R, hd]
            own = key_head(row_of(q.shape)[0])
            for h in range(kv_heads):
                qbd_scr[0][:, h * hd:(h + 1) * hd] = jnp.where(
                    own == h, q, jnp.zeros_like(q))

    block_id = rows_ref[s_idx, j]
    pos = pos_ref[s_idx, 0]

    def batched(lo, hi, allowed):
        s = jax.lax.dot_general(           # [R, cw] f32: every head at once
            qbd_scr[0][...], k_ref[0, lo:hi, :],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p, corr = _online_softmax(s, allowed, m_scr, l_scr, R)
        pv = jax.lax.dot_general(          # [R, heads*hd] f32
            p.astype(v_ref.dtype), v_ref[0, lo:hi, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        # a row's own key head is the diagonal block of the product
        own = key_head(row_of((R, hd))[0])
        acc_scr[:R, :] = acc_scr[:R, :] * corr + sum(
            jnp.where(own == h, pv[:, h * hd:(h + 1) * hd], 0.0)
            for h in range(kv_heads))

    def by_head(lo, hi, allowed):
        gw = group * w                     # a key head's query rows
        for h in range(kv_heads):
            rows_h = slice(h * gw, (h + 1) * gw)
            lanes_h = slice(h * hd, (h + 1) * hd)
            s = jax.lax.dot_general(       # [gw, cw] f32 on the MXU
                q_ref[0, rows_h, :], k_ref[0, lo:hi, lanes_h],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p, corr = _online_softmax(
                s, allowed, m_scr.at[rows_h], l_scr.at[rows_h], gw)
            acc_scr[rows_h, :] = acc_scr[rows_h, :] * corr \
                + jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, lo:hi, lanes_h],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    # the trash block pads a chain; a block past the window's last
    # position holds nothing any row may see (and was not fetched)
    @pl.when((block_id != TRASH_BLOCK)
             & (j <= _last_entry(pos, w, block_len)))
    def _compute():
        for c in range(-(-block_len // block_kv)):
            lo = c * block_kv
            hi = min(block_len, lo + block_kv)
            # one score tile: every head's rows, or one key head's
            tile = (R if qbd_scr else group * w, hi - lo)
            # chain-logical key positions of this chunk vs each query
            # row's global position: covers causality AND length (the
            # tail block's unwritten positions are > pos + i)
            tpos = j * block_len + lo + jax.lax.broadcasted_iota(
                jnp.int32, tile, 1)
            i = row_of(tile)[1] if qbd_scr else \
                jax.lax.broadcasted_iota(jnp.int32, tile, 0)
            if group > 1 and not qbd_scr:
                i = i % w                  # row r*w + i of the key head
            (batched if qbd_scr else by_head)(lo, hi, tpos <= pos + i)

    @pl.when(j == nj - 1)
    def _emit():
        l = jnp.maximum(l_scr[:R, :1], 1e-35)
        o_ref[0] = (acc_scr[:R] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "slots_tile",
                                             "interpret"))
def _paged_pallas(q, k_pool, v_pool, rows, pos, *, block_kv: int,
                  slots_tile: int, interpret: bool):
    S, H, w, hd = q.shape
    BL, width = k_pool.shape[1], k_pool.shape[2]
    MB = rows.shape[1]
    st = max(min(int(slots_tile), max(S, 1)), 1)
    bkv = max(min(int(block_kv), BL), 1)
    Sp = -(-S // st) * st
    R = H * w
    Rp = max(R, 8)                        # sublane-minimum scratch rows
    qf = jnp.pad(q.reshape(S, R, hd), ((0, Sp - S), (0, 0), (0, 0)))
    rows_p = jnp.pad(rows.astype(jnp.int32), ((0, Sp - S), (0, 0)),
                     constant_values=TRASH_BLOCK)
    pos_p = jnp.pad(pos.astype(jnp.int32), (0, Sp - S))[:, None]
    group = H * hd // width               # query heads a key head
    kern = functools.partial(
        _paged_kernel, scale=hd ** -0.5, heads=H, w=w, block_len=BL,
        block_kv=bkv, slots_tile=st, **({"group": group} if group > 1
                                        else {}))

    def block_of(g, u, j, rt, pt):
        # the zero-copy read: the table entry IS the block index
        return (chain_block(rt, pt, g * st + u, j, w=w, block_len=BL),
                0, 0)

    scratch = [
        pltpu.VMEM((Rp, 128), jnp.float32),   # running max
        pltpu.VMEM((Rp, 128), jnp.float32),   # running denominator
        pltpu.VMEM((Rp, hd), jnp.float32),    # output accumulator
    ]
    if R <= _BATCHED_ROWS:
        scratch.append(pltpu.VMEM((R, width), q.dtype))  # block-diagonal q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Sp // st, st, MB),
        in_specs=[
            pl.BlockSpec((1, R, hd),
                         lambda g, u, j, rt, pt: (g * st + u, 0, 0)),
            pl.BlockSpec((1, BL, width), block_of),
            pl.BlockSpec((1, BL, width), block_of),
        ],
        out_specs=pl.BlockSpec(
            (1, R, hd), lambda g, u, j, rt, pt: (g * st + u, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Sp, R, hd), v_pool.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            # a key head's ``group * w`` rows of scores and weights are
            # live at once: more than the default scope at 8 x 128 rows
            **({"vmem_limit_bytes": _GROUPED_VMEM_LIMIT} if group > 1
               else {})),
        interpret=interpret,
        **({"name": GROUPED_KERNEL_NAME} if group > 1 else {}),
    )(rows_p, pos_p, qf, k_pool, v_pool)
    return out[:S].reshape(S, H, w, hd)


#: the kernel's name in a device trace where key heads are shared (the
#: per-head case keeps the name it always had: ``_paged_pallas``)
GROUPED_KERNEL_NAME = "paged_gqa_attn"
_GROUPED_VMEM_LIMIT = 64 << 20

#: fast memory one window may claim: half of the 16 MiB a v5e kernel
#: scopes; the k/v blocks, the score tile and Mosaic's own temporaries
#: share the other half
_WINDOW_VMEM_BYTES = 8 << 20


def max_window(heads: int, hd: int, dtype) -> int:
    """The widest query window (rows per slot) ONE call of the kernel
    takes. It
    holds a slot's whole window in fast memory — q and the output block
    double-buffered, the f32 accumulator, running max and denominator,
    ``heads * w`` rows of each at 128-lane width — so ``w`` is bounded
    by VMEM, not by the context (first chip compile, PR 23: a 4096-row
    window of 2 heads asked for 23.5 MB against 16). Floored to the
    prefill window ladder (a multiple of 64, below that a power of two);
    a longer prompt suffix is prefilled in chunks of this width.
    ``heads`` are the QUERY heads (the rows held do not depend on how
    many key heads they share); :func:`paged_window_attention` attends a
    wider window in sub-windows of at most this many rows."""
    lanes = -(-int(hd) // 128) * 128
    per_row = lanes * (4 * jnp.dtype(dtype).itemsize + 4) + 2 * 128 * 4
    w = max(_WINDOW_VMEM_BYTES // (int(heads) * per_row), 1)
    return w // 64 * 64 if w >= 64 else 1 << (w.bit_length() - 1)


# ------------------------------------------------------------- resolution
def _tuned_paged(context: int, hd: int, w: int,
                 platform: str) -> tuple[int, int] | None:
    """Autotuned (block_kv, slots_tile) for this (context-bucket,
    platform) from the offline winner registry, or None when untuned.
    A plain dict read — this runs at jit trace time inside the serving
    programs, where locks/IO/clock are trace-safety hazards."""
    try:
        from ..perf import autotune
    except Exception:  # pragma: no cover - perf layer optional
        return None
    win = autotune.kernel_winner("paged_attn",
                                 autotune.paged_key(context, hd, w),
                                 platform)
    if not win:
        return None
    try:
        return int(win["block_kv"]), int(win["slots_tile"])
    except (KeyError, TypeError, ValueError):
        return None


def _resolve_paged(block_kv, slots_tile, *, context: int,
                   block_len: int, hd: int, w: int,
                   platform: str) -> tuple[int, int]:
    """Final (block_kv, slots_tile): explicit caller values win; then
    the autotuned winner for this context bucket; then the defaults
    (whole pool block per chunk, one slot per grid row)."""
    tuned = None
    if block_kv is None or slots_tile is None:
        tuned = _tuned_paged(context, hd, w, platform)
    if block_kv is None:
        block_kv = tuned[0] if tuned else block_len
    if slots_tile is None:
        slots_tile = tuned[1] if tuned else 1
    return (max(min(int(block_kv), int(block_len)), 1),
            max(int(slots_tile), 1))


# --------------------------------------------------------------- public
def paged_window_attention(q, k_pool, v_pool, rows, pos, *,
                           block_kv: int | None = None,
                           slots_tile: int | None = None,
                           impl: str | None = None,
                           interpret: bool | None = None):
    """Windowed paged attention: ``q`` [S, H, w, hd] holds w query rows
    per slot at global positions ``pos[s] + i`` (speculative verify
    passes the k+1 draft window); ``k_pool``/``v_pool`` are ONE layer's
    pools ``[num_blocks, block_len, kv_heads*hd]`` (a token's key heads
    side by side on the lanes; ``kv_heads`` divides ``H``, and query heads
    ``g*H/kv_heads …`` read key head ``g``: grouped-query attention);
    ``rows`` [S, max_blocks]
    is the ``PagedKVManager.block_rows`` table (TRASH_BLOCK padding);
    ``pos`` [S] int32. Query row i attends pool positions
    ``t <= pos + i`` through the slot's chain — the window's own k/v
    must already be scattered (write-then-attend, like
    ``decode_window``'s cache update). Returns [S, H, w, hd]. A window
    wider than :func:`max_window` is attended in equal sub-windows, each a
    slot of its own over the same chain (the window's keys are in the pool
    already, so a sub-window sees the ones before it).

    ``impl``: "pallas" | "lax" | None (platform switch — TPU-class
    backends run the kernel, everything else the bit-exact lax
    reference). ``interpret`` forces the Pallas interpreter (tests).
    ``block_kv``/``slots_tile`` default to the autotuned winner
    (``perf.autotune``, kernel "paged_attn"), else block_len / 1;
    every config returns identical values."""
    plat = target_platform()
    if impl is None:
        impl = "pallas" if plat == "tpu" else "lax"
    rows = jnp.asarray(rows, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if impl == "lax":
        return _paged_reference(q, k_pool, v_pool, rows, pos)
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} is not one of pallas|lax")
    if interpret is None:
        interpret = plat != "tpu"
    BL = int(k_pool.shape[1])
    context = int(rows.shape[1]) * BL
    S, H, w, hd = q.shape
    parts = -(-w // max_window(H, hd, q.dtype))
    if parts > 1:
        sub = -(-w // (parts * 8)) * 8
        q = jnp.pad(q, ((0, 0), (0, 0), (0, parts * sub - w), (0, 0)))
        q = jnp.transpose(q.reshape(S, H, parts, sub, hd),
                          (0, 2, 1, 3, 4)).reshape(S * parts, H, sub, hd)
        rows = jnp.repeat(rows, parts, axis=0)
        pos = (pos[:, None] + sub * jnp.arange(parts)[None]).reshape(-1)
    block_kv, slots_tile = _resolve_paged(
        block_kv, slots_tile, context=context, block_len=BL,
        hd=int(q.shape[3]), w=int(q.shape[2]), platform=plat)
    out = _paged_pallas(q, k_pool, v_pool, rows, pos,
                        block_kv=block_kv, slots_tile=slots_tile,
                        interpret=bool(interpret))
    if parts > 1:
        out = jnp.transpose(out.reshape(S, parts, H, sub, hd),
                            (0, 2, 1, 3, 4)).reshape(S, H, parts * sub, hd)
        out = out[:, :, :w]
    return out


def paged_attention(q, k_pool, v_pool, rows, pos, *,
                    block_kv: int | None = None,
                    slots_tile: int | None = None,
                    impl: str | None = None,
                    interpret: bool | None = None):
    """Single-token paged decode attention: ``q`` [S, H, hd] is each
    slot's newest query at global position ``pos[s]`` (already written
    to the pools); attends pool positions ``t <= pos[s]`` through the
    block table. The w=1 case of :func:`paged_window_attention` —
    returns [S, H, hd]."""
    out = paged_window_attention(q[:, :, None, :], k_pool, v_pool,
                                 rows, pos, block_kv=block_kv,
                                 slots_tile=slots_tile, impl=impl,
                                 interpret=interpret)
    return out[:, :, 0, :]


# ====================================================== latent attention
# Paged attention over a LATENT pool (multi-head latent attention in its
# absorbed form): a layer caches ONE array, ``[num_blocks, block_len,
# value_dim + rope_dim]`` — the normalised key-value latent and the
# rotated key all heads share — and every head of a slot scores against
# the same numbers. So a slot's step over one block is two products with
# the heads batched into the rows: ``[heads*w, C+R] x [C+R, block_len]``
# for the scores and ``[heads*w, block_len] x [block_len, C]`` for the
# weighted sum, whose values are the first ``C`` of the numbers already
# read for the scores (one read serves both).

#: the kernel's own name in a device trace (``_paged_latent_pallas``
#: is the jitted function the events carry)
LATENT_KERNEL_NAME = "paged_latent"
#: fast memory the latent kernel asks the compiler for, and the part of
#: it one tile of query rows may claim (the block, the score tile and
#: Mosaic's temporaries share the rest)
_LATENT_VMEM_LIMIT = 48 << 20
_LATENT_TILE_BYTES = 16 << 20
#: HBM one slot's window may hold as temporaries of the walk around the
#: kernel: its query rows and output rows, ``heads * w`` of each
_LATENT_WINDOW_HBM_BYTES = 64 << 20


def latent_row_tile(heads: int, width: int, value_dim: int, dtype) -> int:
    """Query positions (of ``heads`` rows each) one grid cell of the
    latent kernel holds in fast memory: q and the output block
    double-buffered, the float32 accumulator, running max and
    denominator, and three score-tile temporaries of a 512-wide block. A
    power of two, at least 1."""
    item = jnp.dtype(dtype).itemsize
    lanes = -(-int(width) // 128) * 128
    per_row = (2 * lanes * item + 2 * int(value_dim) * item
               + 4 * int(value_dim) + 2 * 128 * 4 + 3 * 512 * 4)
    w = max(_LATENT_TILE_BYTES // (int(heads) * per_row), 1)
    return 1 << (w.bit_length() - 1)


def latent_max_window(heads: int, width: int, value_dim: int,
                      dtype) -> int:
    """The widest query window (rows per slot) the latent walk takes.
    The kernel tiles a window's rows (:func:`latent_row_tile`), so fast
    memory does not bound it; what does is the window's query and output
    rows in HBM, ``heads * w * (width + value_dim)`` numbers a slot,
    held under ``_LATENT_WINDOW_HBM_BYTES``. Floored to the prefill
    window ladder as :func:`max_window` is; a longer prompt suffix is
    prefilled in chunks of this width."""
    per_row = int(heads) * (int(width) + int(value_dim)) \
        * jnp.dtype(dtype).itemsize
    w = max(_LATENT_WINDOW_HBM_BYTES // per_row, 1)
    return w // 64 * 64 if w >= 64 else 1 << (w.bit_length() - 1)


@functools.partial(jax.jit, static_argnames=("scale", "value_dim"))
def _latent_reference(q, pool, rows, pos, *, scale: float,
                      value_dim: int):
    """Pure-lax twin of the latent kernel: ``jnp.take`` each slot's
    chained blocks through the table, scores of every head against the
    same ``[L, C+R]`` numbers in float32, ``-inf`` outside ``t <= pos +
    i``, softmax, NaN→0 for fully-masked rows, the weights in the pool's
    type against the first ``value_dim`` numbers."""
    S, w, H, _ = q.shape
    NB, BL, width = pool.shape
    L = rows.shape[1] * BL
    idx = (rows[:, :, None] * BL
           + jnp.arange(BL)[None, None, :]).reshape(S, L)
    c = jnp.take(pool.reshape(NB * BL, width), idx, axis=0)   # [S, L, C+R]
    s = jnp.einsum("swhd,sld->swhl", q, c,
                   preferred_element_type=jnp.float32) * scale
    allowed = (jnp.arange(L)[None, None, :]
               <= (pos[:, None] + jnp.arange(w)[None, :])[:, :, None])
    s = jnp.where(allowed[:, :, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("swhl,slc->swhc", p.astype(pool.dtype),
                      c[..., :value_dim],
                      preferred_element_type=jnp.float32).astype(pool.dtype)


def _latent_kernel(rows_ref, pos_ref, q_ref, c_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, heads: int, tile_w: int,
                   block_len: int, value_dim: int):
    """One (slot, row tile, chain block) grid cell: ``tile_w`` query
    positions of ``heads`` rows each (position-major) against the pool
    block the table named."""
    s_idx = pl.program_id(0)
    t = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    block_id = rows_ref[s_idx, j]
    first_q = pos_ref[s_idx, 0] + t * tile_w

    # the trash block pads a chain; a block past the tile's last query
    # position holds nothing any of its rows may see
    @pl.when((block_id != TRASH_BLOCK)
             & (j * block_len <= first_q + tile_w - 1))
    def _compute():
        R = heads * tile_w
        q = q_ref[0]                       # [R, C+R]
        c = c_ref[0]                       # [block_len, C+R]
        s = jax.lax.dot_general(           # [R, block_len] f32, one dot
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        tpos = j * block_len + jax.lax.broadcasted_iota(
            jnp.int32, (R, block_len), 1)
        qpos = first_q + jax.lax.broadcasted_iota(
            jnp.int32, (R, block_len), 0) // heads
        allowed = tpos <= qpos
        s = jnp.where(allowed, s, _NEG)
        m_prev = m_scr[:R, :1]
        l_prev = l_scr[:R, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:R, :1] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:R, :1] = m_new
        acc_scr[:R, :] = acc_scr[:R, :] * corr + jax.lax.dot_general(
            p.astype(c.dtype), c[:, :value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _emit():
        R = heads * tile_w
        l = jnp.maximum(l_scr[:R, :1], 1e-35)
        o_ref[0] = (acc_scr[:R] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_dim",
                                             "tile_w", "interpret"))
def _paged_latent_pallas(q, pool, rows, pos, *, scale: float,
                         value_dim: int, tile_w: int, interpret: bool):
    S, w, H, width = q.shape
    BL = pool.shape[1]
    MB = rows.shape[1]
    tw = max(min(int(tile_w), w), 1)
    wp = -(-w // tw) * tw                 # rows past w: computed, dropped
    R = H * tw
    Rp = max(R, 8)
    qf = jnp.pad(q, ((0, 0), (0, wp - w), (0, 0), (0, 0))).reshape(
        S, wp * H, width)
    kern = functools.partial(_latent_kernel, scale=scale, heads=H,
                             tile_w=tw, block_len=BL, value_dim=value_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, wp // tw, MB),
        in_specs=[
            pl.BlockSpec((1, R, width),
                         lambda s, t, j, rt, pt: (s, t, 0)),
            # the zero-copy read: the table entry IS the block index
            pl.BlockSpec((1, BL, width),
                         lambda s, t, j, rt, pt: (rt[s, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, value_dim),
                               lambda s, t, j, rt, pt: (s, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((Rp, 128), jnp.float32),       # running max
            pltpu.VMEM((Rp, 128), jnp.float32),       # running denominator
            pltpu.VMEM((Rp, value_dim), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, wp * H, value_dim), pool.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM_LIMIT),
        interpret=interpret,
        name=LATENT_KERNEL_NAME,
    )(rows.astype(jnp.int32), pos.astype(jnp.int32)[:, None], qf, pool)
    return out.reshape(S, wp, H, value_dim)[:, :w]


def paged_latent_attention(q, pool, rows, pos, *, scale: float,
                           value_dim: int, impl: str | None = None,
                           interpret: bool | None = None):
    """Windowed paged attention over a latent pool. ``q`` [S, w, H, C+R]
    holds, for each of a slot's ``w`` new rows at global positions
    ``pos[s] + i`` and each head, the query absorbed into the latent's
    space (``C`` numbers) and its rotated part (``R``); ``pool`` is ONE
    layer's ``[num_blocks, block_len, C+R]``; ``rows`` [S, max_blocks] is
    the block table (TRASH_BLOCK padding); ``pos`` [S] int32. Row ``i``
    attends pool positions ``t <= pos + i`` through the slot's chain (the
    window's own entries must already be scattered) with scores ``q . c *
    scale`` and returns the weighted sum of the first ``value_dim``
    numbers: [S, w, H, value_dim] in the pool's type.

    ``impl``: "pallas" | "lax" | None (the platform switch of
    :func:`paged_window_attention`); ``interpret`` forces the Pallas
    interpreter (tests)."""
    plat = target_platform()
    if impl is None:
        impl = "pallas" if plat == "tpu" else "lax"
    rows = jnp.asarray(rows, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if impl == "lax":
        return _latent_reference(q, pool, rows, pos, scale=float(scale),
                                 value_dim=int(value_dim))
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} is not one of pallas|lax")
    if interpret is None:
        interpret = plat != "tpu"
    tile_w = latent_row_tile(int(q.shape[2]), int(q.shape[3]),
                             int(value_dim), pool.dtype)
    return _paged_latent_pallas(q, pool, rows, pos, scale=float(scale),
                                value_dim=int(value_dim), tile_w=tile_w,
                                interpret=bool(interpret))


# ============================================ block-sparse grouped-query
# Paged attention in which (1) several query heads share a key head (a
# GROUP: the key head's block is fetched once for all of them and they are
# the rows of one product) and (2) a query row attends a LIST of chosen
# blocks of ``block_size`` tokens, not its slot's whole chain: the grid
# walks the list, which rides scalar prefetch as the table does above, and
# a cell past the list's end fetches nothing. Below a model's dense length
# the caller lists every block up to the row, and the same kernel walks
# the whole chain. The unit is ONE query token of one group: each token
# chooses for itself, so a window of ``w`` tokens is ``w`` lists.
#
# The pool rests ``[num_blocks, block_len, kv_heads * 2 * hd]``: a token's
# key heads side by side on the lanes, each head's KEY AND VALUE side by
# side (``[k_g | v_g]``), so that a chosen block of one head is ONE copy of
# ``[block_size, 2 * hd]`` (first chip run, PR 33: a cell's time went with
# the count of copies and of products, not with their bytes). A chosen
# block is rows ``[m * block_size, (m + 1) * block_size)`` of the sequence,
# so with ``block_len`` a multiple of ``block_size`` it is one slab of the
# pool viewed ``[num_blocks * block_len / block_size, block_size, ...]``.
# A grid cell is handed ``_SPARSE_FETCH`` slabs and scores them in ONE
# product (the slabs laid end to end), one softmax update, one weighted sum.
#
# The choice is made by the caller from the scores of :func:`select_scores`:
# every query row against the slot's COMPRESSED keys, which rest a few rows
# a block in a pool of their own ``[num_blocks, rows_per_block, kv_heads *
# hd]`` and are read through the same table.

#: the kernels' own names in a device trace
SPARSE_KERNEL_NAME = "paged_sparse_attn"
SELECT_KERNEL_NAME = "paged_sparse_select"
#: chosen blocks one grid cell of the sparse kernel is handed (that many
#: fetches and one pair of products share a cell's fixed cost), and table
#: entries one cell of the scoring pass is handed
_SPARSE_FETCH = 16
_SELECT_FETCH = 16
_SELECT_ROW_TILE = 512


@functools.partial(jax.jit, static_argnames=("block_size", "scale"))
def _sparse_reference(q, kv_pool, phys, logical, qpos, *,
                      block_size: int, scale: float):
    """Pure-lax twin of the sparse kernel: gather every listed slab,
    scores in float32, ``-inf`` outside ``t <= qpos`` and at entries with
    ``logical < 0``, softmax, NaN→0 for rows with nothing to see, the
    weights in the pool's type against the values."""
    T, G, R, hd = q.shape
    bs = block_size
    slabs = kv_pool.reshape(-1, bs, G, 2 * hd)
    g = jnp.arange(G)[None, :, None]
    kv = slabs[phys, :, g]                               # [T, G, K, bs, 2hd]
    k, v = kv[..., :hd], kv[..., hd:]
    s = jnp.einsum("tgrd,tgkbd->tgrkb", q, k,
                   preferred_element_type=jnp.float32) * scale
    tpos = logical[..., None] * bs + jnp.arange(bs)      # [T, G, K, bs]
    allowed = (logical[..., None] >= 0) & (tpos <= qpos[:, None, None, None])
    s = jnp.where(allowed[:, :, None], s, -jnp.inf)
    K = phys.shape[-1]
    p = jax.nn.softmax(s.reshape(T, G, R, K * bs), axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p).reshape(T, G, R, K, bs)
    return jnp.einsum("tgrkb,tgkbd->tgrd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def _sparse_kernel(phys_ref, logical_ref, qpos_ref, q_ref, *refs,
                   scale: float, block_size: int, fetch: int, groups: int,
                   listed: int):
    kv_refs = refs[:fetch]
    o_ref, m_scr, l_scr, acc_scr = refs[fetch:]
    t = pl.program_id(0)
    g = pl.program_id(1)
    c = pl.program_id(2)
    R, hd = q_ref.shape[2], q_ref.shape[3]
    bs = block_size

    @pl.when(c == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    base = (t * groups + g) * listed + c * fetch

    # the list has its real entries first: a cell whose first
    # entry is past the list's end has nothing to see (and fetched nothing)
    @pl.when(logical_ref[base] >= 0)
    def _compute():
        kv = kv_refs[0][0] if fetch == 1 else jnp.concatenate(
            [r[0] for r in kv_refs], axis=0)             # [fetch*bs, 2hd]
        s = jax.lax.dot_general(                         # [R, fetch*bs] f32
            q_ref[0, 0], kv[:, :hd], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # each column's position in the sequence: its slab's block index
        # times block_size plus its row; past the list's end, out of sight
        first = jnp.full(s.shape, -1, jnp.int32)
        for i in range(fetch):
            first = jnp.where(lane >= i * bs, logical_ref[base + i], first)
        tpos = first * bs + lane % bs
        allowed = (first >= 0) & (tpos <= qpos_ref[t])
        p, corr = _online_softmax(s, allowed, m_scr, l_scr, R)
        acc_scr[:R, :] = acc_scr[:R, :] * corr + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, hd:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _emit():
        l = jnp.maximum(l_scr[:R, :1], 1e-35)
        o_ref[0, 0] = (acc_scr[:R] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "scale",
                                             "interpret"))
def _paged_sparse_attn_pallas(q, kv_pool, phys, logical, qpos, *,
                              block_size: int, scale: float,
                              interpret: bool):
    T, G, R, hd = q.shape
    bs = block_size
    K = phys.shape[-1]
    nf = min(_SPARSE_FETCH, K)
    Kp = -(-K // nf) * nf
    # past the list's end the last slab again (no fetch), marked unseen
    phys = jnp.pad(phys, ((0, 0), (0, 0), (0, Kp - K)), mode="edge")
    logical = jnp.pad(logical, ((0, 0), (0, 0), (0, Kp - K)),
                      constant_values=-1)
    slabs = kv_pool.reshape(-1, bs, G * 2 * hd)
    Rp = max(R, 8)
    kern = functools.partial(_sparse_kernel, scale=scale, block_size=bs,
                             fetch=nf, groups=G, listed=Kp)

    def slab(i):
        return pl.BlockSpec(
            (1, bs, 2 * hd), lambda t, g, c, ph, lg, qp:
            (ph[(t * G + g) * Kp + c * nf + i], 0, g))

    own = pl.BlockSpec((1, 1, R, hd), lambda t, g, c, ph, lg, qp:
                       (t, g, 0, 0))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(T, G, Kp // nf),
            in_specs=[own] + [slab(i) for i in range(nf)],
            out_specs=own,
            scratch_shapes=[pltpu.VMEM((Rp, 128), jnp.float32),
                            pltpu.VMEM((Rp, 128), jnp.float32),
                            pltpu.VMEM((Rp, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, G, R, hd), kv_pool.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=SPARSE_KERNEL_NAME,
    )(phys.reshape(-1), logical.reshape(-1), qpos, q, *([slabs] * nf))


def sparse_block_attention(q, kv_pool, phys, logical, qpos, *,
                           block_size: int, scale: float,
                           impl: str | None = None,
                           interpret: bool | None = None):
    """Grouped-query attention of single query tokens over LISTED blocks.
    ``q`` [T, G, R, hd]: ``T`` query tokens, ``G`` key heads, the ``R``
    query heads that share each; ``kv_pool`` ONE layer's pool
    ``[num_blocks, block_len, G*2*hd]``, a head's key and value side by
    side; ``phys``/``logical`` [T, G, K] int32 the list a (token, key
    head) attends: ``logical`` the block's index in the sequence (tokens
    ``logical * block_size ...``; the real entries first, in any order,
    ``-1`` past the list's end) and ``phys`` the slab of the pool that
    holds it (``table entry * (block_len / block_size) + offset``; past
    the list's end the last real entry again, so that nothing is fetched
    there); ``qpos`` [T] the tokens' global positions: a row sees tokens
    ``<= qpos`` of its listed blocks. Returns [T, G, R, hd] in the pool's
    type.

    ``impl``: "pallas" | "lax" | None (the platform switch of
    :func:`paged_window_attention`); ``interpret`` forces the Pallas
    interpreter (tests)."""
    plat = target_platform()
    if impl is None:
        impl = "pallas" if plat == "tpu" else "lax"
    phys = jnp.asarray(phys, jnp.int32)
    logical = jnp.asarray(logical, jnp.int32)
    qpos = jnp.asarray(qpos, jnp.int32)
    if impl == "lax":
        return _sparse_reference(q, kv_pool, phys, logical, qpos,
                                 block_size=int(block_size),
                                 scale=float(scale))
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} is not one of pallas|lax")
    if interpret is None:
        interpret = plat != "tpu"
    return _paged_sparse_attn_pallas(
        q, kv_pool, phys, logical, qpos, block_size=int(block_size),
        scale=float(scale), interpret=bool(interpret))


# ----------------------------------------------------- the scoring pass
@functools.partial(jax.jit, static_argnames=("scale",))
def _select_reference(q, ck_pool, rows, *, scale: float):
    S, G, M, hd = q.shape
    NB, rpb, _ = ck_pool.shape
    ck = ck_pool.reshape(NB, rpb, G, hd)[rows]         # [S, MB, rpb, G, hd]
    ck = ck.reshape(S, -1, G, hd)
    return jnp.einsum("sgmd,scgd->sgmc", q, ck,
                      preferred_element_type=jnp.float32) * scale


def _select_kernel(rows_ref, q_ref, *refs, scale: float, fetch: int):
    ck_refs, o_ref = refs[:fetch], refs[fetch]
    G, hd = q_ref.shape[1], q_ref.shape[3]
    ck = jnp.concatenate([r[0] for r in ck_refs], axis=0) if fetch > 1 \
        else ck_refs[0][0]                               # [fetch*rpb, G*hd]
    for g in range(G):
        o_ref[0, g] = jax.lax.dot_general(
            q_ref[0, g], ck[:, g * hd:(g + 1) * hd],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_sparse_select_pallas(q, ck_pool, rows, *, scale: float,
                                interpret: bool):
    S, G, M, hd = q.shape
    rpb = ck_pool.shape[1]
    MB = rows.shape[1]
    nf = max(_SELECT_FETCH, -(-128 // rpb))            # whole lanes out
    MBp = -(-MB // nf) * nf
    rows = jnp.pad(rows, ((0, 0), (0, MBp - MB)),
                   constant_values=TRASH_BLOCK)
    tm = min(_SELECT_ROW_TILE, M)
    Mp = -(-M // tm) * tm
    q = jnp.pad(q, ((0, 0), (0, 0), (0, Mp - M), (0, 0)))
    kern = functools.partial(_select_kernel, scale=scale, fetch=nf)

    def entry(i):
        return pl.BlockSpec((1, rpb, G * hd), lambda s, r, c, rt:
                            (rt[s, c * nf + i], 0, 0))

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, Mp // tm, MBp // nf),
            in_specs=[pl.BlockSpec((1, G, tm, hd), lambda s, r, c, rt:
                                   (s, 0, r, 0))]
            + [entry(i) for i in range(nf)],
            out_specs=pl.BlockSpec((1, G, tm, nf * rpb),
                                   lambda s, r, c, rt: (s, 0, r, c))),
        out_shape=jax.ShapeDtypeStruct((S, G, Mp, MBp * rpb), jnp.float32),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=SELECT_KERNEL_NAME,
    )(rows, q, *([ck_pool] * nf))
    return out[:, :, :M, :MB * rpb]


def select_scores(q, ck_pool, rows, *, scale: float,
                  impl: str | None = None, interpret: bool | None = None):
    """The scoring pass of block selection: every query row against every
    compressed key of its slot's chain. ``q`` [S, G, M, hd] (``M`` rows of
    key head ``g``: a window's tokens times the query heads that share
    it); ``ck_pool`` ONE layer's compressed-key pool ``[num_blocks,
    rows_per_block, G*hd]``; ``rows`` [S, max_blocks] the block table.
    Returns ``[S, G, M, max_blocks * rows_per_block]`` float32 ``q . ck *
    scale``, column ``b * rows_per_block + r`` row ``r`` of the chain's
    ``b``-th block: EVERY column, also where no key has been written (the
    caller knows from the positions which columns hold one)."""
    plat = target_platform()
    if impl is None:
        impl = "pallas" if plat == "tpu" else "lax"
    rows = jnp.asarray(rows, jnp.int32)
    if impl == "lax":
        return _select_reference(q, ck_pool, rows, scale=float(scale))
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} is not one of pallas|lax")
    if interpret is None:
        interpret = plat != "tpu"
    return _paged_sparse_select_pallas(q, ck_pool, rows, scale=float(scale),
                                       interpret=bool(interpret))
