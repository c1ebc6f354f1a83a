"""Pallas TPU kernels: lightning (linear) attention over a per-sequence
state pool.

A lightning-attention layer caches no entry a token: per head it keeps a
STATE ``S`` of ``[head_dim, head_dim]`` float32 a sequence, whatever the
sequence's length, with

    S_t = lam_h * S_{t-1} + k_t^T v_t        o_t = q_t S_t

and ``lam_h = exp(-slope_h)``. The states of all sequences rest in ONE pool
a layer, ``[rows, heads, head_dim, head_dim]`` float32, which
``dl.paged_kv.PagedKVManager`` hands out by row (row 0 is the trash row, as
block 0 is the trash block: inactive slots and padding rows point there).
Both kernels read a slot's state where it rests and write it back in place
(the pool is aliased to the output): no copy of a state is gathered or
scattered around the call.

- :data:`STEP_KERNEL_NAME`, the decode step (``w`` = 1): per (slot, tile of
  heads) one read-modify-write of the tile's states. The outer product
  ``k^T v`` is ONE matrix product ``diag(k) @ rows(v)`` (exact: a single
  product of two numbers of the serving type each, accumulated in
  float32), so no vector is moved from lanes to sublanes.
- :data:`CHUNK_KERNEL_NAME`, a window of ``w`` rows (prefill): per (slot,
  tile of heads), sub-chunks of ``C`` rows: within a sub-chunk the masked
  product ``((q k^T) * D) v`` with ``D[t, s] = lam^(t-s)`` for ``s <= t``,
  across sub-chunks the state. Rows at and past ``lens[s]`` are padding:
  their keys are taken as zero and the state stops decaying at the last
  real row, so the state written back is the state after ``lens[s]``
  tokens.

A slot at position 0 has no history: its state is taken as ZERO whatever
the row holds (a fresh sequence's row is never cleared on the host's
word). Operands of every product are of the serving type (the state is
rounded to it where it is an operand of ``q S``; it is KEPT and UPDATED in
float32); accumulation is float32.

Off-TPU the same call runs a pure-``lax`` reference of the same
formulation (same sub-chunks, same rounding points); the platform switch
is the one ``pallas_paged_attention`` uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.compat import tpu_compiler_params as _CompilerParams
from ..utils.platform import target_platform
from .paged_kv import TRASH_ROW

__all__ = ["lightning_attention", "STEP_KERNEL_NAME", "CHUNK_KERNEL_NAME",
           "TRASH_ROW", "sub_chunk"]

#: the kernels' own names in a device trace
STEP_KERNEL_NAME = "lightning_step"
CHUNK_KERNEL_NAME = "lightning_chunk"
_VMEM_LIMIT = 64 << 20


def sub_chunk(w: int) -> int:
    """Rows of one sub-chunk of a ``w``-row window (``w`` as
    :func:`_padded_window` leaves it)."""
    for c in (128, 64):
        if w % c == 0:
            return c
    return w


def _padded_window(w: int) -> int:
    """A window's rows as the chunked form takes them: a multiple of 64
    from 64 up, below that a power of two of at least 8."""
    if w >= 64:
        return -(-w // 64) * 64
    p = 8
    while p < w:
        p <<= 1
    return p


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# ------------------------------------------------------------ one head
def _chunk_head(q, k, v, s, slope_row, n, C: int):
    """One head's window: ``q``/``k``/``v`` [w, hd] of the serving type,
    ``s`` [hd, hd] float32 (the state before the window), ``slope_row``
    [1, L] float32 (the head's slope on every lane, ``L >= max(hd, C)``),
    ``n`` the count of real rows. Returns ``(o [w, hd] float32, the
    state after n rows)``. Plain ``jnp`` on values: the kernel calls it on
    what it loaded, the reference maps it over slots and heads."""
    w, hd = q.shape
    dt = q.dtype
    ti = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    decay = jnp.where(si <= ti, jnp.exp(
        -slope_row[:, :C] * (ti - si).astype(jnp.float32)), 0.0)   # [C, C]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, hd), 0)
    slope_hd = slope_row[:, :hd]
    into = jnp.exp(-slope_hd * (row + 1).astype(jnp.float32))    # lam^(t+1)
    outs = []
    for c in range(w // C):
        cnt = jnp.clip(n - c * C, 0, C)
        qc, vc = q[c * C:(c + 1) * C], v[c * C:(c + 1) * C]
        kc = k[c * C:(c + 1) * C]
        kc = jnp.where(row < cnt, kc, jnp.zeros_like(kc))
        a = _dot(qc, kc, ((1,), (1,))) * decay                   # [C, C]
        o = _dot(a.astype(dt), vc, ((1,), (0,))) \
            + _dot(qc, s.astype(dt), ((1,), (0,))) * into
        outs.append(o)
        left = jnp.maximum(cnt - 1 - row, 0).astype(jnp.float32)
        kw = (kc.astype(jnp.float32) * jnp.exp(-slope_hd * left)).astype(dt)
        cnt_row = jnp.full((1, hd), cnt, jnp.int32).astype(jnp.float32)
        s = jnp.exp(-slope_hd * cnt_row) * s \
            + _dot(kw, vc, ((0,), (0,)))                         # k^T v
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, 0)), s


def _step_head(q, k, v, s, slope_row, dt):
    """One head's decode step: ``q``/``k``/``v`` [1, hd] float32 holding
    numbers of the serving type ``dt`` (passed wide so that a tile of 8
    heads is one register), ``s`` [hd, hd] float32. Returns ``(o [1, hd]
    float32, the new state)``."""
    hd = s.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    kd = jnp.where(eye, jnp.broadcast_to(k, (hd, hd)), 0.0)
    outer = _dot(kd.astype(dt), jnp.broadcast_to(v, (hd, hd)).astype(dt),
                 ((1,), (0,)))                                   # k^T v
    s = jnp.exp(-slope_row[:, :hd]) * s + outer
    o = _dot(jnp.broadcast_to(q, (8, hd)).astype(dt), s.astype(dt),
             ((1,), (0,)))
    return o[:1], s


# ------------------------------------------------------------ lax path
@jax.jit
def _reference(q, k, v, state, srows, pos, lens, slopes):
    """Pure-lax twin of both kernels: gather the slots' states, the same
    per-head formulation, scatter them back."""
    S, w, H, hd = q.shape
    s_in = jnp.where((pos > 0)[:, None, None, None], state[srows], 0.0)
    if w == 1:
        s_new = jnp.exp(-slopes)[None, :, None, None] * s_in + jnp.einsum(
            "shd,she->shde", k[:, 0].astype(jnp.float32),
            v[:, 0].astype(jnp.float32))
        o = jnp.einsum("shd,shde->she", q[:, 0], s_new.astype(q.dtype),
                       preferred_element_type=jnp.float32)[:, None]
    else:
        wp = _padded_window(w)
        C = sub_chunk(wp)
        pad = ((0, 0), (0, wp - w), (0, 0), (0, 0))
        qh, kh, vh = (jnp.transpose(jnp.pad(a, pad), (0, 2, 1, 3))
                      for a in (q, k, v))                  # [S, H, wp, hd]
        L = max(hd, C)
        slope_rows = jnp.broadcast_to(slopes[:, None, None], (H, 1, L))
        per_head = jax.vmap(
            lambda q, k, v, s, sl, n: _chunk_head(q, k, v, s, sl, n, C),
            in_axes=(0, 0, 0, 0, 0, None))
        o, s_new = jax.vmap(per_head, in_axes=(0, 0, 0, 0, None, 0))(
            qh, kh, vh, s_in, slope_rows, lens)
        o = jnp.transpose(o, (0, 2, 1, 3))[:, :w]
    return o, state.at[srows].set(s_new)


# --------------------------------------------------------- pallas path
def _step_kernel(srows_ref, pos_ref, q_ref, k_ref, v_ref, slope_ref, s_ref,
                 o_ref, s_out_ref, *, th: int, dt):
    s_idx = pl.program_id(0)
    fresh = pos_ref[s_idx] <= 0
    for h in range(th):
        s = jnp.where(fresh, 0.0, s_ref[0, h])
        o, s = _step_head(q_ref[0, h:h + 1], k_ref[0, h:h + 1],
                          v_ref[0, h:h + 1], s, slope_ref[h], dt)
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1] = o


def _chunk_kernel(srows_ref, pos_ref, lens_ref, q_ref, k_ref, v_ref,
                  slope_ref, s_ref, o_ref, s_out_ref, *, th: int, C: int):
    s_idx = pl.program_id(0)
    fresh = pos_ref[s_idx] <= 0
    n = lens_ref[s_idx]
    for h in range(th):
        s = jnp.where(fresh, 0.0, s_ref[0, h])
        o, s = _chunk_head(q_ref[0, h], k_ref[0, h], v_ref[0, h], s,
                           slope_ref[h], n, C)
        s_out_ref[0, h] = s
        o_ref[0, h] = o


def _head_tile(H: int, want: int) -> int:
    th = min(want, H)
    while H % th:
        th -= 1
    return th


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=())
def _lightning_step_pallas(q, k, v, state, srows, pos, slopes, *,
                           interpret: bool):
    S, H, hd = q.shape
    th = _head_tile(H, 8)
    slope_rows = jnp.broadcast_to(slopes[:, None, None], (H, 1, hd))
    kern = functools.partial(_step_kernel, th=th, dt=q.dtype)
    vec = pl.BlockSpec((1, th, hd), lambda s, j, sr, ps: (s, j, 0))
    st = pl.BlockSpec((1, th, hd, hd), lambda s, j, sr, ps: (sr[s], j, 0, 0))
    o, state = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, H // th),
            in_specs=[vec, vec, vec,
                      pl.BlockSpec((th, 1, hd),
                                   lambda s, j, sr, ps: (j, 0, 0)),
                      st],
            out_specs=[vec, st]),
        out_shape=[jax.ShapeDtypeStruct((S, H, hd), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: srows, pos, q, k, v, slopes, state -> state out
        input_output_aliases={6: 1},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=STEP_KERNEL_NAME,
    )(srows, pos, q.astype(jnp.float32), k.astype(jnp.float32),
      v.astype(jnp.float32), slope_rows, state)
    return o, state


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lightning_chunk_pallas(q, k, v, state, srows, pos, lens, slopes, *,
                            interpret: bool):
    S, w, H, hd = q.shape
    wp = _padded_window(w)
    C = sub_chunk(wp)
    # a tile of heads holds q, k, v and the float32 output double-buffered
    th = _head_tile(H, max((4 << 20) // (wp * hd * 20), 1))
    pad = ((0, 0), (0, wp - w), (0, 0), (0, 0))
    qh, kh, vh = (jnp.transpose(jnp.pad(a, pad), (0, 2, 1, 3))
                  for a in (q, k, v))                      # [S, H, wp, hd]
    L = max(hd, C)
    slope_rows = jnp.broadcast_to(slopes[:, None, None], (H, 1, L))
    kern = functools.partial(_chunk_kernel, th=th, C=C)
    win = pl.BlockSpec((1, th, wp, hd), lambda s, j, sr, ps, ln: (s, j, 0, 0))
    st = pl.BlockSpec((1, th, hd, hd),
                      lambda s, j, sr, ps, ln: (sr[s], j, 0, 0))
    o, state = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, H // th),
            in_specs=[win, win, win,
                      pl.BlockSpec((th, 1, L),
                                   lambda s, j, sr, ps, ln: (j, 0, 0)),
                      st],
            out_specs=[win, st]),
        out_shape=[jax.ShapeDtypeStruct((S, H, wp, hd), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: srows, pos, lens, q, k, v, slopes, state -> state out
        input_output_aliases={7: 1},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=CHUNK_KERNEL_NAME,
    )(srows, pos, lens, qh, kh, vh, slope_rows, state)
    return jnp.transpose(o, (0, 2, 1, 3))[:, :w], state


def lightning_attention(q, k, v, state, srows, pos, lens, slopes, *,
                        impl: str | None = None,
                        interpret: bool | None = None):
    """Lightning attention of a window over the state pool. ``q``/``k``/
    ``v`` [S, w, H, hd] hold each slot's ``w`` new rows (normed, rotated
    and scaled by the caller, of the serving type); ``state`` is ONE
    layer's pool ``[rows, H, hd, hd]`` float32; ``srows`` [S] the slots'
    rows in it (``TRASH_ROW`` for a slot that is not there); ``pos`` [S]
    the position of each slot's first row (0: the state is taken as
    zero); ``lens`` [S] how many of the ``w`` rows are real; ``slopes``
    [H] float32, ``lam_h = exp(-slopes[h])``. Returns ``(o [S, w, H, hd]
    float32, the pool with every slot's state after its real rows)``.

    ``impl``: "pallas" | "lax" | None (TPU-class backends run the
    kernels, everything else the lax reference); ``interpret`` forces the
    Pallas interpreter (tests)."""
    plat = target_platform()
    if impl is None:
        impl = "pallas" if plat == "tpu" else "lax"
    srows = jnp.asarray(srows, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    slopes = jnp.asarray(slopes, jnp.float32)
    if impl == "lax":
        return _reference(q, k, v, state, srows, pos, lens, slopes)
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} is not one of pallas|lax")
    if interpret is None:
        interpret = plat != "tpu"
    if q.shape[1] == 1:
        o, state = _lightning_step_pallas(
            q[:, 0], k[:, 0], v[:, 0], state, srows, pos, slopes,
            interpret=bool(interpret))
        return o[:, None], state
    return _lightning_chunk_pallas(q, k, v, state, srows, pos, lens, slopes,
                                   interpret=bool(interpret))
