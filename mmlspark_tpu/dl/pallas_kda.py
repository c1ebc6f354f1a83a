"""Pallas TPU kernels: the gated delta rule with a decay for each KEY
CHANNEL (Kimi Delta Attention, KDA) over a per-sequence state pool.

Per head a STATE ``S`` of ``[key_dim, value_dim]`` float32 a sequence,
with ``g_t`` [key_dim] the log decay of each key channel (``<= 0``) and
``beta_t`` the head's write strength:

    S <- Diag(exp g_t) S;   d_t = beta_t (v_t - S^T k_t);   S <- S + k_t d_t^T;
    o_t = S^T q_t

``dl.pallas_gated_delta`` is the same rule with ONE decay a head; here
each row of the state decays at its own rate. The pool, the rows, the
aliasing and the conventions (a slot at position 0 starts from zero, rows
at and past ``lens`` are padding that neither decays nor writes, products
on operands of the serving type with float32 accumulation, the state kept
and updated in float32) are ``dl.pallas_gated_delta``'s.

- :data:`STEP_KERNEL_NAME`, the decode step (``w`` = 1): per (slot, tile of
  heads) one read-modify-write of the tile's states; the ``[key_dim]``
  decay scales the state's rows before the delta term, and ``k^T S`` and
  ``q^T S`` stay ONE product against the decayed state
  (``pallas_gated_delta._step_head``).
- :data:`CHUNK_KERNEL_NAME`, a window of ``w`` rows, in chunks of ``CHUNK``
  = 64 rows. With ``gamma_t`` [key_dim] the running sum of ``g`` inside a
  chunk, every intra-chunk product carries ``exp(gamma_t,c - gamma_s,c)``
  a channel, so the scalar kernel's ``Gamma`` matrix is gone:

      A[t, s] = sum_c k_t,c k_s,c exp(gamma_t,c - gamma_s,c)   (s < t)
      M[t, s] = sum_c q_t,c k_s,c exp(gamma_t,c - gamma_s,c)   (s <= t)
      L = diag(beta) A,  T = (I + L)^-1 (float32, :func:`lower_inverse`)
      V' = T (beta v) - T (beta k exp(gamma)) S
      O  = (q exp(gamma)) S + M V'
      S <- Diag(exp gamma_C) S + (k exp(gamma_C - gamma))^T V'

  At the family's decays (``exp(A_log)`` up to 16) ``gamma`` falls to
  about -1,000 inside a chunk, so the factorisation ``(k exp(gamma)) (k
  exp(-gamma))^T`` overflows float32. ``A`` and ``M`` are therefore made
  in SUB-CHUNKS of ``SUB`` = 16 rows, folded about a reference row: for
  ``t`` in sub-chunk ``i`` (first row ``r_i``) and ``s < r_i``,
  ``exp(gamma_t - gamma_s) = exp(gamma_t - gamma_{r_i}) exp(gamma_{r_i} -
  gamma_s)``, both exponents ``<= 0`` (one product a sub-chunk, operands
  of the serving type); the diagonal ``SUB x SUB`` blocks are summed a
  channel at a time in float32. No exponent is positive anywhere. These
  decay products are elementwise work and small products made by XLA in
  front of the kernel; the kernel is the inverse, the products against the
  state and the carried state.

Off-TPU the same call runs a pure-``lax`` reference of the same formulation
(same chunks, same rounding points); the platform switch is the one
``pallas_paged_attention`` uses.
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
from .pallas_gated_delta import (_HIGHEST, CHUNK, _dot, _padded_window,
                                 _step_head)
from .pallas_lightning import _head_tile

__all__ = ["kda_rule", "lower_inverse", "STEP_KERNEL_NAME",
           "CHUNK_KERNEL_NAME", "CHUNK", "SUB", "TRASH_ROW"]

#: the kernels' own names in a device trace
STEP_KERNEL_NAME = "kda_step"
CHUNK_KERNEL_NAME = "kda_chunk"
#: rows of a sub-chunk, about whose first row the decay is folded
SUB = 16
_VMEM_LIMIT = 64 << 20


def _column(row):
    """A ``[1, n]`` row as an ``[n, 1]`` column (the diagonal of the row
    laid on a square, summed along its lanes)."""
    n = row.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def lower_inverse(lower):
    """``(I + L)^-1`` of a strictly lower triangular ``L`` [C, C] float32,
    in float32, by forward substitution: the ``SUB`` x ``SUB`` diagonal
    blocks by ``X <- I - L X`` (a row of each block final a pass), then
    blocks merged two at a time, ``[[A, 0], [B, D]]^-1 = [[A', 0], [-D' B
    A', D']]``. Every product is of numbers the inverse holds. Where a
    chunk's keys point much the same way and decay slowly, ``L`` is dense
    with entries near ``beta``: its powers, which the product form ``(I -
    L)(I + L^2)(I + L^4)...`` sums, then grow past 1e9 and their float32
    sum cancels to nothing right (``tests/test_kda.py``)."""
    C = lower.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = jnp.where(ti == si, 1.0, 0.0)
    block = jnp.where(ti // SUB == si // SUB, lower, 0.0)
    inv = eye - block
    for _ in range(SUB - 2):
        inv = eye - _dot(block, inv, ((1,), (0,)), _HIGHEST)
    span = SUB
    while span < C:
        off = jnp.where((ti // (2 * span) == si // (2 * span))
                        & (ti // span != si // span), lower, 0.0)
        inv = inv - _dot(inv, _dot(off, inv, ((1,), (0,)), _HIGHEST),
                         ((1,), (0,)), _HIGHEST)
        span *= 2
    return inv


# ------------------------------------------------------------ one head
def _chunk_step(lower, within, qg, kbg, ke, vb, end, s):
    """One chunk of one head: ``lower`` = ``diag(beta) A`` and ``within`` =
    ``M`` [C, C] float32; ``qg`` = ``q exp(gamma)``, ``kbg`` = ``beta k
    exp(gamma)``, ``ke`` = ``k exp(gamma_C - gamma)`` [C, dk] and ``vb`` =
    ``beta v`` [C, dv] of the serving type; ``end`` [1, dk] float32 =
    ``exp(gamma_C)``; ``s`` [dk, dv] float32. Returns ``(o [C, dv]
    float32, the state after the chunk)``."""
    dt = qg.dtype
    inv = lower_inverse(lower).astype(dt)
    s_op = s.astype(dt)
    wrote = _dot(inv, vb, ((1,), (0,))) - _dot(
        _dot(inv, kbg, ((1,), (0,))).astype(dt), s_op, ((1,), (0,)))
    wrote_op = wrote.astype(dt)
    o = _dot(qg, s_op, ((1,), (0,))) \
        + _dot(within.astype(dt), wrote_op, ((1,), (0,)))
    return o, _column(end) * s + _dot(ke, wrote_op, ((0,), (0,)))


def _decay_products(q, k, v, g, beta, lens):
    """What the chunked form multiplies, from a window's ``q``/``k`` [S, w,
    H, dk], ``v`` [S, w, H, dv] (serving type), ``g`` [S, w, H, dk] and
    ``beta`` [S, w, H] float32 and ``lens`` [S]: the window padded to whole
    chunks, heads first. Returns ``((qg, kbg, ke, vb) [S, H, wp, ...] of
    the serving type, lower, within [S, H, wp, CHUNK] float32, end [S, H,
    nc, 1, dk] float32)``; rows past ``lens`` get ``beta`` = ``g`` = 0."""
    S, w, H, dk = q.shape
    dt = q.dtype
    wp = _padded_window(w)
    nc, ns = wp // CHUNK, CHUNK // SUB
    real = jnp.arange(wp)[None, :] < lens[:, None]              # [S, wp]
    pad = ((0, 0), (0, wp - w), (0, 0))
    beta = jnp.where(real[:, :, None], jnp.pad(beta, pad), 0.0)
    g = jnp.where(real[:, :, None, None], jnp.pad(g, pad + ((0, 0),)), 0.0)

    def heads_first(a):          # [S, wp, H, n] -> [S, H, nc, CHUNK, n]
        a = jnp.pad(a, ((0, 0), (0, wp - a.shape[1]), (0, 0), (0, 0)))
        return jnp.transpose(a.astype(jnp.float32), (0, 2, 1, 3)).reshape(
            S, H, nc, CHUNK, a.shape[-1])

    q, k, v, g = (heads_first(a) for a in (q, k, v, g))
    b = jnp.transpose(beta, (0, 2, 1)).reshape(S, H, nc, CHUNK, 1)
    gamma = jnp.cumsum(g, axis=3)                          # [S,H,nc,C,dk]
    last = gamma[:, :, :, -1:]                             # [S,H,nc,1,dk]
    parts = (q * jnp.exp(gamma), b * k * jnp.exp(gamma),
             k * jnp.exp(last - gamma), b * v)
    parts = tuple(a.reshape(S, H, wp, a.shape[-1]).astype(dt)
                  for a in parts)

    # A and M: rows in sub-chunk i against columns before its first row
    # r_i, folded about r_i, one product a sub-chunk ...
    sub = lambda a: a.reshape(S, H, nc, ns, SUB, dk)
    gs = sub(gamma)
    ref = gs[:, :, :, :, :1]                               # [.., ns, 1, dk]
    near = jnp.exp(gs - ref)                               # t >= r_i: <= 1
    col = jnp.arange(CHUNK)
    before = col[None, :] < (jnp.arange(ns) * SUB)[:, None]     # [ns, C]
    far = jnp.exp(jnp.where(before[None, None, None, :, :, None],
                            ref - gamma[:, :, :, None], -jnp.inf))
    kc = (k[:, :, :, None] * far).astype(dt)               # [.., ns, C, dk]

    def off(rows):
        return jnp.einsum("...ibd,...isd->...ibs", (sub(rows) * near)
                          .astype(dt), kc,
                          preferred_element_type=jnp.float32)

    # ... and the diagonal SUB x SUB blocks a channel at a time
    t_ = jnp.arange(SUB)
    lag = gs[..., :, None, :] - gs[..., None, :, :]        # [.., ns,t,s,dk]
    dec = jnp.exp(jnp.minimum(lag, 0.0))
    ks = sub(k)

    def diag(rows, strict):
        keep = t_[None, :] < t_[:, None] if strict \
            else t_[None, :] <= t_[:, None]
        d = jnp.sum(sub(rows)[..., :, None, :] * ks[..., None, :, :] * dec,
                    axis=-1)
        return jnp.where(keep, d, 0.0)                     # [.., ns, t, s]

    eye = jnp.eye(ns, dtype=jnp.float32)[:, None, :, None]

    def whole(offs, diags):     # [.., ns, SUB, C] + blocks -> [S,H,wp,C]
        blocks = offs.reshape(S, H, nc, ns, SUB, ns, SUB) \
            + diags[..., :, :, None, :] * eye
        return blocks.reshape(S, H, wp, CHUNK)

    lower = b.reshape(S, H, wp, 1) * whole(off(k), diag(k, True))
    within = whole(off(q), diag(q, False))
    end = jnp.exp(last)
    return parts, lower, within, end


# ------------------------------------------------------------ lax path
@jax.jit
def _reference(q, k, v, g, beta, state, srows, pos, lens):
    """Pure-lax twin of both kernels: gather the slots' states, the same
    per-head formulation, scatter them back."""
    S, w, H, dk = q.shape
    dv = v.shape[-1]
    dt = q.dtype
    s_in = jnp.where((pos > 0)[:, None, None, None], state[srows], 0.0)
    if w == 1:
        live = (lens > 0)[:, None]
        decay = jnp.where(live[..., None], jnp.exp(g[:, 0]), 1.0)
        b = jnp.where(live, beta[:, 0], 0.0)[..., None]
        s = decay[..., None] * s_in
        k0, q0, v0 = k[:, 0], q[:, 0], v[:, 0]
        s_op = s.astype(dt)
        held = jnp.einsum("shk,shkv->shv", k0, s_op,
                          preferred_element_type=jnp.float32)
        d = b * (v0.astype(jnp.float32) - held)
        kq = jnp.sum(k0.astype(jnp.float32) * q0.astype(jnp.float32), -1,
                     keepdims=True)
        o = jnp.einsum("shk,shkv->shv", q0, s_op,
                       preferred_element_type=jnp.float32) + d * kq
        s_new = s + jnp.einsum(
            "shk,shv->shkv", k0.astype(jnp.float32),
            d.astype(dt).astype(jnp.float32))
        o = o[:, None]
    else:
        parts, lower, within, end = _decay_products(q, k, v, g, beta, lens)
        wp = lower.shape[2]
        nc = wp // CHUNK
        chunks = tuple(a.reshape(S, H, nc, CHUNK, a.shape[-1])
                       for a in (lower, within) + parts)

        def head(chunks, end, s):
            def step(s, xs):
                *cs, en = xs
                o, s = _chunk_step(*cs, en, s)
                return s, o
            s, o = jax.lax.scan(step, s, (*chunks, end))
            return o.reshape(wp, dv), s

        o, s_new = jax.vmap(jax.vmap(head))(chunks, end, s_in)
        o = jnp.transpose(o, (0, 2, 1, 3))[:, :w]
    return o, state.at[srows].set(s_new)


# --------------------------------------------------------- pallas path
def _step_kernel(srows_ref, pos_ref, q_ref, k_ref, v_ref, decay_ref,
                 beta_ref, s_ref, o_ref, s_out_ref, *, th: int, dt):
    fresh = pos_ref[pl.program_id(0)] <= 0
    for h in range(th):
        s = jnp.where(fresh, 0.0, s_ref[0, h])
        o, s = _step_head(q_ref[0, h:h + 1], k_ref[0, h:h + 1],
                          v_ref[0, h:h + 1], s,
                          _column(decay_ref[0, h:h + 1]),
                          beta_ref[0, h:h + 1], dt)
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1] = o


def _chunk_kernel(srows_ref, pos_ref, lower_ref, within_ref, qg_ref,
                  kbg_ref, ke_ref, vb_ref, end_ref, s_ref, o_ref, s_out_ref,
                  *, nc: int):
    fresh = pos_ref[pl.program_id(0)] <= 0
    s = jnp.where(fresh, 0.0, s_ref[0, 0])
    for c in range(nc):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        o, s = _chunk_step(
            lower_ref[0, 0, rows], within_ref[0, 0, rows],
            qg_ref[0, 0, rows], kbg_ref[0, 0, rows], ke_ref[0, 0, rows],
            vb_ref[0, 0, rows], end_ref[0, 0, c], s)
        o_ref[0, 0, rows] = o
    s_out_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(q, k, v, g, beta, state, srows, pos, lens, *,
                 interpret: bool):
    S, H, dk = q.shape
    dv = v.shape[-1]
    th = _head_tile(H, 8)
    live = (lens > 0)[:, None]
    decay = jnp.where(live[..., None], jnp.exp(g), 1.0)       # [S, H, dk]
    beta = jnp.broadcast_to(jnp.where(live, beta, 0.0)[..., None],
                            (S, H, dv))
    kern = functools.partial(_step_kernel, th=th, dt=q.dtype)
    vec_k = pl.BlockSpec((1, th, dk), lambda s, j, sr, ps: (s, j, 0))
    vec_v = pl.BlockSpec((1, th, dv), lambda s, j, sr, ps: (s, j, 0))
    st = pl.BlockSpec((1, th, dk, dv),
                      lambda s, j, sr, ps: (sr[s], j, 0, 0))
    o, state = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, H // th),
            in_specs=[vec_k, vec_k, vec_v, vec_k, vec_v, st],
            out_specs=[vec_v, st]),
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: srows, pos, q, k, v, decay, beta, state -> state out
        input_output_aliases={7: 1},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=STEP_KERNEL_NAME,
    )(srows, pos, q.astype(jnp.float32), k.astype(jnp.float32),
      v.astype(jnp.float32), decay, beta, state)
    return o, state


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pallas(q, k, v, g, beta, state, srows, pos, lens, *,
                  interpret: bool):
    S, w, H, dk = q.shape
    dv = v.shape[-1]
    parts, lower, within, end = _decay_products(q, k, v, g, beta, lens)
    wp = lower.shape[2]
    nc = wp // CHUNK
    kern = functools.partial(_chunk_kernel, nc=nc)

    def win(lanes):
        return pl.BlockSpec((1, 1, wp, lanes),
                            lambda s, j, sr, ps: (s, j, 0, 0))

    st = pl.BlockSpec((1, 1, dk, dv), lambda s, j, sr, ps: (sr[s], j, 0, 0))
    o, state = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, H),
            in_specs=[win(CHUNK), win(CHUNK), win(dk), win(dk), win(dk),
                      win(dv),
                      pl.BlockSpec((1, 1, nc, 1, dk),
                                   lambda s, j, sr, ps: (s, j, 0, 0, 0)),
                      st],
            out_specs=[win(dv), st]),
        out_shape=[jax.ShapeDtypeStruct((S, H, wp, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: srows, pos, lower, within, four products, end, state
        input_output_aliases={9: 1},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=CHUNK_KERNEL_NAME,
    )(srows, pos, lower, within, *parts, end, state)
    return jnp.transpose(o, (0, 2, 1, 3))[:, :w], state


def kda_rule(q, k, v, g, beta, state, srows, pos, lens, *,
             impl: str | None = None, interpret: bool | None = None):
    """The KDA rule of a window over the state pool. ``q``/``k`` [S, w, H,
    dk] and ``v`` [S, w, H, dv] hold each slot's ``w`` new rows
    (normalised, ``q`` scaled, of the serving type); ``g`` [S, w, H, dk]
    float32 the log decay of each key channel (``<= 0``) and ``beta`` [S,
    w, H] float32 the write strength; ``state`` is ONE layer's pool
    ``[rows, H, dk, dv]`` float32; ``srows`` [S] the slots' rows in it
    (``TRASH_ROW`` for a slot that is not there); ``pos`` [S] the position
    of each slot's first row (0: the state is taken as zero); ``lens`` [S]
    how many of the ``w`` rows are real. Returns ``(o [S, w, H, dv]
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
    g = jnp.asarray(g, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    if impl == "lax":
        return _reference(q, k, v, g, beta, state, srows, pos, lens)
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} is not one of pallas|lax")
    if interpret is None:
        interpret = plat != "tpu"
    if q.shape[1] == 1:
        o, state = _step_pallas(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state, srows,
            pos, lens, interpret=bool(interpret))
        return o[:, None], state
    return _chunk_pallas(q, k, v, g, beta, state, srows, pos, lens,
                         interpret=bool(interpret))
