"""Pallas TPU kernels: the gated delta rule over a per-sequence state pool.

A gated DeltaNet layer caches no entry a token: per head it keeps a STATE
``S`` of ``[key_dim, value_dim]`` float32 a sequence, with

    S <- exp(g_t) S;   d_t = beta_t (v_t - S^T k_t);   S <- S + k_t d_t^T;
    o_t = S^T q_t

where the decay ``g_t <= 0`` and the write strength ``beta_t`` in (0, 1)
are computed from the token (unlike ``dl.pallas_lightning``, whose decay is
a constant a head and which adds ``k v^T`` without looking at what the
state already holds at ``k``). The states of all sequences rest in ONE pool
a layer, ``[rows, heads, key_dim, value_dim]`` float32, which
``dl.paged_kv.PagedKVManager`` hands out by row (row 0 is the trash row).
Both kernels read a slot's state where it rests and write it back in place
(the pool is aliased to the output).

- :data:`STEP_KERNEL_NAME`, the decode step (``w`` = 1): per (slot, tile of
  heads) one read-modify-write of the tile's states. ``k^T S`` and ``q^T
  S`` are ONE product against the decayed state (``S_new^T q = S^T q + d (k
  . q)``, so the read-out needs no second pass over the new state); the
  outer product ``k d^T`` is ``diag(k) @ rows(d)``.
- :data:`CHUNK_KERNEL_NAME`, a window of ``w`` rows (prefill), in chunks of
  ``CHUNK`` = 64 rows as the family's ``chunk_gated_delta_rule``: with
  ``gamma`` the running sum of ``g`` inside a chunk, ``Gamma[t, s] =
  exp(gamma_t - gamma_s)`` for ``s <= t``, ``L = tril(diag(beta) K K^T *
  Gamma, -1)`` and ``T = (I + L)^-1`` (``L`` is nilpotent: ``T = (I -
  L)(I + L^2)(I + L^4)...``, five squarings, in float32),

      V' = T (beta v) - T (beta k exp(gamma)) S        (what is written)
      O  = (q exp(gamma)) S + ((q K^T) * Gamma) V'
      S <- exp(gamma_C) S + (k exp(gamma_C - gamma))^T V'

  The decay products (``Gamma``, the scaled copies of ``q`` and ``k``) are
  elementwise and are made by XLA in front of the kernel; the kernel is the
  products, the inverse and the carried state. Rows at and past ``lens[s]``
  are padding: their ``beta`` and ``g`` are taken as zero, so they neither
  decay the state nor write to it, and the state written back is the state
  after ``lens[s]`` tokens.

A slot at position 0 has no history: its state is taken as ZERO whatever
the row holds. Operands of every product are of the serving type (the state
is rounded to it where it is an operand; it is KEPT and UPDATED in
float32), except the inverse's, which are float32; accumulation is float32.

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
from .pallas_lightning import _head_tile

__all__ = ["gated_delta_rule", "STEP_KERNEL_NAME", "CHUNK_KERNEL_NAME",
           "CHUNK", "TRASH_ROW"]

#: the kernels' own names in a device trace
STEP_KERNEL_NAME = "gated_delta_step"
CHUNK_KERNEL_NAME = "gated_delta_chunk"
#: rows of one chunk of a prefill window
CHUNK = 64
_VMEM_LIMIT = 64 << 20
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


# ------------------------------------------------------------ one head
def _step_head(q, k, v, s, decay, beta, dt):
    """One head's decode step: ``q``/``k`` [1, dk] and ``v`` [1, dv]
    float32 holding numbers of the serving type ``dt``, ``s`` [dk, dv]
    float32, ``decay`` = ``exp(g)`` and ``beta`` [1, dv] float32 (the
    head's scalar on every lane). Returns ``(o [1, dv] float32, the new
    state)``."""
    dk = s.shape[0]
    s = decay * s
    row = jax.lax.broadcasted_iota(jnp.int32, (8, dk), 0)
    both = jnp.where(row == 0, jnp.broadcast_to(k, (8, dk)),
                     jnp.where(row == 1, jnp.broadcast_to(q, (8, dk)), 0.0))
    read = _dot(both.astype(dt), s.astype(dt), ((1,), (0,)))   # [8, dv]
    d = beta * (v - read[0:1])
    o = read[1:2] + d * jnp.sum(k * q, axis=-1, keepdims=True)
    eye = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    kd = jnp.where(eye, jnp.broadcast_to(k, (dk, dk)), 0.0)
    outer = _dot(kd.astype(dt), jnp.broadcast_to(
        d, (dk, d.shape[1])).astype(dt), ((1,), (0,)))          # k d^T
    return o, s + outer


def _chunk_step(q, qg, k, kb, kbg, ke, vb, gam, end, s):
    """One chunk of one head: ``q`` (scaled), ``qg`` = ``q exp(gamma)``,
    ``k``, ``kb`` = ``beta k``, ``kbg`` = ``beta k exp(gamma)``, ``ke`` =
    ``k exp(gamma_C - gamma)`` [C, dk] and ``vb`` = ``beta v`` [C, dv] of
    the serving type; ``gam`` [C, C] float32 the decay mask ``Gamma`` (zero
    above the diagonal); ``end`` [1, dv] float32 = ``exp(gamma_C)`` on
    every lane; ``s`` [dk, dv] float32. Returns ``(o [C, dv] float32, the
    state after the chunk)``."""
    C = q.shape[0]
    dt = q.dtype
    ti = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lower = jnp.where(si < ti, _dot(kb, k, ((1,), (1,))) * gam, 0.0)
    # (I + L)^-1 = (I - L)(I + L^2)(I + L^4)...: L^C = 0
    inv = jnp.where(si == ti, 1.0, 0.0) - lower
    power = -lower
    span = 1
    while span * 2 < C:
        power = _dot(power, power, ((1,), (0,)), _HIGHEST)
        inv = inv + _dot(inv, power, ((1,), (0,)), _HIGHEST)
        span *= 2
    inv = inv.astype(dt)
    s_op = s.astype(dt)
    wrote = _dot(inv, vb, ((1,), (0,))) - _dot(
        _dot(inv, kbg, ((1,), (0,))).astype(dt), s_op, ((1,), (0,)))
    wrote_op = wrote.astype(dt)
    within = _dot(q, k, ((1,), (1,))) * gam
    o = _dot(qg, s_op, ((1,), (0,))) \
        + _dot(within.astype(dt), wrote_op, ((1,), (0,)))
    return o, end * s + _dot(ke, wrote_op, ((0,), (0,)))


def _padded_window(w: int) -> int:
    return -(-w // CHUNK) * CHUNK


def _decay_products(q, k, v, g, beta, lens):
    """What the chunked form multiplies, made elementwise from a window's
    ``q``/``k`` [S, w, H, dk], ``v`` [S, w, H, dv] (serving type), ``g``/
    ``beta`` [S, w, H] float32 and ``lens`` [S]: the window padded to whole
    chunks and laid ``[S, H, wp, ...]``; rows past ``lens`` get ``beta`` =
    ``g`` = 0."""
    S, w, H, dk = q.shape
    dt = q.dtype
    wp = _padded_window(w)
    nc = wp // CHUNK
    real = (jnp.arange(wp)[None, :] < lens[:, None])[:, :, None]   # [S,wp,1]
    pad3 = ((0, 0), (0, wp - w), (0, 0))
    g = jnp.where(real, jnp.pad(g, pad3), 0.0)
    beta = jnp.where(real, jnp.pad(beta, pad3), 0.0)
    pad4 = pad3 + ((0, 0),)
    q, k, v = (jnp.pad(a, pad4).astype(jnp.float32) for a in (q, k, v))
    gamma = jnp.cumsum(g.reshape(S, nc, CHUNK, H), axis=2)
    last = gamma[:, :, -1:, :]                                # [S,nc,1,H]
    lag = gamma[:, :, :, None, :] - gamma[:, :, None, :, :]   # [S,nc,t,s,H]
    keep = (jnp.arange(CHUNK)[:, None] >= jnp.arange(CHUNK)[None, :])
    gam = jnp.where(keep[None, None, :, :, None], jnp.exp(
        jnp.minimum(lag, 0.0)), 0.0)
    gam = jnp.transpose(gam, (0, 4, 1, 2, 3)).reshape(S, H, wp, CHUNK)
    eg = jnp.exp(gamma).reshape(S, wp, H, 1)
    left = jnp.exp(last - gamma).reshape(S, wp, H, 1)
    b = beta[..., None]
    heads_first = lambda a: jnp.transpose(a, (0, 2, 1, 3)).astype(dt)
    parts = tuple(heads_first(a) for a in (
        q, q * eg, k, k * b, k * b * eg, k * left, v * b))
    end = jnp.transpose(jnp.exp(last[:, :, 0, :]), (0, 2, 1))  # [S,H,nc]
    return parts, gam, end


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
        decay = jnp.where(live, jnp.exp(g[:, 0]), 1.0)[..., None, None]
        b = jnp.where(live, beta[:, 0], 0.0)[..., None]
        s = decay * s_in
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
        parts, gam, end = _decay_products(q, k, v, g, beta, lens)
        wp = gam.shape[2]
        nc = wp // CHUNK
        chunks = tuple(a.reshape(S, H, nc, CHUNK, a.shape[-1])
                       for a in parts)
        gam = gam.reshape(S, H, nc, CHUNK, CHUNK)
        end_rows = jnp.broadcast_to(end[..., None, None], (S, H, nc, 1, dv))

        def head(chunks, gam, end_rows, s):
            def step(s, xs):
                *cs, gm, en = xs
                o, s = _chunk_step(*cs, gm, en, s)
                return s, o
            s, o = jax.lax.scan(step, s, (*chunks, gam, end_rows))
            return o.reshape(wp, dv), s

        o, s_new = jax.vmap(jax.vmap(head))(chunks, gam, end_rows, s_in)
        o = jnp.transpose(o, (0, 2, 1, 3))[:, :w]
    return o, state.at[srows].set(s_new)


# --------------------------------------------------------- pallas path
def _step_kernel(srows_ref, pos_ref, q_ref, k_ref, v_ref, decay_ref,
                 beta_ref, s_ref, o_ref, s_out_ref, *, th: int, dt):
    s_idx = pl.program_id(0)
    fresh = pos_ref[s_idx] <= 0
    for h in range(th):
        s = jnp.where(fresh, 0.0, s_ref[0, h])
        o, s = _step_head(q_ref[0, h:h + 1], k_ref[0, h:h + 1],
                          v_ref[0, h:h + 1], s, decay_ref[0, h:h + 1],
                          beta_ref[0, h:h + 1], dt)
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1] = o


def _chunk_kernel(srows_ref, pos_ref, q_ref, qg_ref, k_ref, kb_ref, kbg_ref,
                  ke_ref, vb_ref, gam_ref, end_ref, s_ref, o_ref, s_out_ref,
                  *, nc: int):
    s_idx = pl.program_id(0)
    fresh = pos_ref[s_idx] <= 0
    s = jnp.where(fresh, 0.0, s_ref[0, 0])
    for c in range(nc):
        rows = slice(c * CHUNK, (c + 1) * CHUNK)
        o, s = _chunk_step(
            q_ref[0, 0, rows], qg_ref[0, 0, rows], k_ref[0, 0, rows],
            kb_ref[0, 0, rows], kbg_ref[0, 0, rows], ke_ref[0, 0, rows],
            vb_ref[0, 0, rows], gam_ref[0, 0, rows], end_ref[0, 0, c], s)
        o_ref[0, 0, rows] = o
    s_out_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(q, k, v, g, beta, state, srows, pos, lens, *,
                 interpret: bool):
    S, H, dk = q.shape
    dv = v.shape[-1]
    th = _head_tile(H, 8)
    live = (lens > 0)[:, None]
    decay = jnp.broadcast_to(
        jnp.where(live, jnp.exp(g), 1.0)[..., None], (S, H, dv))
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
            in_specs=[vec_k, vec_k, vec_v, vec_v, vec_v, st],
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
    parts, gam, end = _decay_products(q, k, v, g, beta, lens)
    wp = gam.shape[2]
    nc = wp // CHUNK
    end_rows = jnp.broadcast_to(end[..., None, None], (S, H, nc, 1, dv))
    kern = functools.partial(_chunk_kernel, nc=nc)

    def win(lanes):
        return pl.BlockSpec((1, 1, wp, lanes),
                            lambda s, j, sr, ps: (s, j, 0, 0))

    st = pl.BlockSpec((1, 1, dk, dv), lambda s, j, sr, ps: (sr[s], j, 0, 0))
    o, state = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, H),
            in_specs=[win(dk)] * 6 + [
                win(dv), win(CHUNK),
                pl.BlockSpec((1, 1, nc, 1, dv),
                             lambda s, j, sr, ps: (s, j, 0, 0, 0)),
                st],
            out_specs=[win(dv), st]),
        out_shape=[jax.ShapeDtypeStruct((S, H, wp, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: srows, pos, seven products, gamma, end, state -> state
        input_output_aliases={11: 1},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=CHUNK_KERNEL_NAME,
    )(srows, pos, *parts, gam, end_rows, state)
    return jnp.transpose(o, (0, 2, 1, 3))[:, :w], state


def gated_delta_rule(q, k, v, g, beta, state, srows, pos, lens, *,
                     impl: str | None = None,
                     interpret: bool | None = None):
    """The gated delta rule of a window over the state pool. ``q``/``k``
    [S, w, H, dk] and ``v`` [S, w, H, dv] hold each slot's ``w`` new rows
    (normalised, ``q`` scaled, a key head repeated for the value heads it
    serves, of the serving type); ``g`` [S, w, H] float32 the log decay
    (``<= 0``) and ``beta`` [S, w, H] float32 the write strength; ``state``
    is ONE layer's pool ``[rows, H, dk, dv]`` float32; ``srows`` [S] the
    slots' rows in it (``TRASH_ROW`` for a slot that is not there); ``pos``
    [S] the position of each slot's first row (0: the state is taken as
    zero); ``lens`` [S] how many of the ``w`` rows are real. Returns ``(o
    [S, w, H, dv] float32, the pool with every slot's state after its real
    rows)``.

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
