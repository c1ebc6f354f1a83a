"""Pallas TPU kernel: fused flash attention (forward).

The long-context encoder's hot op. The XLA formulation
(``text_encoder._dense_attention``) materializes the [T, T] score matrix
in HBM — at T=2048, B=32, H=8 that is 4 GB of f32 score traffic per
layer, and HBM bandwidth, not the MXU, bounds throughput. The TPU-native
formulation streams K/V blocks through VMEM with a running-softmax
accumulator (same math as ``parallel/ring_attention._block_update``), so
scores never leave the chip:

    grid = (B*H, T/block_q, T/block_k), k-blocks innermost
    per (q-block, k-block) cell:  s = q k^T on the MXU,
        online max/denominator update in VMEM scratch,
        acc += softmax-weights @ v on the MXU
    emit acc / l once per q-block on the last k step.

Backward runs the blockwise (XLA) formulation via recompute — inference
is the featurizer's hot path; training pays one extra forward.

Tiling: q/k/v blocks keep head_dim on the lane axis (pads to 128 lanes
below head_dim 128 — run heads at 64 or 128 wide for best effect), and
the running max/denominator ride a (block_q, 128) f32 scratch so their
updates stay VPU-shaped. Mask handling matches the dense path bit-wise:
fully-masked rows emit zeros.

No reference counterpart (SURVEY §5: long-context is "absent in the
reference") — this kernel serves the framework's first-class extension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.compat import tpu_compiler_params as _CompilerParams

from ..utils.platform import target_platform  # noqa: F401 (re-export)

_NEG = -1e30  # additive mask value; -inf breaks the running-max algebra


def _allowed_2d(mask_ref, off_ref, shape, qb_idx, kb_idx, causal: bool):
    """[BQ, BK] validity: key mask (row-broadcast) ∧, when causal, the
    lower-triangular position constraint from GLOBAL positions —
    per-call offset (``off_ref`` [1, 2] = (q_off, k_off), traced: ring
    attention passes each step's shard offsets) + block index × block
    size + in-block iota on each axis."""
    # 2-D [1, BK] load — a 1-D vector load here crashes the Mosaic
    # layout pass ("arr.size() >= layout_rank")
    valid = mask_ref[0] != 0
    if not causal:
        return jnp.broadcast_to(valid, shape)
    qpos = off_ref[0, 0] + qb_idx * shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)
    kpos = off_ref[0, 1] + kb_idx * shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1)
    return valid & (kpos <= qpos)


def _block_reachable(off_ref, bq: int, bk: int, qb_idx, kb_idx,
                     causal: bool):
    """False iff EVERY (q, k) pair in this grid cell is above the
    causal diagonal — such cells contribute exactly zero and their MXU
    work can be skipped (the ~2x causal saving). Dynamic predicate, so
    it composes with traced ring offsets."""
    if not causal:
        return True
    first_q = off_ref[0, 0] + qb_idx * bq        # smallest q position
    first_k = off_ref[0, 1] + kb_idx * bk        # smallest k position
    return first_k <= first_q + bq - 1


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, off_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float,
                  causal: bool = False):
    """One (bh, q-block, k-block) grid cell of the online softmax."""
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_block_reachable(off_ref, q_ref.shape[1], k_ref.shape[1],
                              qb, kb, causal))
    def _compute():
        q = q_ref[0]                               # [BQ, D]
        k = k_ref[0]                               # [BK, D]
        s = jax.lax.dot_general(                   # [BQ, BK] f32 on MXU
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        allowed = _allowed_2d(mask_ref, off_ref, s.shape, qb, kb,
                              causal)
        s = jnp.where(allowed, s, _NEG)

        m_prev = m_scr[:, :1]                      # [BQ, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [BQ, BK]
        # a fully-masked block: every s is _NEG and m_new is _NEG, so
        # p = exp(0) = 1 row-wide — kill it with the validity mask
        p = jnp.where(allowed, p, 0.0)
        corr = jnp.exp(m_prev - m_new)             # [BQ, 1]
        l_scr[:, :1] = l_prev * corr \
            + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:, :1] = m_new
        # p rounds to the value dtype before the MXU pass — bit-matching
        # the dense path's ``p.astype(v.dtype)`` (text_encoder.py:48)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:, :1], 1e-35)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def _flash_kernel_lse(q_ref, k_ref, v_ref, mask_ref, off_ref, o_ref,
                      lse_ref, m_scr, l_scr, acc_scr, *, scale: float,
                      causal: bool = False):
    """Forward cell that additionally emits the logsumexp row stats the
    fused backward needs (same math as ``_flash_kernel``)."""
    _flash_kernel(q_ref, k_ref, v_ref, mask_ref, off_ref, o_ref,
                  m_scr, l_scr, acc_scr, scale=scale, causal=causal)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == nk - 1)
    def _emit_lse():
        l = jnp.maximum(l_scr[:, :1], 1e-35)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _flash_kernel_causal_packed(q_ref, k_ref, v_ref, mask_ref, off_ref,
                                o_ref, *maybe_lse, scale: float,
                                bk: int, with_lse: bool):
    """Causal forward with REAL grid pruning: one grid cell per
    (bh, q-block), K/V resident whole-row in VMEM, and a
    ``fori_loop`` over ONLY the reachable k-blocks — above-diagonal
    blocks are never fetched, never launched, never masked. The
    streaming-grid kernel (``_flash_kernel``) skips their MXU work via
    ``pl.when`` but still runs their grid slots and block copies; this
    kernel removes the slots themselves (the true ~2x causal saving),
    at the cost of requiring K/V to fit VMEM — the fallback below keeps
    the streaming path for longer T (and the sharded ring/ulysses
    variants shrink per-device T long before that matters)."""
    lse_ref = maybe_lse[0] if with_lse else None
    qb = pl.program_id(1)
    bq = q_ref.shape[1]
    nk = k_ref.shape[1] // bk
    # reachable bound from GLOBAL positions (traced ring offsets ride
    # off_ref exactly as in the streaming kernel)
    last_q = off_ref[0, 0] + qb * bq + bq - 1
    n_reach = jnp.clip((last_q - off_ref[0, 1]) // bk + 1, 0, nk)

    q = q_ref[0]                                   # [BQ, D]

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(kb * bk, bk), :]        # [BK, D]
        v = v_ref[0, pl.ds(kb * bk, bk), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        valid = mask_ref[0, :, pl.ds(kb * bk, bk)] != 0   # [1, BK]
        qpos = off_ref[0, 0] + qb * bq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        kpos = off_ref[0, 1] + kb * bk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        allowed = valid & (kpos <= qpos)
        s = jnp.where(allowed, s, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(allowed, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    D = q_ref.shape[2]
    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_reach, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-35)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    if with_lse:
        lse_ref[0] = m + jnp.log(l_safe)


# K+V whole-row VMEM budget for the packed causal kernel; beyond this
# the streaming grid takes over (VMEM is ~16 MiB/core — leave room for
# q/o blocks, scratch, and double-buffering)
_PACKED_KV_BYTES = 4 * 1024 * 1024

# per-block K (and V) VMEM budget for the AUTO block_k choice below
_AUTO_BK_BYTES = 512 * 1024


def _resolve_block_k(block_k, k, causal: bool) -> int:
    """Default block_k. The k-block size IS the contraction dim of the
    p·V matmul, so on the MXU bigger is directly faster: a v5e sweep at
    T=2048/D=64 measured 554 encoder seqs/s at bk=2048 (single k-block,
    one-pass softmax) vs 368 at the old fixed 512 (+51%). Auto picks
    the whole padded row when a K block fits ``_AUTO_BK_BYTES``, else
    the largest 128-multiple that does. CAUSAL keeps 512: bk is the
    pruning granularity there, and coarse blocks forfeit the ~2x
    triangle saving (measured 1.57x at T=2048 with bk=512)."""
    if block_k is not None:
        return block_k
    if causal:
        return 512
    T, D = k.shape[2], k.shape[3]
    tk = -(-T // 128) * 128               # padded row length
    budget = _AUTO_BK_BYTES // max(D * k.dtype.itemsize, 1)
    # hard 2048 cap: the fused BACKWARD holds several [block_q, bk]
    # f32 intermediates (s/p/dp/ds) in VMEM — 2048 is measured to
    # compile and win on v5e; 4096 would put ~16 MB of score blocks in
    # a ~16 MB VMEM
    return max(min(tk, budget // 128 * 128, 2048), 512)


def _flash_pack(q, k, v, key_mask, block_q, block_k):
    """Shared padding/reshape for forward and backward kernels."""
    B, H, T, D = q.shape
    bq = min(block_q, max(8, T))
    bk = min(block_k, max(128, T))
    qp = (-T) % bq
    kp = (-T) % bk
    qf = jnp.pad(q.reshape(B * H, T, D), ((0, 0), (0, qp), (0, 0)))
    kf = jnp.pad(k.reshape(B * H, T, D), ((0, 0), (0, kp), (0, 0)))
    vf = jnp.pad(v.reshape(B * H, T, D), ((0, 0), (0, kp), (0, 0)))
    # [B, T] bool → [B*H, 1, Tk] i8, padded keys invalid. The unit
    # middle axis is load-bearing on TPU: Mosaic requires a block's
    # last-two dims to be (8k, 128k) or match the array, and a
    # per-(b,h) mask row can only block as (1, bk) if the sublane axis
    # is a real size-1 array dim.
    mask = jnp.broadcast_to(key_mask[:, None, :], (B, H, T)) \
        .reshape(B * H, T).astype(jnp.int8)
    mask = jnp.pad(mask, ((0, 0), (0, kp)))[:, None, :]
    return qf, kf, vf, mask, (B, H, T, D, bq, bk, qp, kp)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret",
                                    "with_lse", "causal"))
def _flash_forward(q, k, v, key_mask, offs=None, *, block_q: int = 256,
                   block_k: int = 512, interpret: bool = False,
                   with_lse: bool = False, causal: bool = False):
    qf, kf, vf, mask, (B, H, T, D, bq, bk, qp, kp) = _flash_pack(
        q, k, v, key_mask, block_q, block_k)
    scale = D ** -0.5
    nq, nk = (T + qp) // bq, (T + kp) // bk
    if offs is None:
        offs = jnp.zeros((1, 2), jnp.int32)
    kv_bytes = 2 * (T + kp) * D * k.dtype.itemsize
    if causal and kv_bytes <= _PACKED_KV_BYTES:
        # pruned-grid causal path: grid cells exist only per q-block;
        # reachable k-blocks iterate INSIDE the cell, so above-diagonal
        # work is never launched at all
        packed_specs = [
            pl.BlockSpec((1, bq, D), lambda b, iq: (b, iq, 0)),
            pl.BlockSpec((1, T + kp, D), lambda b, iq: (b, 0, 0)),
            pl.BlockSpec((1, T + kp, D), lambda b, iq: (b, 0, 0)),
            pl.BlockSpec((1, 1, T + kp), lambda b, iq: (b, 0, 0)),
            pl.BlockSpec((1, 2), lambda b, iq: (0, 0)),
        ]
        o_spec = pl.BlockSpec((1, bq, D), lambda b, iq: (b, iq, 0))
        o_shape = jax.ShapeDtypeStruct((B * H, T + qp, D), v.dtype)
        params = _CompilerParams(
            dimension_semantics=("parallel", "parallel"))
        kern = functools.partial(_flash_kernel_causal_packed,
                                 scale=scale, bk=bk, with_lse=with_lse)
        if with_lse:
            out, lse = pl.pallas_call(
                kern, grid=(B * H, nq), in_specs=packed_specs,
                out_specs=(o_spec,
                           pl.BlockSpec((1, bq, 1),
                                        lambda b, iq: (b, iq, 0))),
                out_shape=(o_shape,
                           jax.ShapeDtypeStruct((B * H, T + qp, 1),
                                                jnp.float32)),
                compiler_params=params, interpret=interpret,
            )(qf, kf, vf, mask, offs)
            return (out[:, :T].reshape(B, H, T, D),
                    lse[:, :T, 0].reshape(B, H, T))
        out = pl.pallas_call(
            kern, grid=(B * H, nq), in_specs=packed_specs,
            out_specs=o_spec, out_shape=o_shape,
            compiler_params=params, interpret=interpret,
        )(qf, kf, vf, mask, offs)
        return out[:, :T].reshape(B, H, T, D)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, iq, ik: (b, iq, 0)),
        pl.BlockSpec((1, bk, D), lambda b, iq, ik: (b, ik, 0)),
        pl.BlockSpec((1, bk, D), lambda b, iq, ik: (b, ik, 0)),
        pl.BlockSpec((1, 1, bk), lambda b, iq, ik: (b, 0, ik)),
        pl.BlockSpec((1, 2), lambda b, iq, ik: (0, 0)),
    ]
    o_spec = pl.BlockSpec((1, bq, D), lambda b, iq, ik: (b, iq, 0))
    o_shape = jax.ShapeDtypeStruct((B * H, T + qp, D), v.dtype)
    scratch = [
        pltpu.VMEM((bq, 128), jnp.float32),   # running max
        pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
        pltpu.VMEM((bq, D), jnp.float32),     # output accumulator
    ]
    params = _CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    if with_lse:
        out, lse = pl.pallas_call(
            functools.partial(_flash_kernel_lse, scale=scale,
                              causal=causal),
            grid=(B * H, nq, nk),
            in_specs=in_specs,
            out_specs=(o_spec,
                       pl.BlockSpec((1, bq, 1),
                                    lambda b, iq, ik: (b, iq, 0))),
            out_shape=(o_shape,
                       jax.ShapeDtypeStruct((B * H, T + qp, 1),
                                            jnp.float32)),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
        )(qf, kf, vf, mask, offs)
        return (out[:, :T].reshape(B, H, T, D),
                lse[:, :T, 0].reshape(B, H, T))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal),
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=o_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, mask, offs)
    return out[:, :T].reshape(B, H, T, D)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, off_ref, do_ref,
                   lse_ref, dsum_ref, dq_ref, dq_scr, *, scale: float,
                   causal: bool = False):
    """dq = Σ_k ds·K with ds = p·(dp − D)·scale, p = exp(s − lse)."""
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_block_reachable(off_ref, q_ref.shape[1], k_ref.shape[1],
                              qb, kb, causal))
    def _compute():
        q = q_ref[0]                               # [BQ, D]
        k = k_ref[0]                               # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        allowed = _allowed_2d(mask_ref, off_ref, s.shape, qb, kb,
                              causal)
        p = jnp.exp(s - lse_ref[0])                # lse [BQ, 1] bcasts
        p = jnp.where(allowed, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(                  # [BQ, BK]
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dsum_ref[0]) * scale        # dsum [BQ, 1]
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, mask_ref, off_ref, q_ref, do_ref,
                    lse_ref, dsum_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale: float, causal: bool = False):
    """dv = Σ_q pᵀ·dO; dk = Σ_q dsᵀ·Q — accumulated over q blocks."""
    ikb = pl.program_id(1)
    qb = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_block_reachable(off_ref, q_ref.shape[1], k_ref.shape[1],
                              qb, ikb, causal))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        # grid here is (bh, k-block, q-block): q index is program_id(2)
        allowed = _allowed_2d(mask_ref, off_ref, s.shape, qb, ikb,
                              causal)
        p = jnp.exp(s - lse_ref[0])
        p = jnp.where(allowed, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(  # pᵀ [BK,BQ] · dO
            p.astype(do_ref.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dsum_ref[0]) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(  # dsᵀ [BK,BQ] · Q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret",
                                    "causal"))
def _flash_backward(q, k, v, key_mask, o, lse, g, dlse=None,
                    offs=None, *, block_q: int = 256,
                    block_k: int = 512, interpret: bool = False,
                    causal: bool = False):
    """Fused FlashAttention-2-style backward: recompute p per block from
    the saved logsumexp, never materializing [T, T] in HBM.

    ``dlse``: cotangent of the logsumexp output (the lse-returning
    variant). ∂lse/∂s_j = p_j folds into the D-term: ds = p·(dp − (D −
    dlse))·scale."""
    qf, kf, vf, mask, (B, H, T, D, bq, bk, qp, kp) = _flash_pack(
        q, k, v, key_mask, block_q, block_k)
    scale = D ** -0.5
    gf = jnp.pad(g.reshape(B * H, T, D), ((0, 0), (0, qp), (0, 0)))
    # D_i = Σ_d dO·O per row; zero for padded rows since g pads with 0
    dsum = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1)                                 # [B, H, T]
    if dlse is not None:
        dsum = dsum - dlse.astype(jnp.float32)
    dsum = jnp.pad(dsum.reshape(B * H, T),
                   ((0, 0), (0, qp)))[..., None]            # [BH, Tq, 1]
    lse_f = jnp.pad(lse.reshape(B * H, T), ((0, 0), (0, qp)),
                    constant_values=0.0)[..., None]      # [BH, Tq, 1]
    nq, nk = (T + qp) // bq, (T + kp) // bk
    if offs is None:
        offs = jnp.zeros((1, 2), jnp.int32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, 2), lambda b, iq, ik: (0, 0)),
            pl.BlockSpec((1, bq, D), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, iq, ik: (b, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T + qp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, mask, offs, gf, lse_f, dsum)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal),
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bk, D), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, ik, iq: (b, 0, ik)),
            pl.BlockSpec((1, 2), lambda b, ik, iq: (0, 0)),
            pl.BlockSpec((1, bq, D), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, bq, D), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ik, iq: (b, iq, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, D), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda b, ik, iq: (b, ik, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B * H, T + kp, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T + kp, D), v.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kf, vf, mask, offs, qf, gf, lse_f, dsum)

    return (dq[:, :T].reshape(B, H, T, D),
            dk[:, :T].reshape(B, H, T, D),
            dv[:, :T].reshape(B, H, T, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, key_mask, offs, block_q, block_k, interpret,
           bwd_impl, causal):
    return _flash_forward(q, k, v, key_mask, offs, block_q=block_q,
                          block_k=block_k, interpret=interpret,
                          causal=causal)


def _flash_fwd(q, k, v, key_mask, offs, block_q, block_k, interpret,
               bwd_impl, causal):
    # forward-for-gradient also emits the logsumexp row stats, but only
    # when the fused backward will actually consume them — the blockwise
    # backward recomputes from q/k/v and would otherwise pin out+lse in
    # the residuals for nothing
    fused_bwd = bwd_impl == "pallas" or (bwd_impl == "auto"
                                         and not interpret)
    if fused_bwd:
        out, lse = _flash_forward(q, k, v, key_mask, offs,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret, with_lse=True,
                                  causal=causal)
        return out, (q, k, v, key_mask, offs, out, lse)
    out = _flash_forward(q, k, v, key_mask, offs, block_q=block_q,
                         block_k=block_k, interpret=interpret,
                         causal=causal)
    return out, (q, k, v, key_mask, offs, None, None)


def _flash_bwd(block_q, block_k, interpret, bwd_impl, causal, res, g):
    q, k, v, key_mask, offs, out, lse = res
    if bwd_impl == "pallas" or (bwd_impl == "auto" and not interpret):
        # fused FA2-style backward: per-block p recomputed from the
        # saved logsumexp, [T, T] never touches HBM
        dq, dk, dv = _flash_backward(q, k, v, key_mask, out, lse, g,
                                     offs=offs, block_q=block_q,
                                     block_k=block_k,
                                     interpret=interpret, causal=causal)
        return dq, dk, dv, None, None
    # recompute-based backward through the XLA blockwise formulation:
    # same math, O(T) memory — with the causal mask's global-position
    # offsets threaded through (the ring path's shard coordinates)
    from ..parallel.ring_attention import blockwise_attention

    def ref(q, k, v):
        return blockwise_attention(q, k, v, block_size=block_k,
                                   key_mask=key_mask, causal=causal,
                                   q_offset=offs[0, 0],
                                   k_offset=offs[0, 1])

    _, vjp = jax.vjp(ref, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_lse(q, k, v, key_mask, offs, block_q, block_k, interpret,
               causal):
    return _flash_forward(q, k, v, key_mask, offs, block_q=block_q,
                          block_k=block_k, interpret=interpret,
                          with_lse=True, causal=causal)


def _flash_lse_fwd(q, k, v, key_mask, offs, block_q, block_k, interpret,
                   causal):
    out, lse = _flash_forward(q, k, v, key_mask, offs, block_q=block_q,
                              block_k=block_k, interpret=interpret,
                              with_lse=True, causal=causal)
    return (out, lse), (q, k, v, key_mask, offs, out, lse)


# test hook: force the fused backward through the interpreter so the
# dlse kernel math is exercised off-TPU (tiny shapes only — slow)
_FORCE_FUSED_LSE_BWD = False


def _flash_lse_bwd(block_q, block_k, interpret, causal, res, cots):
    g, dlse = cots
    q, k, v, key_mask, offs, out, lse = res
    if not interpret or _FORCE_FUSED_LSE_BWD:
        dq, dk, dv = _flash_backward(q, k, v, key_mask, out, lse, g,
                                     dlse=dlse, offs=offs,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret, causal=causal)
        return dq, dk, dv, None, None
    # off-TPU: XLA recompute through the blockwise (o, lse) reference
    # with the causal offsets threaded through — the interpreted Pallas
    # backward would crawl (tests force it via _FORCE_FUSED_LSE_BWD)
    from ..parallel.ring_attention import blockwise_attention

    def ref(q, k, v):
        return blockwise_attention(q, k, v, block_size=block_k,
                                   key_mask=key_mask, causal=causal,
                                   q_offset=offs[0, 0],
                                   k_offset=offs[0, 1],
                                   return_lse=True)

    _, vjp = jax.vjp(ref, q, k, v)
    dq, dk, dv = vjp((g, dlse))
    return dq, dk, dv, None, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _pack_offs(q_offset, k_offset):
    return jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)]).reshape(1, 2)


def _tuned_blocks(T: int, D: int, causal: bool,
                  platform: str) -> tuple[int, int] | None:
    """Autotuned (block_q, block_k) for this (shape-bucket, platform)
    from the offline winner registry (``perf.autotune``, ISSUE 12), or
    None when untuned — the hand-picked defaults apply then, so an
    untuned shape behaves exactly as before. The lookup is a plain
    dict read: flash_attention runs at jit trace time inside jitted
    encoders, where locks/IO/clock are trace-safety hazards."""
    try:
        from ..perf import autotune
    except Exception:  # pragma: no cover - perf layer optional
        return None
    w = autotune.kernel_winner("flash_attention",
                               autotune.attn_key(T, D, causal), platform)
    if not w:
        return None
    try:
        return int(w["block_q"]), int(w["block_k"])
    except (KeyError, TypeError, ValueError):
        return None


def _resolve_blocks(q, k, block_q, block_k, causal: bool,
                    platform: str) -> tuple[int, int]:
    """Final (block_q, block_k): explicit caller values win; otherwise
    the autotuned winner for this shape bucket; otherwise the measured
    hand-picked defaults (256 / ``_resolve_block_k`` auto)."""
    tuned = None
    if block_q is None or block_k is None:
        tuned = _tuned_blocks(int(q.shape[2]), int(q.shape[3]),
                              bool(causal), platform)
    if block_q is None:
        block_q = tuned[0] if tuned else 256
    if block_k is None and tuned is not None:
        block_k = tuned[1]
    return int(block_q), _resolve_block_k(block_k, k, causal)


def flash_attention_lse(q, k, v, key_mask=None, *,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool | None = None,
                        causal: bool = False, q_offset=0, k_offset=0):
    """Flash attention that also returns the per-row logsumexp of the
    scaled scores — the merge statistic ring attention needs to combine
    per-shard partial attentions. Returns ``(o [B,H,T,D], lse [B,H,T])``;
    fully-masked rows report lse ≈ -1e30 (their o is zero), which the
    standard lse-merge treats as an empty contribution. Differentiable
    in both outputs (fused Pallas backward).

    ``causal`` masks GLOBAL positions ``offset + index`` — the
    (possibly traced) ``q_offset``/``k_offset`` let sequence-sharded
    callers (the causal ring) express each shard's true coordinates.

    ``block_q``/``block_k`` default to the autotuned winner for this
    (shape-bucket, platform) when one is registered (``perf.autotune``),
    else the measured hand-picked tiles — explicit values always win."""
    plat = target_platform()
    if interpret is None:
        interpret = plat != "tpu"
    if key_mask is None:
        key_mask = jnp.ones((q.shape[0], q.shape[2]), bool)
    block_q, block_k = _resolve_blocks(q, k, block_q, block_k, causal,
                                       plat)
    return _flash_lse(q, k, v, key_mask, _pack_offs(q_offset, k_offset),
                      block_q, block_k, bool(interpret), bool(causal))


def flash_attention(q, k, v, key_mask=None, *,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None,
                    bwd_impl: str = "auto", causal: bool = False,
                    q_offset=0, k_offset=0):
    """Fused flash attention. q/k/v [B, H, T, D]; ``key_mask`` [B, T]
    bool (True = valid). Off-TPU it runs the Pallas interpreter (slow —
    tests only); the XLA ``blockwise`` impl is the right CPU choice.

    ``bwd_impl``: "auto" uses the fused Pallas backward on TPU and the
    XLA blockwise recompute elsewhere; "pallas"/"blockwise" force one
    (tests force "pallas" under the interpreter).

    ``causal``: lower-triangular masking from GLOBAL positions
    (``offset + index``; offsets may be traced — sequence-sharded
    callers pass shard coordinates), fused into both forward and
    backward kernels. The forward PRUNES the grid outright when K/V
    fit the VMEM budget (one cell per q-block, an inner loop over only
    reachable k-blocks — above-diagonal work never launches); longer
    sequences and the backward fall back to the streaming grid with a
    ``pl.when`` reachability skip. The saving is the pruned-cell
    fraction and trades against k-block width (the non-causal path
    auto-sizes bk to the whole row; causal keeps bk=512 as its pruning
    granularity). What causal buys at a given length is not measured
    on the current code (``bench.py`` flashcausal rows).

    ``block_q``/``block_k`` default to the autotuned winner for this
    (shape-bucket, platform) when one is registered (``perf.autotune``),
    else the measured hand-picked tiles — explicit values always win.
    """
    plat = target_platform()
    if interpret is None:
        interpret = plat != "tpu"
    if bwd_impl not in ("auto", "pallas", "blockwise"):
        raise ValueError(f"bwd_impl={bwd_impl!r} is not one of "
                         "auto|pallas|blockwise")
    if key_mask is None:
        key_mask = jnp.ones((q.shape[0], q.shape[2]), bool)
    block_q, block_k = _resolve_blocks(q, k, block_q, block_k, causal,
                                       plat)
    return _flash(q, k, v, key_mask, _pack_offs(q_offset, k_offset),
                  block_q, block_k, bool(interpret), bwd_impl,
                  bool(causal))
