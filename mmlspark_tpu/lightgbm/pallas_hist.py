"""Pallas TPU kernel: masked histogram build.

The hot op of GBDT training (SURVEY §7.4 hard part #1): accumulate
(grad, hess, count) into per-(feature, bin) cells. XLA lowers the
scatter-add formulation poorly on TPU (serialized updates); the TPU-native
formulation is a one-hot contraction on the MXU:

    for each feature f, row block R:
        onehot[b, r] = (bins[f, r] == b)           # [B, block] VPU compare
        hist[f] += vals^T @ onehot^T               # [3, B] MXU contraction

Tiling obeys the mosaic constraint that a block's last two dims be
(8k, 128m) or span the array: bins are laid out [F, n] and blocked
(8 features, block_rows); the output is [F, 3, B] so its last two dims
span (3, num_bins) exactly, and the contraction keeps the wide bin axis
on the 128-lane dimension. Grid = (F/8, row_blocks); each feature-block's
output accumulates across the row-block grid dimension (revisited output
block, init on first visit).

``count`` (scalar-prefetch arg) makes the kernel's compute proportional
to the occupied prefix of the row buffer: row blocks past ``count`` skip
their MXU work (their DMA still runs). It exists for callers that
compact rows to the front; the dense engine deliberately does NOT —
measured on v5e the kernel is DMA/overhead-bound, and a
``nonzero``+gather compaction per split costs ~1000x more than the full
masked scan it would save (see ``engine.local_hist``).

Used automatically by the trainer when running on TPU; the scatter-add
path remains the CPU/interpret fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FEAT_BLOCK = 8          # default feature-block tile (autotunable)
DEFAULT_BLOCK_ROWS = 2048


def _hist_kernel(count_ref, bins_ref, vals_ref, out_ref, *,
                 num_bins: int, block_rows: int, feat_block: int):
    """One (feature-block, row-block) cell: accumulate one-hot contraction
    for ``feat_block`` features at once; skip blocks past the occupied
    prefix."""
    rb = pl.program_id(1)

    @pl.when(rb == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(rb * block_rows < count_ref[0])
    def _compute():
        vals_t = vals_ref[:]                   # [3, block] f32 (sublanes)
        block = vals_t.shape[1]
        ids = jax.lax.broadcasted_iota(jnp.int32, (num_bins, block), 0)
        for i in range(feat_block):            # unrolled MXU calls
            onehot = (bins_ref[i:i + 1, :] == ids).astype(jnp.float32)
            # vals [3, block] × onehot [B, block] contracted over rows →
            # [3, B]: the wide bin axis rides the 128-lane dimension.
            # DEFAULT precision: the one-hot operand is exact in bf16, so
            # only vals round (~1e-3 rel) — statistically negligible for
            # split gains, and 2x faster than HIGHEST (measured on v5e).
            acc = jax.lax.dot_general(
                vals_t, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            out_ref[i] = out_ref[i] + acc


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows",
                                    "feat_block", "interpret"))
def _hist_call(bins, vals, count, *, num_bins: int, block_rows: int,
               feat_block: int, interpret: bool) -> jnp.ndarray:
    n, F = bins.shape
    n_pad = (-n) % block_rows
    f_pad = (-F) % feat_block
    # pad bins with an out-of-range id so padded rows/features hit no bin
    bins_t = jnp.pad(bins.astype(jnp.int32).T, ((0, f_pad), (0, n_pad)),
                     constant_values=num_bins)
    # vals transposed to [3, n]: the 3-wide axis lives on sublanes, so a
    # block is (3, block_rows) instead of (block_rows, 3) whose 3-wide
    # lane dim VMEM-pads 3 → 128 (42x waste; OOMs at large block_rows)
    vals_t = jnp.pad(vals.T, ((0, 0), (0, n_pad)))
    nb = bins_t.shape[1] // block_rows
    nf = bins_t.shape[0] // feat_block

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nf, nb),
        in_specs=[
            pl.BlockSpec((feat_block, block_rows),
                         lambda f, r, *_: (f, r)),
            pl.BlockSpec((3, block_rows), lambda f, r, *_: (0, r)),
        ],
        out_specs=pl.BlockSpec((feat_block, 3, num_bins),
                               lambda f, r, *_: (f, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_bins=num_bins,
                          block_rows=block_rows, feat_block=feat_block),
        out_shape=jax.ShapeDtypeStruct((F + f_pad, 3, num_bins),
                                       jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(count, bins_t, vals_t)
    return out[:F].transpose(0, 2, 1)          # [F, B, 3]


def _tuned_hist(n: int, F: int, num_bins: int) -> tuple[int, int] | None:
    """Autotuned (feat_block, block_rows) for this (shape-bucket,
    platform) from the offline winner registry (``perf.autotune``,
    ISSUE 12), or None when untuned — the hand-picked defaults apply
    then. Plain dict read: this runs at jit trace time."""
    try:
        from ..perf import autotune
        from ..utils.platform import target_platform
        w = autotune.kernel_winner("hist",
                                   autotune.hist_key(n, F, num_bins),
                                   target_platform())
    except Exception:  # pragma: no cover - perf layer optional
        return None
    if not w:
        return None
    try:
        return int(w["feat_block"]), int(w["block_rows"])
    except (KeyError, TypeError, ValueError):
        return None


def hist_pallas(bins: jnp.ndarray, vals: jnp.ndarray, *, num_bins: int,
                count: jnp.ndarray | None = None,
                block_rows: int | None = None,
                feat_block: int | None = None,
                interpret: bool = False) -> jnp.ndarray:
    """bins u8/i32 [n, F], vals f32 [n, 3] (pre-masked) → [F, B, 3].

    ``count``: occupied rows at the front of the buffer (device i32
    scalar); rows past it must be padding (an out-of-range bin id or
    zero vals) and their row blocks are skipped. Defaults to n.

    ``block_rows``/``feat_block`` default to the autotuned winner for
    this (shape-bucket, platform) when one is registered
    (``perf.autotune``), else the hand-picked 2048/8 tiles — explicit
    values always win. Tile choice changes the schedule, not the math:
    the same one-hot contractions accumulate per bin (f32 summation
    order across row blocks is the only difference — within the atol
    the existing kernel tests already assert).
    """
    n, F = bins.shape
    tuned = None
    if block_rows is None or feat_block is None:
        tuned = _tuned_hist(int(n), int(F), int(num_bins))
    if block_rows is None:
        block_rows = tuned[1] if tuned else DEFAULT_BLOCK_ROWS
    if feat_block is None:
        feat_block = tuned[0] if tuned else FEAT_BLOCK
    if count is None:
        count = jnp.int32(n)
    count = jnp.asarray(count, jnp.int32).reshape(1)
    return _hist_call(bins, vals, count, num_bins=int(num_bins),
                      block_rows=int(block_rows),
                      feat_block=int(feat_block),
                      interpret=bool(interpret))


def use_pallas_hist() -> bool:
    """TPU only — the scatter path wins on CPU. Honours an active
    ``jax.default_device(...)`` CPU pin (compiled Pallas cannot lower for
    a CPU placement). The platform alone decides: an import or lowering
    error surfaces, it never selects the scatter path."""
    from ..utils.platform import target_platform
    return target_platform() == "tpu"
