"""The block-sparse attention kernel against HBM's roof: the bytes its
calls in the traced stretch need (``work_sparse_linear.kernel_work``: the
chosen blocks' keys and values once a (row, key head), never more than
the distinct cached positions; queries in, outputs out) over the
bandwidth, over the kernel's seconds in the trace."""

from benchmark import sala_stats


def read(ctx):
    return sala_stats.hbm_roofline(ctx, "sparse_attn", "sparse_attn")
