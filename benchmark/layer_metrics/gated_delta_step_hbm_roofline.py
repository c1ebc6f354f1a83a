"""The delta rule's decode kernel against HBM's roof: the bytes its calls
in the traced stretch need (``work_gated_delta_moe.kernel_work``: every
decoding sequence's state once in and once out a layer, its q, k, v row in
and output row out) over the bandwidth, over the kernel's seconds in the
trace."""

from benchmark import q3n_stats


def read(ctx):
    return q3n_stats.roofline(ctx, "gdn_step", flops=False)
