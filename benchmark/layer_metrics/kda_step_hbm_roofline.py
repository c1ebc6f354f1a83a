"""The KDA decode kernel against HBM's roof: the bytes its calls in the
traced stretch need (``work_kda_mla_moe.kernel_work``: every decoding
sequence's state once in and once out a layer, its q, k, v row, its decay
a channel and its output row) over the bandwidth, over the kernel's
seconds in the trace."""

from benchmark import kimi_stats


def read(ctx):
    return kimi_stats.roofline(ctx, "kda_step", flops=False)
