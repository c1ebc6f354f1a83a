"""The lightning attention kernels against HBM's roof: the bytes their
calls in the traced stretch need (``work_sparse_linear.kernel_work``:
every sequence's state once in and once out a layer, its q, k, v rows in
and output rows out) over the bandwidth, over the two kernels' seconds in
the trace."""

from benchmark import sala_stats


def read(ctx):
    return sala_stats.hbm_roofline(ctx, "lightning", "lightning_step",
                                   "lightning_chunk")
