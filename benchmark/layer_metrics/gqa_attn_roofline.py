"""The grouped-query paged attention kernel against its roofline: the
larger of its needed FLOPs over the MXU's peak (every row of 16 heads
against every position it attends) and its needed bytes over HBM's (every
distinct cached position of 2 key heads once a program, the shared prompt
once however many slots chain it; each row's query and output), over the
kernel's seconds in the trace."""

from benchmark import q3n_stats


def read(ctx):
    return q3n_stats.roofline(ctx, "gqa_attn")
