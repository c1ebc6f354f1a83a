"""The delta rule's chunked kernel against its roofline: the larger of the
needed FLOPs of its windows' rows over the MXU's peak (the recurrence's 7
dk dv a row a head: what the chunked form does beyond that is its own) and
their needed bytes over HBM's (the state once in and once out a window,
the rows' q, k, v and outputs), over the kernel's seconds in the trace."""

from benchmark import q3n_stats


def read(ctx):
    return q3n_stats.roofline(ctx, "gdn_chunk")
