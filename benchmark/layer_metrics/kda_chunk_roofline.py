"""The KDA chunked kernel against its roofline, where a window rode in the
traced stretch: the larger of the needed FLOPs of its windows' rows over
the MXU's peak (the recurrence's 7 dk dv a row a head: what the chunked
form does beyond that is its own) and their needed bytes over HBM's (the
state once in and once out a window, the rows' q, k, v, decays and
outputs), over the kernel's seconds in the trace."""

from benchmark import kimi_stats


def read(ctx):
    return kimi_stats.roofline(ctx, "kda_chunk")
