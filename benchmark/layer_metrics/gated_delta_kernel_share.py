"""Share of the device's busy time in the traced stretch that the gated
delta-rule kernels take: the decode step's read-modify-write of the states
and the chunked form of the prefill windows, together."""

from benchmark import q3n_stats


def read(ctx):
    return q3n_stats.kernel_share(ctx, "gdn_step", "gdn_chunk")
