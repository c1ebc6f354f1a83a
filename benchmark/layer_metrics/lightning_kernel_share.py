"""Share of the device's busy time in the traced stretch that the
lightning attention kernels take: the decode step's read-modify-write of
the states and the chunked form of the prefill windows, together."""

from benchmark import sala_stats


def read(ctx):
    return sala_stats.kernel_share(ctx, "lightning_step", "lightning_chunk")
