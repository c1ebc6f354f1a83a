"""Share of the device's busy time in the traced stretch that the KDA
kernels take: the decode step's read-modify-write of the states and the
chunked form of the windows that rode, together."""

from benchmark import kimi_stats


def read(ctx):
    return kimi_stats.kernel_share(ctx, "kda_step", "kda_chunk")
