"""Share of the device's busy time in the traced stretch that the expert
products take: the grouped kernels' events (``ragged-dot-gate-up`` and
``ragged-dot-down``, over the 64 held experts of four layers), summed by
the driver from the trace."""

from benchmark import kimi_stats


def read(ctx):
    return kimi_stats.kernel_share(ctx, "expert")
