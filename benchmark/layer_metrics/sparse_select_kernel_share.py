"""Share of the device's busy time in the traced stretch that the scoring
pass of block selection takes (every query row against the compressed
keys of its slot's chain)."""

from benchmark import sala_stats


def read(ctx):
    return sala_stats.kernel_share(ctx, "sparse_select")
