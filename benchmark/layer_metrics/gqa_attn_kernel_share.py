"""Share of the device's busy time in the traced stretch that the
grouped-query paged attention kernel takes (decode rows and window rows
alike; the cell's ``kernel_pattern``)."""

from benchmark import q3n_stats


def read(ctx):
    return q3n_stats.kernel_share(ctx, "gqa_attn")
