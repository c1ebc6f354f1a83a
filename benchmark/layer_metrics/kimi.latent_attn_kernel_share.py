"""Share of the device's busy time in the traced stretch that the paged
latent attention kernel takes (32 heads a slot on one latent pool), the
decoding rows' calls and the riding windows' together."""

from benchmark import kimi_stats


def read(ctx):
    return kimi_stats.kernel_share(ctx, "latent_attn")
