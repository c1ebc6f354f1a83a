"""The paged latent attention kernel against its roofline: the larger of
its needed FLOPs over the MXU's peak (every row of 32 heads against every
position it attends, scores over 576 numbers and the sum over 512) and
its needed bytes over HBM's (every distinct cached position once a
program, 576 numbers; each row's query and output), over the kernel's
seconds in the trace. At 32 heads a decoding row does 60 FLOPs a cached
byte: HBM's bound."""

from benchmark import kimi_stats


def read(ctx):
    return kimi_stats.roofline(ctx, "latent_attn")
