"""Whole step against the MXU's peak: the FLOPs the window's programs
cannot avoid (``work_kda_mla_moe.step_work``: every row through the
matrices it meets, each held pair through its expert, every row against
the positions it attends in the latent layer, the KDA recurrence a row a
head, one row of logits a sampled token) over the window's seconds times
the published peak."""

from benchmark import kimi_stats


def read(ctx):
    return kimi_stats.whole_step_share(ctx, "flops", "flops_per_s_bf16")
