"""Whole step against the MXU's peak: the FLOPs the window's programs
cannot avoid (``work_gated_delta_moe.step_work``: every row through the
matrices it meets, each held pair through its expert, every row against
the positions it attends, the delta rule's recurrence a row a head, one
row of logits a sampled token) over the window's seconds times the
published peak."""

from benchmark import q3n_stats


def read(ctx):
    return q3n_stats.whole_step_share(ctx, "flops", "flops_per_s_bf16")
