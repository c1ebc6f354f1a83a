"""Whole step against the MXU's peak: the FLOPs the window's program calls
cannot avoid (``work_mla_moe.step_work``: every row through the matrices
every token meets, each held token-expert pair through its expert, every
row against every cached position it attends, one row of logits a
sequence) over the window's seconds times the published peak."""

from benchmark import docqa_stats


def read(ctx):
    if not ctx["on_chip"]:
        return None
    need = docqa_stats.needed(ctx, docqa_stats.window(ctx))
    if not need or need["flops"] <= 0:
        return None
    return 100.0 * need["flops"] / (
        ctx["elapsed_s"] * ctx["peaks"]["flops_per_s_bf16"])
