"""Whole-step share of the chip's bf16 peak: the FLOPs the window's
prompt and generated tokens need (from shapes, ``work_decoder``: the
blocks' matrices a token, attention over the positions it sees, one row
of logits a prompt and a generated token) over the window's seconds
times the published peak."""

from benchmark import generate_stats


def read(ctx):
    if not ctx["on_chip"]:
        return None
    need = generate_stats.needed(ctx, generate_stats.window(ctx))
    if not need or need["flops"] <= 0:
        return None
    return 100.0 * need["flops"] / (
        ctx["elapsed_s"] * ctx["peaks"]["flops_per_s_bf16"])
