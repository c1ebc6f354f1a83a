"""Whole step against the MXU's peak: the FLOPs the window's program calls
cannot avoid (``work_sparse_linear.step_work``: every row through the
matrices, every row against the tokens of its chosen blocks and the
compressed keys it scores, a state update and read-out a lightning head,
one row of logits a sequence) over the window's seconds times the
published peak."""

from benchmark import sala_stats


def read(ctx):
    if not ctx["on_chip"]:
        return None
    need = sala_stats.needed(ctx, sala_stats.window(ctx))
    if not need or need["flops"] <= 0:
        return None
    return 100.0 * need["flops"] / (
        ctx["elapsed_s"] * ctx["peaks"]["flops_per_s_bf16"])
