"""Whole step against HBM's peak: the bytes the window's program calls
cannot avoid (``work_sparse_linear.step_work``: the weights once a call,
the chosen blocks and compressed keys, every lightning state once in and
once out) over the window's seconds times the published bandwidth."""

from benchmark import sala_stats


def read(ctx):
    if not ctx["on_chip"]:
        return None
    need = sala_stats.needed(ctx, sala_stats.window(ctx))
    if not need or need["bytes"] <= 0:
        return None
    return 100.0 * need["bytes"] / (
        ctx["elapsed_s"] * ctx["peaks"]["hbm_bytes_per_s"])
