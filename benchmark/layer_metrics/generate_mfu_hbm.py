"""Whole step against the HBM floor, decode's own roof: the bytes the
window's program calls cannot avoid (``work_decoder``: the weights once a
call, every cached position of the call's sequences once) over the
window's seconds times the published bandwidth."""

from benchmark import generate_stats


def read(ctx):
    if not ctx["on_chip"]:
        return None
    need = generate_stats.needed(ctx, generate_stats.window(ctx))
    if not need or need["bytes"] <= 0:
        return None
    return 100.0 * need["bytes"] / (
        ctx["elapsed_s"] * ctx["peaks"]["hbm_bytes_per_s"])
