"""Whole step against the HBM floor: the bytes the window's program calls
cannot avoid (``work_mla_moe.step_work``: the weights every call reads
once, each held expert that got a token once a call, every DISTINCT cached
position once a call however many slots share it) over the window's
seconds times the published bandwidth."""

from benchmark import docqa_stats


def read(ctx):
    if not ctx["on_chip"]:
        return None
    need = docqa_stats.needed(ctx, docqa_stats.window(ctx))
    if not need or need["bytes"] <= 0:
        return None
    return 100.0 * need["bytes"] / (
        ctx["elapsed_s"] * ctx["peaks"]["hbm_bytes_per_s"])
