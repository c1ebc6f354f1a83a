"""Held experts that got a token in a program, of the held experts of all
layers, mean over the window's boundaries (``moe_experts_touched_total``):
the share of the experts' weights a program has to read."""

from benchmark import q3n_stats


def read(ctx):
    cfg = ctx["cfg"]
    stats = [s for s in q3n_stats.window(ctx) if s["moe_held"] > 0]
    if not ctx["on_chip"] or not stats:
        return None
    lo, hi = cfg["experts_held"]
    groups = (int(hi) - int(lo)) * int(cfg["num_hidden_layers"])
    return 100.0 * sum(s["moe_touched"] for s in stats) / (
        len(stats) * groups)
