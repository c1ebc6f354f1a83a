"""The fullest held expert's pairs in a program (``moe_expert_load_max``,
read at every boundary) over the mean pairs a held expert got in that
program, mean over the window's boundaries: how uneven the grouped
product's groups are at 2.5 (decode rows alone) to 12.5 (with a 512-row
window) pairs an expert."""

from benchmark import q3n_stats


def read(ctx):
    cfg = ctx["cfg"]
    stats = [s for s in q3n_stats.window(ctx) if s["moe_held"] > 0]
    if not stats:
        return None
    lo, hi = cfg["experts_held"]
    groups = (int(hi) - int(lo)) * int(cfg["num_hidden_layers"])
    return sum(s["load_max"] * groups / s["moe_held"]
               for s in stats) / len(stats)
