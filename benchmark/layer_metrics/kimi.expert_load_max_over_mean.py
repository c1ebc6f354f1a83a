"""The fullest held expert's pairs in a program (``moe_expert_load_max``,
read at every boundary) over the mean pairs a held expert got in that
program, mean over the window's boundaries: how uneven the grouped
product's groups are at about 4 pairs an expert (128 decoding rows, 2
held pairs a row)."""

from benchmark import kimi_stats


def read(ctx):
    cfg = ctx["cfg"]
    stats = [s for s in kimi_stats.window(ctx) if s.get("moe_held", 0) > 0]
    if not stats:
        return None
    lo, hi = cfg["experts_held"]
    groups = (int(hi) - int(lo)) * (int(cfg["num_hidden_layers"])
                                    - int(cfg["first_k_dense_replace"]))
    return sum(s["load_max"] * groups / s["moe_held"]
               for s in stats) / len(stats)
