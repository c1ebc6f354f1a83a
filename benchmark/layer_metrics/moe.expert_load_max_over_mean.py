"""The fullest held expert's pairs in a decode call
(``moe_expert_load_max``, read at every boundary that only decoded) over
the mean pairs a held expert got in those calls: how uneven the grouped
product's groups are."""


def read(ctx):
    cfg = ctx["cfg"]
    stats = [s for s in ctx["stats"]
             if "moe_held" in s and s["prefilled"] == 0 and s["moe_held"] > 0]
    if not stats:
        return None
    lo, hi = cfg["experts_held"]
    groups = (int(hi) - int(lo)) * (int(cfg["num_hidden_layers"])
                                    - int(cfg["first_k_dense_replace"]))
    mean = sum(s["moe_held"] for s in stats) / (len(stats) * groups)
    return sum(s["load_max"] for s in stats) / len(stats) / mean
