"""Token-expert pairs that landed on a held expert, per token per expert
layer, over the window: ``moe_pairs_held_total`` over the pairs counted
(held and absent) divided by the experts a token chooses. With 40 of 160
experts held and 6 a token, 1.5 where routing is spread evenly."""


def read(ctx):
    stats = [s for s in ctx["stats"] if "moe_held" in s]
    pairs = sum(s["moe_held"] + s["moe_absent"] for s in stats)
    if pairs <= 0:
        return None
    top_k = int(ctx["cfg"]["num_experts_per_tok"])
    return sum(s["moe_held"] for s in stats) / (pairs / top_k)
