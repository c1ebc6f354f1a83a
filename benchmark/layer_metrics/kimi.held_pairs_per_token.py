"""Token-expert pairs that landed on a held expert, per token per expert
layer, over the window (``moe_pairs_held_total`` over the pairs counted,
held and absent, divided by the experts a token chooses): with 64 of 256
experts held and 8 a token, 2 where routing is spread evenly."""

from benchmark import kimi_stats


def read(ctx):
    stats = [s for s in kimi_stats.window(ctx) if "moe_held" in s]
    pairs = sum(s["moe_held"] + s["moe_absent"] for s in stats)
    if pairs <= 0:
        return None
    top_k = int(ctx["cfg"]["num_experts_per_token"])
    return sum(s["moe_held"] for s in stats) / (pairs / top_k)
