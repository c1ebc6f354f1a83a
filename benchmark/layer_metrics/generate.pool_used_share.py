"""Blocks referenced by live sequences over the blocks the pool can hand
out (all but the reserved trash block), mean over the window's
boundaries: memory in use against memory reserved (the ``kv_blocks_used``
gauge)."""

from benchmark import generate_stats


def read(ctx):
    every = generate_stats.window(ctx)
    if not ctx["on_chip"] or not every:
        return None
    usable = ctx["params"]["engine"]["num_blocks"] - 1
    return 100.0 * sum(s["blocks_used"] for s in every) / (
        len(every) * usable)
