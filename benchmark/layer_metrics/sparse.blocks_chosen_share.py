"""Blocks the sparse layers attended over the blocks their chains held,
of the window's rows (``sparse_blocks_chosen_total`` over
``sparse_blocks_in_chain_total``, the walk's counts a (row, key head) a
layer): what block selection leaves of dense attention's reads."""


def read(ctx):
    stats = [s for s in ctx["stats"] if "blocks_in_chain" in s]
    held = sum(s["blocks_in_chain"] for s in stats)
    if not ctx["on_chip"] or held <= 0:
        return None
    return 100.0 * sum(s["blocks_chosen"] for s in stats) / held
