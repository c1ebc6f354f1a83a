"""Prompt rows that rode with the decoding rows, mean over the window's
boundaries (``gen_prefill_rows_total{ride="decode"}``): the slots stay
full while this keeps up with the turns the callers send."""

from benchmark import q3n_stats


def read(ctx):
    every = q3n_stats.window(ctx)
    if not every:
        return None
    return sum(s["ride_rows"] for s in every) / len(every)
