"""Whole step against HBM's peak: the bytes the window's programs cannot
avoid (``work_gated_delta_moe.step_work``: ONE program a boundary, the
weights once, each held expert that got a token once, every distinct
cached position once, every sequence's state and tail once in and once
out a DeltaNet layer) over the window's seconds times the published
bandwidth."""

from benchmark import q3n_stats


def read(ctx):
    return q3n_stats.whole_step_share(ctx, "bytes", "hbm_bytes_per_s")
