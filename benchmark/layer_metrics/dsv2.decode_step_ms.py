"""Mean host milliseconds of a boundary that only decoded (no request
was prefilled at it): one decode program over the slots, the fetch of its
tokens and the engine's bookkeeping."""

from benchmark import docqa_stats, generate_stats


def read(ctx):
    return generate_stats.mean_ms(docqa_stats.decode_only(ctx))
