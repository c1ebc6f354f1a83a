"""Mean host milliseconds of a boundary that only decoded (no request
was prefilled at it): one decode program over the slots, the fetch of its
tokens and the engine's bookkeeping."""

from benchmark import generate_stats, sala_stats


def read(ctx):
    return generate_stats.mean_ms(sala_stats.decode_only(ctx))
