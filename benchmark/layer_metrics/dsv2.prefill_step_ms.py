"""Mean milliseconds of a boundary in which a suffix was prefilled: its
prefill program (the same weights read for at most 192 rows), then the
decode program (the driver's own clock around ``LLMEngine.step()`` and
the refill)."""

from benchmark import generate_stats


def read(ctx):
    return generate_stats.mean_ms(generate_stats.with_prefill(ctx))
