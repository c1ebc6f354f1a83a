"""Mean milliseconds of a boundary in which a prefill ran: its chunk
programs, then the decode program (the driver's own clock around
``LLMEngine.step()`` and the refill)."""

from benchmark import generate_stats


def read(ctx):
    return generate_stats.mean_ms(generate_stats.with_prefill(ctx))
