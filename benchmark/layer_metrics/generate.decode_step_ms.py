"""Mean milliseconds of a boundary in which no prefill ran: one decode
program over the live slots, with the host's work around it (the
driver's own clock around ``LLMEngine.step()`` and the refill)."""

from benchmark import generate_stats


def read(ctx):
    return generate_stats.mean_ms(generate_stats.decode_only(ctx))
