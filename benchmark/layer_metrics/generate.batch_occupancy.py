"""Slots that committed a token a decode step: the window's
``gen_tokens_total`` over its ``gen_decode_steps_total`` times the
engine's slots."""

from benchmark import generate_stats


def read(ctx):
    every = generate_stats.window(ctx)
    if not ctx["on_chip"] or len(every) < 2:
        return None
    steps = every[-1]["decode_steps_total"] - every[0]["decode_steps_total"]
    tokens = every[-1]["decode_tokens_total"] \
        - every[0]["decode_tokens_total"]
    if steps <= 0:
        return None
    return 100.0 * tokens / (steps * ctx["params"]["engine"]["slots"])
