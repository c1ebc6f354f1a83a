"""Share of the window's boundary time spent in boundaries that
prefilled: how long the decoding slots were held."""

from benchmark import generate_stats


def read(ctx):
    every = generate_stats.window(ctx)
    total = sum(s["seconds"] for s in every)
    if not ctx["on_chip"] or total <= 0:
        return None
    return 100.0 * sum(s["seconds"]
                       for s in generate_stats.with_prefill(ctx)) / total
