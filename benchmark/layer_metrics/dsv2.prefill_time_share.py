"""Share of the window's boundary time inside the engine's ``llm.prefill``
spans (admitted suffixes fed through the prefill programs, up to the
fetch of their first tokens), during which every decoding slot waits:
the spans' seconds over their ``llm.step`` roots' seconds."""

from benchmark.span_metrics import window_trees


def read(ctx):
    trees = window_trees(ctx, "llm.step")
    total = sum(root.seconds for root, _ in trees)
    if not ctx["on_chip"] or total <= 0:
        return None
    return 100.0 * sum(c.seconds for _, kids in trees for c in kids
                       if c.name == "llm.prefill") / total
