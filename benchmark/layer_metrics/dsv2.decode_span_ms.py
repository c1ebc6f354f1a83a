"""Mean host milliseconds of the engine's ``llm.decode`` span: the block
tables, the decode program and the one fetch of its tokens and counts,
under the window's ``llm.step`` spans."""

from benchmark.span_metrics import mean_child_ms


def read(ctx):
    return mean_child_ms(ctx, "llm.step", "llm.decode")
