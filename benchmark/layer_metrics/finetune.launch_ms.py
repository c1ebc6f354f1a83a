"""Mean host milliseconds of the call of the jitted train step: the
``train.launch`` spans of the window's epochs."""

from benchmark.span_metrics import mean_child_ms


def read(ctx):
    return mean_child_ms(ctx, "train.epoch", "train.launch")
