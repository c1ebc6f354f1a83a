"""Mean host milliseconds of a step's two ``device_put`` calls: the
``train.put`` spans of the window's epochs."""

from benchmark.span_metrics import mean_child_ms


def read(ctx):
    return mean_child_ms(ctx, "train.epoch", "train.put")
