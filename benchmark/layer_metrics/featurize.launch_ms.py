"""Mean host milliseconds of the call of the jitted program for a
minibatch (endpoint check included): the ``tpu_model.launch`` spans of
the window's transforms."""

from benchmark.span_metrics import mean_child_ms


def read(ctx):
    return mean_child_ms(ctx, "tpu_model.transform", "tpu_model.launch")
