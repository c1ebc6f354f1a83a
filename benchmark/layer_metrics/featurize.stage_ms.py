"""Mean host milliseconds a minibatch spent being sliced out of the
column and, for the tail, padded to the compiled shape: the
``tpu_model.stage`` spans of the window's transforms."""

from benchmark.span_metrics import mean_child_ms


def read(ctx):
    return mean_child_ms(ctx, "tpu_model.transform", "tpu_model.stage")
