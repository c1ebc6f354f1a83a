"""Mean host milliseconds of the ``jnp.asarray`` call that hands a
minibatch to the device: the ``tpu_model.put`` spans of the window's
transforms."""

from benchmark.span_metrics import mean_child_ms


def read(ctx):
    return mean_child_ms(ctx, "tpu_model.transform", "tpu_model.put")
