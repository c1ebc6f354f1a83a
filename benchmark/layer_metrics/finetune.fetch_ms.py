"""Mean host milliseconds an epoch spent in ``device_get`` of its losses,
where the host waits for the device: the ``train.fetch`` spans of the
window's epochs."""

from benchmark.span_metrics import mean_child_ms


def read(ctx):
    return mean_child_ms(ctx, "train.epoch", "train.fetch")
