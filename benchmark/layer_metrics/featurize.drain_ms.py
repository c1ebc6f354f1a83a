"""Mean host milliseconds per minibatch that ``TPUModel`` spent waiting
for the device and pulling outputs, over the window's transforms."""

from benchmark.drivers_common import mean_ms_per_minibatch


def read(ctx):
    return mean_ms_per_minibatch(ctx, "drain_ms")
