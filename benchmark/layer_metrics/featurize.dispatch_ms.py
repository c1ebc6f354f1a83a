"""Mean host milliseconds per minibatch that ``TPUModel`` spent
dispatching (the blocking host-to-device copy included), over the
window's transforms; from ``ImageFeaturizer.last_transform_stats``."""

from benchmark.drivers_common import mean_ms_per_minibatch


def read(ctx):
    return mean_ms_per_minibatch(ctx, "dispatch_ms")
