"""Host milliseconds a transform spent not waiting for the device: the
``tpu_model.transform`` span less its ``tpu_model.drain`` children, mean
over the window's transforms."""

from benchmark.span_metrics import window_trees


def read(ctx):
    trees = window_trees(ctx, "tpu_model.transform")
    if not trees:
        return None
    work = [root.seconds - sum(c.seconds for c in kids
                               if c.name == "tpu_model.drain")
            for root, kids in trees]
    return 1e3 * sum(work) / len(work)
