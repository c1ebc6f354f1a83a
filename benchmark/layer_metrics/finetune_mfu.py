"""Whole-step share of the chip's bf16 peak in training: images per
second of the traced run times three times the forward FLOPs of an image
(forward, and the two matrix products of the backward pass; recomputed
operations do not count) over the published peak."""


def read(ctx):
    if not ctx["on_chip"]:
        return None
    flops = 3 * ctx["work"].resnet_forward_flops(ctx["cfg"], head=True)
    return 100.0 * ctx["rate"] * flops / ctx["peaks"]["flops_per_s_bf16"]
