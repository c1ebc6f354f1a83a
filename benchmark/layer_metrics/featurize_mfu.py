"""Whole-transform share of the chip's bf16 peak: images per second of
the traced run times the forward FLOPs an image needs (from shapes,
``work.resnet_forward_flops``, classifier left out: only ``pooled`` is
read) over the published peak."""


def read(ctx):
    if not ctx["on_chip"]:
        return None
    flops = ctx["work"].resnet_forward_flops(ctx["cfg"], head=False)
    return 100.0 * ctx["rate"] * flops / ctx["peaks"]["flops_per_s_bf16"]
