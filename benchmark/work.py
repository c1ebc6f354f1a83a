"""Needed work, from shapes: the operations and bytes an algorithm cannot
avoid, whatever program implements it. Kept with the benchmark so that no
PR that claims a gain can change the yardstick. Nothing here looks at a
compiled program (``cost_analysis`` counts padding, recompute and unread
outputs; it is printed elsewhere for cross-checking only)."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks by ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _conv_flops(h_out: int, w_out: int, k: int, c_in: int,
                c_out: int) -> int:
    return 2 * h_out * w_out * k * k * c_in * c_out


def resnet_forward_flops(cfg: dict, *, head: bool) -> int:
    """Multiply-add FLOPs (2 per MAC) of one image through the
    convolutions of a bottleneck ResNet (stride on the 3x3, as
    torchvision and this repo place it) and, with ``head``, the
    classifier. BatchNorm, ReLU, pooling and the residual adds are left
    out: they are a fraction of a percent and not MXU work."""
    size = int(cfg["image_size"])
    width = int(cfg["stem_width"])
    h = size // 2                                   # 7x7 stride 2
    total = _conv_flops(h, h, 7, int(cfg["in_channels"]), width)
    h //= 2                                         # 3x3 max-pool stride 2
    c_in = width
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        mid = width * 2 ** i
        out = mid * int(cfg["bottleneck_expansion"])
        for j in range(n_blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            h_out = h // stride
            total += _conv_flops(h, h, 1, c_in, mid)          # 1x1 reduce
            total += _conv_flops(h_out, h_out, 3, mid, mid)   # 3x3 (strided)
            total += _conv_flops(h_out, h_out, 1, mid, out)   # 1x1 expand
            if j == 0:                                        # projection
                total += _conv_flops(h_out, h_out, 1, c_in, out)
            c_in, h = out, h_out
    if head:
        total += 2 * c_in * int(cfg["num_classes"])
    return total


def gbdt_iteration_min_bytes(rows: int, features: int) -> int:
    """HBM bytes one boosting iteration cannot avoid, whatever implements
    it: every row's bins read once (1 byte a feature), its gradient and
    hessian read (2 x 4 bytes), its score read and written (2 x 4)."""
    return rows * (features + 16)
