"""What more than one driver or per-layer reader needs."""

from __future__ import annotations

import math


def resnet_variables(weights: dict) -> dict:
    """The benchmark's flat ResNet weights as the program's variables
    tree: BatchNorm mean/var are ``batch_stats``, the rest ``params``."""
    out = {"params": {}, "batch_stats": {}}
    for name, value in weights.items():
        *path, leaf = name.split("/")
        node = out["batch_stats" if leaf in ("mean", "var") else "params"]
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    return out


def mean_ms_per_minibatch(ctx: dict, key: str):
    """Mean of ``TPUModel.last_stats[key]`` per minibatch over the
    window's transforms; nothing when no transform left stats."""
    stats = [s for s in ctx["stats"] if key in s]
    if not stats:
        return None
    per_call = math.ceil(ctx["params"]["inputs"]["count"]
                         / ctx["params"]["minibatch"])
    return sum(s[key] for s in stats) / (len(stats) * per_call)


def idle_percent(ctx: dict):
    """Share of the traced stretch in which no operation ran on the
    device; nothing when the trace showed no device at work."""
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["idle_share"]
