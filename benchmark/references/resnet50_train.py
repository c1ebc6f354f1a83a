"""Plain reference for ResNet fine-tuning: the training-mode forward
pass (BatchNorm on the batch's own statistics, running statistics
updated), softmax cross-entropy, its gradients by ``jax.grad`` and SGD
with momentum, all float32 at the highest matmul precision, and the
comparison that decides ``correct`` for the fine-tune cell. Shares the
weight names and the seeded weights of ``resnet50.py``; imports nothing
of the program.

Each residual block is rematerialised in the backward pass
(``jax.checkpoint``) so that a batch of 256 float32 images fits the chip;
that changes what is stored, not what is computed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references.resnet50 import _identity, make_weights

BN_MOMENTUM = 0.9
STAT_LEAVES = ("mean", "var")
NUMBERS = ("loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
           "param_change_gap", "bn_stats_gap", "grad_norm_gap_median",
           "param_change_gap_median", "bn_stats_gap_median")


def split(weights: dict):
    """(trainable parameters, BatchNorm running statistics)."""
    params = {k: v for k, v in weights.items()
              if k.rsplit("/", 1)[1] not in STAT_LEAVES}
    stats = {k: v for k, v in weights.items()
             if k.rsplit("/", 1)[1] in STAT_LEAVES}
    return params, stats


def straight_through(round_fn):
    """``round_fn`` in the forward pass, the identity in the backward."""
    def fn(x):
        return x + lax.stop_gradient(round_fn(x) - x)
    return fn


def forward_train(params: dict, stats: dict, images, cfg: dict,
                  round_fn=_identity):
    """Logits of a training-mode pass and the updated running
    statistics."""
    eps = float(cfg["batch_norm_eps"])
    new_stats = {}

    def conv(x, name, stride=1, pad=0):
        return lax.conv_general_dilated(
            round_fn(x), round_fn(params[f"{name}/kernel"]),
            (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)

    def bn(x, name, out):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
        for leaf, batch_value in (("mean", mean), ("var", var)):
            key = f"{name}/{leaf}"
            out[key] = BN_MOMENTUM * stats[key] \
                + (1 - BN_MOMENTUM) * batch_value
        return (x - mean) * lax.rsqrt(var + eps) * params[f"{name}/scale"] \
            + params[f"{name}/bias"]

    def block(x, b, stride, project):
        out = {}
        y = jax.nn.relu(bn(conv(x, f"{b}/Conv_0"), f"{b}/BatchNorm_0", out))
        y = jax.nn.relu(bn(conv(y, f"{b}/Conv_1", stride, 1),
                           f"{b}/BatchNorm_1", out))
        y = bn(conv(y, f"{b}/Conv_2"), f"{b}/BatchNorm_2", out)
        if project:
            x = bn(conv(x, f"{b}/Conv_3", stride), f"{b}/BatchNorm_3", out)
        return jax.nn.relu(y + x), out

    x = jnp.asarray(images).astype(jnp.float32)
    x = jax.nn.relu(bn(conv(x, "conv_init", 2, 3), "bn_init", new_stats))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    idx = 0
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        for j in range(n_blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            x, out = jax.checkpoint(
                block, static_argnums=(1, 2, 3))(
                    x, f"BottleneckBlock_{idx}", stride, j == 0)
            new_stats.update(out)
            idx += 1
    pooled = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(round_fn(pooled), round_fn(params["head/kernel"]),
                     precision=lax.Precision.HIGHEST) + params["head/bias"]
    return logits, new_stats


def loss_of(params, stats, images, labels, cfg, round_fn=_identity):
    logits, new_stats = forward_train(params, stats, images, cfg, round_fn)
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -jnp.mean(picked), new_stats


def _sgd_step(params, stats, trace, images, labels, *, cfg, lr, momentum,
              round_fn):
    (loss, new_stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
        params, stats, images, labels, cfg, round_fn)
    trace = {k: grads[k] + momentum * trace[k] for k in grads}
    params = {k: params[k] - lr * trace[k] for k in params}
    return params, new_stats, trace, loss, grads


def norms(tree: dict) -> dict:
    """Per-leaf L2 norm, in float64."""
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def train_steps(cfg: dict, seed: int, batches: list, *, lr: float,
                momentum: float, round_fn=_identity,
                rows: slice = slice(None)) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's weights.
    Returns the losses, the per-leaf norms of the first gradient, of the
    parameters' change and of the running statistics at the end.
    ``rows`` plants a fault: the steps see only those rows of a batch."""
    params, stats = split(make_weights(cfg, seed))
    start = {k: np.asarray(v) for k, v in params.items()}
    trace = {k: jnp.zeros_like(v) for k, v in params.items()}
    step = jax.jit(functools.partial(
        _sgd_step, cfg=cfg, lr=lr, momentum=momentum, round_fn=round_fn),
        donate_argnums=(0, 1, 2))
    losses, grad_norms = [], None
    for images, labels in batches:
        params, stats, trace, loss, grads = step(
            params, stats, trace, jnp.asarray(images[rows]),
            jnp.asarray(labels[rows]))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = norms(grads)
        del grads
    change = {k: np.asarray(v, np.float64) - start[k]
              for k, v in params.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": norms(change), "stat_norms": norms(stats)}


def leaf_gaps(got: dict, want: dict, leaves=None) -> dict:
    """Per leaf, the gap between its norm in ``got`` and in ``want``,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    leaves = sorted(want) if leaves is None else leaves
    median = float(np.median([want[k] for k in leaves]))
    return {k: abs(got[k] - want[k]) / max(want[k], median, 1e-30)
            for k in leaves}


def moving_leaves(want: dict) -> list:
    """Leaves whose gradient is nought to rounding in the reference move
    by round-off alone: left out of the change, by a rule on the
    reference's gradient (under a thousandth of the median leaf's), not
    by name."""
    g = want["grad_norms"]
    floor = 1e-3 * float(np.median(list(g.values())))
    return sorted(k for k in g if g[k] >= floor)


def compare(got: dict, want: dict, details: dict | None = None) -> dict:
    """The numbers compared, by the names in ``NUMBERS``; ``got`` and
    ``want`` as ``train_steps`` returns them. ``details`` is filled with
    the worst leaves, for a look."""
    out = {}
    for i in range(3):
        out[f"loss{i + 1}_gap"] = abs(got["losses"][i] - want["losses"][i]) \
            / abs(want["losses"][i])
    per_leaf = {
        "grad_norm": leaf_gaps(got["grad_norms"], want["grad_norms"]),
        "param_change": leaf_gaps(got["change_norms"], want["change_norms"],
                                  moving_leaves(want)),
        "bn_stats": leaf_gaps(got["stat_norms"], want["stat_norms"]),
    }
    for name, gaps in per_leaf.items():
        out[f"{name}_gap"] = max(gaps.values())
        out[f"{name}_gap_median"] = float(np.median(list(gaps.values())))
        if details is not None:
            src = {"grad_norm": "grad_norms", "param_change": "change_norms",
                   "bn_stats": "stat_norms"}[name]
            worst = sorted(gaps, key=gaps.get, reverse=True)[:4]
            details[name] = [[k, gaps[k], got[src][k], want[src][k]]
                             for k in worst]
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}
