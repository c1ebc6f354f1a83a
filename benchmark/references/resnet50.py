"""Plain reference for the ResNet configurations: seeded weights, a
float32 forward pass in straightforward ``jax.numpy``/``lax`` at the
highest matmul precision, and the comparison that decides ``correct``
for the featurize cell. Imports nothing of the program and takes nothing
the program made: the benchmark makes the weights here and hands the same
numbers to the program (``drivers/featurize.py``).

Follows He et al. (arXiv:1512.03385) Table 1, bottleneck blocks, with the
stride on the 3x3 (torchvision's ResNet v1.5), symmetric explicit padding,
BatchNorm in inference form with eps from the configuration, ReLU, a 3x3
stride-2 max-pool after the stem and a global average pool.

Weight names are ``<block>/<layer>/<leaf>`` with blocks
``BottleneckBlock_<i>`` numbered through the stages, ``Conv_0..2`` the
1x1/3x3/1x1 path, ``Conv_3``/``BatchNorm_3`` the projection shortcut of a
stage's first block, ``conv_init``/``bn_init`` the stem and ``head`` the
classifier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 16), seed & 0xFFFF)


def weight_shapes(cfg: dict) -> dict:
    """``{name: (kind, shape)}`` for every leaf; kind picks the
    distribution in ``make_weights``."""
    width = int(cfg["stem_width"])
    exp = int(cfg["bottleneck_expansion"])
    out = {"conv_init/kernel": ("conv", (7, 7, int(cfg["in_channels"]),
                                         width))}

    def bn(name, c, last=False):
        out[f"{name}/scale"] = ("bn_scale_last" if last else "bn_scale",
                                (c,))
        out[f"{name}/bias"] = ("bn_bias", (c,))
        out[f"{name}/mean"] = ("bn_mean", (c,))
        out[f"{name}/var"] = ("bn_var", (c,))

    bn("bn_init", width)
    c_in, idx = width, 0
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        mid = width * 2 ** i
        c_out = mid * exp
        for j in range(n_blocks):
            b = f"BottleneckBlock_{idx}"
            out[f"{b}/Conv_0/kernel"] = ("conv", (1, 1, c_in, mid))
            bn(f"{b}/BatchNorm_0", mid)
            out[f"{b}/Conv_1/kernel"] = ("conv", (3, 3, mid, mid))
            bn(f"{b}/BatchNorm_1", mid)
            out[f"{b}/Conv_2/kernel"] = ("conv", (1, 1, mid, c_out))
            bn(f"{b}/BatchNorm_2", c_out, last=True)
            if j == 0:
                out[f"{b}/Conv_3/kernel"] = ("conv", (1, 1, c_in, c_out))
                bn(f"{b}/BatchNorm_3", c_out)
            c_in = c_out
            idx += 1
    out["head/kernel"] = ("dense", (c_in, int(cfg["num_classes"])))
    out["head/bias"] = ("bn_bias", (int(cfg["num_classes"]),))
    return out


def _draw(kind, shape, key):
    if kind == "conv":
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(key, shape) * np.sqrt(2.0 / fan_in)
    if kind == "dense":
        return jax.random.normal(key, shape) * np.sqrt(1.0 / shape[0])
    if kind == "bn_scale":
        return jax.random.uniform(key, shape, minval=0.7, maxval=1.3)
    if kind == "bn_scale_last":      # residual branch live, not dominant
        return jax.random.uniform(key, shape, minval=0.3, maxval=0.7)
    if kind == "bn_var":
        return jax.random.uniform(key, shape, minval=0.7, maxval=1.3)
    # bn_bias, bn_mean
    return jax.random.normal(key, shape) * 0.1


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight from the seed in ONE jitted call on the default
    device, float32 (the type the configuration serves them in)."""
    shapes = weight_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(names))
        return {n: _draw(shapes[n][0], shapes[n][1], keys[i])
                .astype(jnp.float32) for i, n in enumerate(names)}

    return draw(seed_key(seed))


def _identity(x):
    return x


def forward(weights: dict, images, cfg: dict, *, round_fn=_identity):
    """Pooled features ``[n, feature_dim]`` of uint8/float images
    ``[n, H, W, C]``, float32 at the highest matmul precision.
    ``round_fn`` is applied to both operands of every convolution: the
    identity for the reference, a lower-precision rounding for a
    control."""
    eps = float(cfg["batch_norm_eps"])

    def conv(x, name, stride=1, pad=0):
        return lax.conv_general_dilated(
            round_fn(x), round_fn(weights[f"{name}/kernel"]),
            (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)

    def bn(x, name):
        inv = weights[f"{name}/scale"] * lax.rsqrt(
            weights[f"{name}/var"] + eps)
        return (x - weights[f"{name}/mean"]) * inv + weights[f"{name}/bias"]

    x = jnp.asarray(images).astype(jnp.float32)
    x = jax.nn.relu(bn(conv(x, "conv_init", 2, 3), "bn_init"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    idx = 0
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        for j in range(n_blocks):
            b = f"BottleneckBlock_{idx}"
            stride = 2 if (i > 0 and j == 0) else 1
            y = jax.nn.relu(bn(conv(x, f"{b}/Conv_0"), f"{b}/BatchNorm_0"))
            y = jax.nn.relu(bn(conv(y, f"{b}/Conv_1", stride, 1),
                               f"{b}/BatchNorm_1"))
            y = bn(conv(y, f"{b}/Conv_2"), f"{b}/BatchNorm_2")
            if j == 0:
                x = bn(conv(x, f"{b}/Conv_3", stride), f"{b}/BatchNorm_3")
            x = jax.nn.relu(y + x)
            idx += 1
    return jnp.mean(x, axis=(1, 2))


def round_to(dtype_name: str, *, scaled: bool = False):
    """Rounding through a narrower float type and back to float32; with
    ``scaled`` the tensor's largest magnitude is first mapped onto the
    type's largest finite value (per-tensor scaling, as fp8 inference
    does: e4m3 ends at 448 and has no infinity)."""
    dt = jnp.dtype(dtype_name)

    def fn(x):
        if not scaled:
            return x.astype(dt).astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) \
            / float(jnp.finfo(dt).max)
        return (x / scale).astype(dt).astype(jnp.float32) * scale
    return fn


def row_gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-row relative L2 distance of ``got`` from ``ref``."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.linalg.norm(got - ref, axis=1) / np.maximum(
        np.linalg.norm(ref, axis=1), 1e-30)


def compare_features(weights: dict, cfg: dict, images: np.ndarray,
                     served: list, limits: dict, *, block: int = 32) -> list:
    """The featurize cell's comparison. ``images`` are the sampled input
    rows, ``served`` one ``[len(images), feature_dim]`` array per
    transform of the window (the same rows each time). Returns
    ``[(name, value, limit), ...]``."""
    fwd = jax.jit(functools.partial(forward, cfg=cfg))
    ref = np.concatenate([
        np.asarray(fwd(weights, images[s:s + block]))
        for s in range(0, len(images), block)])
    shape_ok = all(np.asarray(out).shape == ref.shape
                   and np.isfinite(out).all() for out in served)
    worst = max(float(row_gaps(out, ref).max()) for out in served) \
        if shape_ok and served else float("inf")
    return [("feature_row_rel_l2_max", worst,
             limits["feature_row_rel_l2_max"])]
