"""What the config-driven decoders of ``serving.llm.LLMEngine`` share
(``dl.latent_moe_decoder``, ``dl.sparse_linear_decoder``,
``dl.gated_delta_moe_decoder``): RMSNorm in float32 (plain and
zero-centred), the matrix product on operands of the serving type with float32
accumulation, the gated SiLU MLP, the two rotary pairings, and the calling
convention the engine uses for a decoder that is a plain class over a
params dict; and what every decoder's ``walk`` shares (``dl.pretrain``'s
too): the rows of several windows laid end to end, so that whatever acts a
row at a time runs once over all of them and reads its weights once, and
split again for what acts a window at a time (the scatter into the cache
and attention).

A configuration dict keeps the published ``config.json`` keys; what else it
holds (``source``, ``reduced``, ``assumed``, ``published``, ``deployment``)
is the benchmark's to state and a decoder's to ignore.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rms_norm", "mm", "gated_silu", "rotate_interleaved",
           "rotate_half", "rotate_half_partial", "rope_angles",
           "DictDecoder", "lay_rows", "split_rows", "window_positions"]


def lay_rows(parts):
    """The rows of several windows end to end: arrays ``[S_i, w_i, ...]``
    with one trailing shape → ``[sum S_i * w_i, ...]``."""
    flat = [p.reshape((-1,) + p.shape[2:]) for p in parts]
    return flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=0)


def split_rows(rows, shapes):
    """:func:`lay_rows` undone: ``rows`` [T, ...] → a tuple of ``[S_i,
    w_i, ...]``, ``shapes`` the windows' ``(S_i, w_i)``."""
    out, at = [], 0
    for S, w in shapes:
        out.append(rows[at:at + S * w].reshape((S, w) + rows.shape[1:]))
        at += S * w
    return tuple(out)


def window_positions(windows):
    """``[S_i, w_i]`` global positions of each window's rows: a window is
    ``(toks [S, w], rows, pos [S], valid, ...)`` and its slot ``s`` holds
    positions ``[pos[s], pos[s] + w)``."""
    return tuple(win[2][:, None] + jnp.arange(win[0].shape[1])[None]
                 for win in windows)


def rms_norm(x, scale, eps: float):
    """RMSNorm over the last axis in float32, times ``scale``."""
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def mm(a, w, dtype):
    """``a @ w`` on operands of ``dtype`` with float32 accumulation."""
    return jnp.matmul(a.astype(dtype), w, preferred_element_type=jnp.float32)


def gated_silu(u, gate, up, down, dtype):
    return mm(jax.nn.silu(mm(u, gate, dtype)) * mm(u, up, dtype), down, dtype)


def rope_angles(positions, inv_freq):
    """``[..., dim / 2]`` angles of whole ``positions`` [...]."""
    return positions.astype(jnp.float32)[..., None] * inv_freq


def rotate_interleaved(x, cos, sin):
    """Turn the interleaved pairs ``(x[2i], x[2i+1])`` of the last axis
    by their angles; first members land in the first half, second in the
    second (only dot products of two rotated vectors are taken)."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rotate_half(x, cos, sin):
    """Turn the pairs ``(x[i], x[i + dim/2])`` of the last axis by their
    angles (the ``rotate_half`` pairing of the Llama-style rotary code);
    each member stays where it was."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rotate_half_partial(x, cos, sin):
    """:func:`rotate_half` on the first ``2 * cos.shape[-1]`` numbers of
    the last axis (a head's rotary part, ``partial_rotary_factor`` of
    it); the rest pass as they are."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return rotate_half(x, cos, sin)
    return jnp.concatenate([rotate_half(x[..., :rot], cos, sin),
                            x[..., rot:]], axis=-1)


class DictDecoder:
    """A decoder that is a plain class over a params dict: the engine
    calls every decoder as ``module.apply({"params": ...}, *args,
    method=...)``."""

    dtype = jnp.dtype(jnp.bfloat16)
    eps = 1e-6

    def apply(self, variables, *args, method="walk"):
        return getattr(self, method)(variables["params"], *args)

    def _rms(self, x, scale):
        return rms_norm(x, scale, self.eps)

    def _rms_centred(self, x, weight):
        """The zero-centred RMSNorm ``x^ (1 + w)``: a weight of zeros is
        the plain norm."""
        return rms_norm(x, 1.0 + weight.astype(jnp.float32), self.eps)

    def _mm(self, a, w):
        return mm(a, w, self.dtype)

    def _gated(self, u, gate, up, down):
        return gated_silu(u, gate, up, down, self.dtype)
