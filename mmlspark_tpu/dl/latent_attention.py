"""Multi-head latent attention (MLA) over ONE paged latent pool a layer: the
mixer ``dl.latent_moe_decoder`` and ``dl.kimi_linear_decoder`` share, built
from a config dict with the published ``config.json`` keys.

With ``u`` a token's normalised hidden state and every matrix product on
operands of the serving type with float32 accumulation:

- query: ``c_q = RMS(u W_qa)``, ``[q_nope, q_rope] = c_q W_qb`` a head
  where ``q_lora_rank`` is set; ``[q_nope, q_rope] = u W_q`` where it is
  null;
- ``[c, k_rope] = u W_kva``, ``c_kv = RMS(c)``; ``[k_nope, v] = c_kv
  W_kvb`` a head;
- positions: ``q_rope`` and the one shared ``k_rope`` rotated by the
  position (the rotary numbers taken as interleaved pairs; YaRN
  frequencies and scales where ``rope_scaling`` says so), or not at all
  where ``mla_use_nope`` is set: then ``k_rope`` is cached as it is and
  the scores are plain dot products over ``nope + rope`` numbers;
- the cache holds ``[c_kv, k_rope]`` a token, ``latent + rope`` numbers in
  whole 128-lane tiles (576 rests in 640: at 576 XLA keeps a ``[blocks,
  block_len, 576]`` pool with ``block_len`` minor and every program copies
  it into the layout the kernel reads and back; the padding is zeros in
  the entry and in the query, so the scores do not see it);
- the paged walk computes the ABSORBED form: ``q_lat = W_kvb,K^T q_nope``,
  score ``(q_lat . c_kv + q_rope . k_rope) * s``, ``o_lat = softmax(score)
  c_kv``, ``out = W_kvb,V o_lat`` — the same function as expanding
  ``[k_nope, v] = W_kvb c_kv`` per head — with all heads of a slot reading
  the same cached numbers
  (``pallas_paged_attention.paged_latent_attention``); then ``W_o``.

A layer's weights: ``q`` [D, H (nope + rope)] or ``q_a``, ``q_a_norm``,
``q_b``; ``kv_a`` [D, latent + rope], ``kv_a_norm`` [latent], ``kv_b``
[latent, H (nope + v)], ``o`` [H v, D].
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from .decoder_blocks import lay_rows, mm, rms_norm, split_rows
from .decoder_blocks import rotate_interleaved as _rotate
from .paged_kv import scatter_positions
from .pallas_paged_attention import (latent_max_window,
                                     paged_latent_attention)

__all__ = ["LatentAttention", "yarn_inv_freq", "yarn_mscale"]


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, scaling: dict) -> np.ndarray:
    """YaRN's ``dim / 2`` inverse frequencies: ``base^(-2i/dim)`` and that
    over ``factor``, blended by the linear ramp between the dimensions at
    which ``original_max_position_embeddings`` positions make
    ``beta_fast`` and ``beta_slow`` rotations."""
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain = 1.0 / base ** exps
    scaled = plain / float(scaling["factor"])

    def at(rotations: float) -> float:
        return dim * math.log(
            int(scaling["original_max_position_embeddings"])
            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(at(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(at(float(scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (scaled * ramp + plain * (1.0 - ramp)).astype(np.float32)


class LatentAttention:
    """See the module docstring. ``dtype`` is the type of the weights, the
    matrix products' operands and the cache."""

    def __init__(self, config: dict, dtype):
        c = config
        self.dtype = jnp.dtype(dtype)
        self.heads = int(c["num_attention_heads"])
        self.nope = int(c["qk_nope_head_dim"])
        self.rope = int(c["qk_rope_head_dim"])
        self.v_dim = int(c["v_head_dim"])
        self.latent = int(c["kv_lora_rank"])
        self.low_rank_query = c.get("q_lora_rank") is not None
        self.cache_width = -(-(self.latent + self.rope) // 128) * 128
        self.eps = float(c["rms_norm_eps"])
        self.positions = not c.get("mla_use_nope", False)
        scaling = c.get("rope_scaling")
        self.softmax_scale = (self.nope + self.rope) ** -0.5
        self._inv_freq = self._rope_scale = None
        if self.positions:
            theta = float(c["rope_theta"])
            self._inv_freq = yarn_inv_freq(self.rope, theta, scaling)
            factor = float(scaling["factor"])
            self._rope_scale = yarn_mscale(factor, float(scaling["mscale"])) \
                / yarn_mscale(factor, float(scaling["mscale_all_dim"]))
            self.softmax_scale *= yarn_mscale(
                factor, float(scaling["mscale_all_dim"])) ** 2

    def max_window(self) -> int:
        return latent_max_window(self.heads, self.cache_width, self.latent,
                                 self.dtype)

    def rotary(self, positions):
        """``(cos, sin)`` [T, rope / 2] of whole ``positions`` [T] (scaled
        as YaRN says); None where the queries and keys take no
        positions."""
        if not self.positions:
            return None
        ang = positions.astype(jnp.float32)[:, None] * self._inv_freq
        return jnp.cos(ang) * self._rope_scale, jnp.sin(ang) * self._rope_scale

    def __call__(self, lw, u, pool, tables, shapes, rotary):
        """``Attn(u) W_o`` [T, D] float32 over ``u`` [T, D], the rows of
        all the windows end to end, and the pool with each window's entries
        scattered first (``valid`` False sends a row's write to the trash
        block); ``tables`` are the windows' ``(rows, pos, valid, positions
        [S, w])``, ``shapes`` their ``(S, w)``, ``rotary`` what
        :meth:`rotary` gave for the rows' positions."""
        H, C, R, N = self.heads, self.latent, self.rope, self.nope
        T = u.shape[0]
        pad = self.cache_width - (C + R)
        if self.low_rank_query:
            u_q = rms_norm(mm(u, lw["q_a"], self.dtype), lw["q_a_norm"],
                           self.eps)
            q = mm(u_q, lw["q_b"], self.dtype).reshape(T, H, N + R)
        else:
            q = mm(u, lw["q"], self.dtype).reshape(T, H, N + R)
        kv = mm(u, lw["kv_a"], self.dtype)                  # [T, C+R]
        c_kv = rms_norm(kv[..., :C], lw["kv_a_norm"], self.eps)
        k_rope, q_rope = kv[..., C:], q[..., N:]
        if rotary is not None:
            cos, sin = rotary
            k_rope = _rotate(k_rope, cos, sin)
            q_rope = _rotate(q_rope, cos[:, None], sin[:, None])
        entry = jnp.concatenate(
            [c_kv, k_rope, jnp.zeros((T, pad), jnp.float32)],
            axis=-1).astype(pool.dtype)
        kv_b = lw["kv_b"].reshape(C, H, N + self.v_dim)
        q_lat = jnp.einsum(
            "thn,chn->thc", q[..., :N].astype(self.dtype), kv_b[..., :N],
            preferred_element_type=jnp.float32)
        q_abs = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros((T, H, pad), jnp.float32)],
            axis=-1).astype(pool.dtype)
        o_lat = []
        for (rows, pos, valid, at), e, qa in zip(
                tables, split_rows(entry, shapes),
                split_rows(q_abs, shapes)):
            ((pool,),) = scatter_positions(((pool,),), rows, at, ((e,),),
                                           valid=valid)
            o_lat.append(paged_latent_attention(
                qa, pool, rows, pos, scale=self.softmax_scale,
                value_dim=C))                               # [S, w, H, C]
        out = jnp.einsum("thc,chv->thv", lay_rows(o_lat), kv_b[..., N:],
                         preferred_element_type=jnp.float32)
        return mm(out.reshape(T, H * self.v_dim), lw["o"], self.dtype), pool
