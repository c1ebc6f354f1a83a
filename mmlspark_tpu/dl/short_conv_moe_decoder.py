"""A decoder that mixes double-gated short-convolution layers with
grouped-query attention layers, a dense SwiGLU after the first few blocks
and a dropless expert layer after every later one, for
``serving.llm.LLMEngine``: the block LFM2's mixture-of-experts models
publish, built from a config dict with that family's ``config.json`` keys
(``layer_types``, ``conv_L_cache``, ``num_dense_layers``,
``use_expert_bias``, ...).

With ``x`` a token's hidden state (float32), ``N`` the RMSNorm ``x rsqrt(mean
x^2 + eps) w`` in float32 (plain weight), every matrix product on operands of
the serving type with float32 accumulation, and layer ``i`` a convolution or
attention as ``layer_types[i]`` says:

- block: ``h = x + Mixer(N(x))``, ``y = h + FFN(N(h))``; a final ``N``, the
  head TIED to the embedding (``logits = N(y) E^T``); no biases.
- short convolution (``conv``): ``[B | C | v] = u W_in``, ``Bx = B * v``
  (rounded to the serving type, as the tail holds it), a causal depthwise
  convolution of ``conv_L_cache`` taps over ``Bx`` with no bias and no
  activation, ``(C * conv(Bx)) W_out``. Its cache is ONE row a sequence:
  the TAIL, the last ``taps - 1`` values of ``Bx`` ``[taps - 1, D]`` of the
  serving type, carried from window to window
  (``decoder_blocks.causal_conv_tail``), so a prompt cut into any windows
  gives the same rows; a slot at position 0 starts from zeros.
- attention (``H`` query heads on ``G`` key heads of ``D / H``): ``q <-
  N(q)``, ``k <- N(k)`` over the head (plain weights), rotary positions
  (``rotate_half``) on the whole head, causal softmax over the paged key
  and value pools ``[blocks, block_len, G hd]``
  (``pallas_paged_attention.paged_window_attention``; heads of 64 lanes go
  two key heads to a 128-lane tile), ``W_o``. No gate.
- FFN: the first ``num_dense_layers`` blocks a SwiGLU of
  ``intermediate_size`` (``w2(silu(u w1) * u w3)``); every later block
  ``num_experts`` routed experts of ``moe_intermediate_size``: ``s =
  sigmoid(u W_r)`` in float32, the ``num_experts_per_tok`` experts of the
  largest ``s + expert_bias`` CHOSEN (``models.moe.route_top_k(bias=)``),
  their UNBIASED ``s`` the weights, over their sum plus ``1e-6``
  (``norm_topk_prob``), times ``routed_scaling_factor``; the terms of the
  experts held (``experts_held``, a range) computed with one grouped
  product over the pairs sorted by expert (``models.moe.dropless_moe``).
  No shared expert.

The interface the engine asks of a decoder (``serving.llm``):
``cache_spec()`` (two ``"token"`` entries for an attention layer, ONE
``"seq"`` entry for a convolution layer, ``dl.paged_kv``),
``max_window()``, ``program_key()``, ``walk`` — which takes the slots'
state rows after the arguments every decoder's walk takes — and
``logits``; ``walk_stats`` names the counts a walk returns.

Parameters are a plain dict: ``embed`` [V, D], ``final_norm`` [D] and
``layers``, a list of dicts: always ``operator_norm``, ``ffn_norm``; a
convolution layer ``in_proj`` [D, 3 D], ``conv`` [D, taps], ``out_proj``
[D, D]; an attention layer ``q`` [D, H hd], ``k``, ``v`` [D, G hd],
``q_norm``, ``k_norm`` [hd], ``o`` [H hd, D]; a dense block ``w1``, ``w3``
[D, I], ``w2`` [I, D]; an expert block ``router`` [D, E], ``expert_bias``
[E], ``exp_gate``/``exp_up`` [n, D, F], ``exp_down`` [n, F, D] over the
held experts. Every matrix is applied as ``x @ w``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models.moe import (MOE_STATS, add_moe_stats, dropless_moe,
                          route_top_k)
from .decoder_blocks import (DictDecoder, causal_conv_tail, gated_silu,
                             lay_rows, rope_angles, rotate_half, split_rows,
                             window_positions)
from .paged_kv import scatter_positions
from .pallas_paged_attention import paged_window_attention

__all__ = ["ShortConvMoEDecoder", "CONV", "FULL"]

CONV = "conv"
FULL = "full_attention"
#: the counts after the expert layers' ``moe_*``: pairs the bias moved,
#: then the convolution layers' rows by kind and the windows whose tail was
#: carried in
LFM2_STATS = ("moe_pairs_biased", "conv_step_rows", "conv_window_rows",
              "conv_tails_carried")


class ShortConvMoEDecoder(DictDecoder):
    """See the module docstring. ``config`` keeps whatever else it holds
    (``source``, ``reduced``, ``assumed``, ``published``, ``deployment``);
    ``dtype`` is the type of the weights, the matrix products' operands,
    the key/value cache and the convolution's tail; ``max_window`` the
    widest prefill window the walk is given."""

    walk_stats = tuple(f"moe_{name}" for name in MOE_STATS) + LFM2_STATS

    def __init__(self, config: dict, *, dtype=jnp.bfloat16,
                 max_window: int = 512):
        self.config = dict(config)
        c = self.config
        self.dtype = jnp.dtype(dtype)
        self.width = int(c["hidden_size"])
        self.mixers = tuple(c["layer_types"])
        self.depth = int(c["num_hidden_layers"])
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.hd = int(c.get("head_dim") or self.width // self.heads)
        self.taps = int(c["conv_L_cache"])
        if len(self.mixers) != self.depth or set(self.mixers) - {CONV, FULL}:
            raise ValueError("layer_types has to name a mixer of "
                             f"{CONV!r} | {FULL!r} for each layer")
        if self.heads % self.kv_heads:
            raise ValueError("key heads have to divide the heads they serve")
        if c.get("conv_bias"):
            raise ValueError("a convolution with a bias is not modelled")
        self.dense_layers = int(c["num_dense_layers"])
        self.experts = int(c["num_experts"])
        self.top_k = int(c["num_experts_per_tok"])
        self.renorm = bool(c.get("norm_topk_prob", True))
        self.scaling = float(c.get("routed_scaling_factor", 1.0))
        self.expert_bias = bool(c.get("use_expert_bias", False))
        self.experts_held = tuple(int(e) for e in c.get(
            "experts_held", (0, self.experts)))
        self.eps = float(c["norm_eps"])
        self._inv_freq = (1.0 / float(c["rope_theta"]) ** (
            np.arange(0, self.hd, 2, dtype=np.float64) / self.hd)
        ).astype(np.float32)
        self._max_window = int(max_window)

    # -- what the engine asks ------------------------------------------------
    def cache_spec(self) -> tuple:
        """An attention layer caches a token's key heads side by side and
        its value heads side by side; a convolution layer its tail a
        SEQUENCE, and nothing a token."""
        width = self.kv_heads * self.hd
        full = (((width,), self.dtype), ((width,), self.dtype))
        conv = (((self.taps - 1, self.width), self.dtype, "seq"),)
        return tuple(full if m == FULL else conv for m in self.mixers)

    def max_window(self) -> int:
        return self._max_window

    def program_key(self) -> dict:
        c = self.config
        keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "rope_theta", "conv_L_cache", "intermediate_size",
                "num_dense_layers", "num_experts", "num_experts_per_tok",
                "moe_intermediate_size", "num_hidden_layers", "vocab_size")
        return {"decoder": "short_conv_moe", "dtype": self.dtype.name,
                "layer_types": list(self.mixers), "head_dim": self.hd,
                "experts_held": list(self.experts_held),
                "norm_topk_prob": self.renorm,
                "routed_scaling_factor": self.scaling,
                "use_expert_bias": self.expert_bias,
                "max_window": self._max_window, **{k: c[k] for k in keys}}

    # -- the mixers ----------------------------------------------------------
    # each takes ``u`` [T, D], the rows of all the windows end to end: its
    # projections run once over them; the cache writes, attention and the
    # convolution a window at a time
    def _attention_layer(self, lw, u, pools, windows, shapes, wrote, rotary):
        T = u.shape[0]
        H, G, hd = self.heads, self.kv_heads, self.hd
        cos, sin = rotary
        q = rotate_half(self._rms(self._mm(u, lw["q"]).reshape(T, H, hd),
                                  lw["q_norm"]), cos, sin)
        k = rotate_half(self._rms(self._mm(u, lw["k"]).reshape(T, G, hd),
                                  lw["k_norm"]), cos, sin)
        v = self._mm(u, lw["v"])
        k_pool, v_pool = pools
        outs = []
        for (_, rows, pos, valid, _), (S, w), q, k, v, t in zip(
                windows, shapes, split_rows(q.astype(self.dtype), shapes),
                split_rows(k.reshape(T, G * hd).astype(k_pool.dtype), shapes),
                split_rows(v.astype(v_pool.dtype), shapes), wrote):
            ((k_pool, v_pool),) = scatter_positions(
                ((k_pool, v_pool),), rows, t, ((k, v),),
                valid=jnp.broadcast_to(valid, (S, w)))
            with jax.named_scope("gqa_attention"):
                out = paged_window_attention(
                    jnp.transpose(q, (0, 2, 1, 3)), k_pool, v_pool, rows,
                    pos)                                   # [S, H, w, hd]
            outs.append(jnp.transpose(out, (0, 2, 1, 3))
                        .reshape(S, w, H * hd))
        return self._mm(lay_rows(outs), lw["o"]), (k_pool, v_pool)

    def _conv_layer(self, lw, u, pools, windows, shapes, stats):
        D = self.width
        (tail,) = pools
        bcx = self._mm(u, lw["in_proj"])
        gate_c = bcx[:, D:2 * D]
        bx = (bcx[:, :D] * bcx[:, 2 * D:]).astype(tail.dtype)
        taps_w = lw["conv"].astype(jnp.float32)            # [D, taps]
        outs = []
        with jax.named_scope("short_conv"):
            for (_, _, pos, valid, srows), (S, w), m in zip(
                    windows, shapes, split_rows(bx, shapes)):
                lens = jnp.sum(jnp.broadcast_to(valid, (S, w)),
                               axis=1).astype(jnp.int32)
                conv, tail = causal_conv_tail(m, tail, srows, pos, lens,
                                              taps_w)
                outs.append(conv)
                rows = jnp.sum(lens)
                stats = stats + jnp.stack([
                    rows if w == 1 else 0, 0 if w == 1 else rows,
                    0 if w == 1 else jnp.sum((pos > 0) & (lens > 0))
                ]).astype(jnp.int32)
        y = gate_c * lay_rows(outs)
        return self._mm(y, lw["out_proj"]), (tail,), stats

    def _expert_layer(self, u, lw, valid):
        """The held experts' part of ``MoE(u)`` over ``u`` [T, D] float32;
        and the counts: ``MOE_STATS`` and the pairs the bias moved."""
        logits = jnp.matmul(u, lw["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        bias = lw["expert_bias"].astype(jnp.float32) \
            if self.expert_bias else None
        with jax.named_scope("experts"):
            experts, weights = route_top_k(scores, top_k=self.top_k,
                                           bias=bias)
            if self.renorm:
                weights = weights / (jnp.sum(weights, axis=-1,
                                             keepdims=True) + 1e-6)
            routed, stats = dropless_moe(
                u.astype(self.dtype), experts, weights * self.scaling,
                lw["exp_gate"], lw["exp_up"], lw["exp_down"],
                held=self.experts_held, valid=valid,
                num_experts=self.experts)
        # a pair the unbiased scores would not have chosen
        _, plain = jax.lax.top_k(scores, self.top_k)
        moved = ~jnp.any(experts[:, :, None] == plain[:, None, :], axis=-1)
        biased = jnp.sum(moved & valid[:, None]).astype(jnp.int32)
        return routed, stats, biased

    # -- the walk -------------------------------------------------------------
    #: the walk takes any number of windows in one call
    several_windows = True

    def walk(self, params, windows, pools):
        """A tuple of WINDOWS, each ``(toks [S, w], rows, pos [S], valid,
        srows [S])`` — [S, w] token ids at per-slot global positions
        ``[pos[s], pos[s] + w)`` → ``(a tuple of [S, w, D] float32 hidden
        rows after the last block, one a window; updated pools; int32
        counts named by ``walk_stats``)``. ``valid`` [S, w] (or [S, 1])
        marks the real rows, a prefix of each slot's window; ``srows`` are
        the slots' rows in the per-sequence pools (the trash row for a
        slot that is not there). The decode step (``w`` = 1), a prefill
        window, or both in one call: the matrices, the router and the
        experts run ONCE over all the windows' rows laid end to end, the
        cache writes, attention and the convolution a window at a time.
        No head."""
        shapes = [win[0].shape for win in windows]
        wrote = window_positions(windows)                    # [S, w] each
        x = params["embed"][lay_rows([win[0] for win in windows])] \
            .astype(jnp.float32)
        ang = rope_angles(lay_rows(wrote), self._inv_freq)[:, None]
        rotary = (jnp.cos(ang), jnp.sin(ang))                # [T, 1, hd/2]
        token_valid = lay_rows([jnp.broadcast_to(win[3], win[0].shape)
                                for win in windows])
        n_moe = len(MOE_STATS)

        # ONE jitted function a kind of block (the blocks of a kind have
        # the same shapes, each its own weights): a program traces and
        # lowers three blocks, not every one
        @functools.partial(jax.jit, static_argnames=("mixer", "dense"))
        def layer(lw, x, layer_pools, windows, wrote, rotary, token_valid,
                  stats, mixer: str, dense: bool):
            u = self._rms(x, lw["operator_norm"])
            conv = stats[n_moe + 1:]
            if mixer == FULL:
                mixed, layer_pools = self._attention_layer(
                    lw, u, layer_pools, windows, shapes, wrote, rotary)
            else:
                mixed, layer_pools, conv = self._conv_layer(
                    lw, u, layer_pools, windows, shapes, conv)
            h = x + mixed
            v = self._rms(h, lw["ffn_norm"])
            moe = stats[:n_moe + 1]
            if dense:
                ffn = gated_silu(v, lw["w1"], lw["w3"], lw["w2"], self.dtype)
            else:
                ffn, counts, biased = self._expert_layer(v, lw, token_valid)
                moe = jnp.concatenate([
                    add_moe_stats(moe[:n_moe], counts),
                    moe[n_moe:] + biased])
            return h + ffn, tuple(layer_pools), jnp.concatenate([moe, conv])

        stats = jnp.zeros((len(self.walk_stats),), jnp.int32)
        new_pools = []
        for i, (lw, layer_pools) in enumerate(zip(params["layers"], pools)):
            x, layer_pools, stats = layer(
                lw, x, layer_pools, windows, wrote, rotary, token_valid,
                stats, mixer=self.mixers[i], dense=i < self.dense_layers)
            new_pools.append(layer_pools)
        return split_rows(x, shapes), tuple(new_pools), stats

    def logits(self, params, hidden):
        """The head, tied to the embedding, over the rows the caller
        picked out of a walk's hidden rows: [..., D] → [..., V] float32
        logits."""
        return jax.lax.dot_general(
            self._rms(hidden, params["final_norm"]).astype(self.dtype),
            params["embed"], (((hidden.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
