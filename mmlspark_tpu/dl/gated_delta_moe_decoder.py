"""A decoder that mixes gated DeltaNet (linear-attention) layers with gated
grouped-query attention layers, every block followed by a dropless expert
layer with a gated shared expert, for ``serving.llm.LLMEngine``: the block
Qwen3-Next publishes, built from a config dict with that model's
``config.json`` keys.

With ``x`` a token's hidden state (float32), ``N`` the zero-centred RMSNorm
``x rsqrt(mean x^2 + eps) (1 + w)`` in float32, every matrix product on
operands of the serving type with float32 accumulation, and layer ``i`` full
attention where ``(i + 1) % full_attention_interval == 0`` (or as
``layer_types`` says), else gated DeltaNet:

- block: ``h = x + Mixer(N(x))``, ``y = h + MoE(N(h))``; a final ``N``, an
  untied head; no biases.
- gated DeltaNet (``linear_num_key_heads`` key heads, ``linear_num_value_
  heads`` value heads): ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u
  W_ba``; a causal depthwise convolution of ``linear_conv_kernel_dim`` taps
  and SiLU over the channels ``[q | k | v]`` (rounded to the serving type
  first, as the tail holds them); a key head serves ``Hv / Hk`` value heads
  in turn; ``q`` and ``k`` normalised to unit length (``q`` also over
  ``sqrt(dk)``); ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)`` in float32; the gated delta rule over a float32 state a head
  (``dl.pallas_gated_delta``); a per-head RMSNorm (plain weight) times
  ``silu(z)``; ``W_out``. Its cache is TWO rows a sequence: the state
  ``[Hv, dk, dv]`` float32 and the convolution's TAIL, the last ``taps -
  1`` inputs ``[taps - 1, channels]`` of the serving type. Both are carried
  from window to window, so a prompt cut into any windows gives the same
  rows; a slot at position 0 starts from zeros whatever its rows hold.
- gated attention (``H`` query heads on ``G`` key heads of ``head_dim``): a
  head's ``2 head_dim`` columns of ``W_q`` are its query then its gate; ``q
  <- N(q)``, ``k <- N(k)`` over the head; rotary positions (``rotate_half``)
  on the first ``partial_rotary_factor`` of each head; causal softmax over
  the paged key and value pools ``[blocks, block_len, G head_dim]``, the
  ``H / G`` query heads of a key head the rows of one product
  (``pallas_paged_attention.paged_window_attention``); ``(attn *
  sigmoid(gate)) W_o``.
- expert layer: ``p = softmax(u W_r)`` in float32 over all ``num_experts``;
  the ``num_experts_per_tok`` largest (``models.moe.route_top_k``),
  renormalised to sum to 1 (``norm_topk_prob``); the layer is TOLD which
  experts it holds (``experts_held``, a range), computes their terms with
  one grouped product over the pairs sorted by expert
  (``models.moe.dropless_moe``) and leaves the absent ones out; plus
  ``sigmoid(u w_sg) E_shared(u)``.

The columns of ``W_qkvz`` and ``W_ba`` are contiguous (``[q | k | v | z]``,
``[b | a]``) where the family's code groups them by key head: a permutation
of the columns (``benchmark/references/qwen3_next.to_family_order``). No
multi-token prediction module.

The interface the engine asks of a decoder (``serving.llm``):
``cache_spec()`` (two ``"token"`` entries for an attention layer, two
``"seq"`` entries for a DeltaNet layer, ``dl.paged_kv``), ``max_window()``,
``program_key()``, ``walk`` — which takes the slots' state rows after the
arguments every decoder's walk takes — and ``logits``; ``walk_stats`` names
the counts a walk returns.

Parameters are a plain dict: ``embed`` [V, D], ``head`` [D, V],
``final_norm`` [D] and ``layers``, a list of dicts: always ``attn_norm``,
``ffn_norm``, ``router`` [D, E], ``exp_gate``/``exp_up`` [n, D, F],
``exp_down`` [n, F, D] over the held experts, ``shared_gate``/``shared_up``
[D, Fs], ``shared_down`` [Fs, D], ``shared_router`` [D, 1]; a DeltaNet layer
``qkvz``, ``ba``, ``conv`` [channels, taps], ``A_log``, ``dt_bias`` [Hv],
``o_norm`` [dv], ``o``; an attention layer ``q`` [D, H 2 hd], ``k``, ``v``
[D, G hd], ``q_norm``, ``k_norm`` [hd], ``o``. Every matrix is applied as
``x @ w``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models.moe import MOE_STATS, dropless_moe, route_top_k
from .decoder_blocks import (DictDecoder, lay_rows, rope_angles,
                             rotate_half_partial, split_rows,
                             window_positions)
from .paged_kv import scatter_positions
from .pallas_gated_delta import gated_delta_rule
from .pallas_paged_attention import paged_window_attention

__all__ = ["GatedDeltaMoEDecoder", "FULL", "LINEAR"]

FULL = "full_attention"
LINEAR = "linear_attention"
#: the counts of the DeltaNet layers, after the expert layers' ``moe_*``
GDN_STATS = ("gdn_step_rows", "gdn_chunk_rows", "gdn_windows_carried")


class GatedDeltaMoEDecoder(DictDecoder):
    """See the module docstring. ``config`` keeps whatever else it holds
    (``source``, ``reduced``, ``assumed``, ``published``, ``deployment``);
    ``dtype`` is the type of the weights, the matrix products' operands,
    the key/value cache and the convolution's tail; ``max_window`` the
    widest prefill window the walk is given."""

    walk_stats = tuple(f"moe_{name}" for name in MOE_STATS) + GDN_STATS

    def __init__(self, config: dict, *, dtype=jnp.bfloat16,
                 max_window: int = 512):
        self.config = dict(config)
        c = self.config
        self.dtype = jnp.dtype(dtype)
        self.width = int(c["hidden_size"])
        self.depth = int(c["num_hidden_layers"])
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.hd = int(c["head_dim"])
        self.rot = int(self.hd * float(c["partial_rotary_factor"]))
        self.k_heads = int(c["linear_num_key_heads"])
        self.v_heads = int(c["linear_num_value_heads"])
        self.dk = int(c["linear_key_head_dim"])
        self.dv = int(c["linear_value_head_dim"])
        self.taps = int(c["linear_conv_kernel_dim"])
        if self.heads % self.kv_heads or self.v_heads % self.k_heads:
            raise ValueError("key heads have to divide the heads they serve")
        if c.get("layer_types"):
            self.mixers = tuple(c["layer_types"])
        else:
            every = int(c["full_attention_interval"])
            self.mixers = tuple(FULL if (i + 1) % every == 0 else LINEAR
                                for i in range(self.depth))
        if len(self.mixers) != self.depth or \
                set(self.mixers) - {FULL, LINEAR}:
            raise ValueError("layer_types has to name a mixer of "
                             f"{FULL!r} | {LINEAR!r} for each layer")
        self.experts = int(c["num_experts"])
        self.top_k = int(c["num_experts_per_tok"])
        self.renorm = bool(c.get("norm_topk_prob", True))
        self.experts_held = tuple(int(e) for e in c.get(
            "experts_held", (0, self.experts)))
        self.eps = float(c["rms_norm_eps"])
        self._inv_freq = (1.0 / float(c["rope_theta"]) ** (
            np.arange(0, self.rot, 2, dtype=np.float64) / self.rot)
        ).astype(np.float32)
        self._max_window = int(max_window)

    # -- what the engine asks ------------------------------------------------
    @property
    def conv_channels(self) -> int:
        return 2 * self.k_heads * self.dk + self.v_heads * self.dv

    def cache_spec(self) -> tuple:
        """An attention layer caches a token's key heads side by side and
        its value heads side by side; a DeltaNet layer a state and a
        convolution tail a SEQUENCE."""
        width = self.kv_heads * self.hd
        full = (((width,), self.dtype), ((width,), self.dtype))
        linear = (((self.v_heads, self.dk, self.dv),
                   jnp.dtype(jnp.float32), "seq"),
                  ((self.taps - 1, self.conv_channels), self.dtype, "seq"))
        return tuple(full if m == FULL else linear for m in self.mixers)

    def max_window(self) -> int:
        return self._max_window

    def program_key(self) -> dict:
        c = self.config
        keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "partial_rotary_factor", "rope_theta",
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim", "num_experts",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_hidden_layers",
                "vocab_size")
        return {"decoder": "gated_delta_moe", "dtype": self.dtype.name,
                "layer_types": list(self.mixers),
                "experts_held": list(self.experts_held),
                "norm_topk_prob": self.renorm,
                "max_window": self._max_window, **{k: c[k] for k in keys}}

    # -- the mixers ----------------------------------------------------------
    # each takes ``u`` [T, D], the rows of all the windows end to end: its
    # projections and its gate run once over them; the cache writes,
    # attention, the convolution and the state's update a window at a time
    def _attention_layer(self, lw, u, pools, windows, shapes, wrote, rotary):
        T = u.shape[0]
        H, G, hd = self.heads, self.kv_heads, self.hd
        cos, sin = rotary
        qg = self._mm(u, lw["q"]).reshape(T, H, 2 * hd)
        q = rotate_half_partial(
            self._rms_centred(qg[..., :hd], lw["q_norm"]), cos, sin)
        gate = qg[..., hd:].reshape(T, H * hd)
        k = rotate_half_partial(self._rms_centred(
            self._mm(u, lw["k"]).reshape(T, G, hd), lw["k_norm"]), cos, sin)
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
        out = lay_rows(outs).astype(jnp.float32) * jax.nn.sigmoid(gate)
        return self._mm(out, lw["o"]), (k_pool, v_pool)

    def _delta_layer(self, lw, u, pools, windows, shapes, stats):
        T = u.shape[0]
        Hk, Hv, dk, dv, taps = (self.k_heads, self.v_heads, self.dk,
                                self.dv, self.taps)
        kd, cd = Hk * dk, self.conv_channels
        state, tail = pools
        qkvz = self._mm(u, lw["qkvz"])
        mixed = qkvz[:, :cd].astype(tail.dtype)
        z = qkvz[:, cd:].reshape(T, Hv, dv)
        ba = self._mm(u, lw["ba"])
        beta = jax.nn.sigmoid(ba[:, :Hv])
        g = -jnp.exp(lw["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, Hv:] + lw["dt_bias"].astype(jnp.float32))
        taps_w = lw["conv"].astype(jnp.float32)            # [cd, taps]
        outs = []
        for (_, _, pos, valid, srows), (S, w), m, b, gw in zip(
                windows, shapes, split_rows(mixed, shapes),
                split_rows(beta, shapes), split_rows(g, shapes)):
            lens = jnp.sum(jnp.broadcast_to(valid, (S, w)),
                           axis=1).astype(jnp.int32)
            # the convolution reads the window behind its last inputs
            before = jnp.where((pos > 0)[:, None, None], tail[srows],
                               jnp.zeros((), tail.dtype))
            ext = jnp.concatenate([before, m], axis=1)     # [S, taps-1+w]
            conv = sum(ext[:, j:j + w].astype(jnp.float32) * taps_w[:, j]
                       for j in range(taps))
            conv = jax.nn.silu(conv)
            keep = lens[:, None] + jnp.arange(taps - 1)[None]
            tail = tail.at[srows].set(jnp.take_along_axis(
                ext, keep[:, :, None], axis=1))
            q = conv[..., :kd].reshape(S, w, Hk, dk)
            k = conv[..., kd:2 * kd].reshape(S, w, Hk, dk)
            v = conv[..., 2 * kd:].reshape(S, w, Hv, dv)
            q = q * jax.lax.rsqrt(
                jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            q, k = (jnp.repeat(a, Hv // Hk, axis=2).astype(self.dtype)
                    for a in (q, k))
            with jax.named_scope("gated_delta"):
                o, state = gated_delta_rule(
                    q, k, v.astype(self.dtype), gw, b, state, srows, pos,
                    lens)
            outs.append(o)
            rows = jnp.sum(lens)
            stats = stats + jnp.stack([
                rows if w == 1 else 0, 0 if w == 1 else rows,
                0 if w == 1 else jnp.sum((pos > 0) & (lens > 0))
            ]).astype(jnp.int32)
        o = lay_rows(outs)                                  # [T, Hv, dv]
        o = self._rms(o, lw["o_norm"]) * jax.nn.silu(z)
        return self._mm(o.reshape(T, Hv * dv), lw["o"]), (state, tail), stats

    def _expert_layer(self, u, lw, valid):
        """``MoE(u)`` over ``u`` [T, D] float32: this holder's routed
        terms plus the gated shared expert's; and the counts."""
        logits = jnp.matmul(u, lw["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        experts, probs = route_top_k(jax.nn.softmax(logits, axis=-1),
                                     top_k=self.top_k)
        if self.renorm:
            probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        routed, stats = dropless_moe(
            u.astype(self.dtype), experts, probs, lw["exp_gate"],
            lw["exp_up"], lw["exp_down"], held=self.experts_held,
            valid=valid)
        shared = jax.nn.sigmoid(self._mm(u, lw["shared_router"])) \
            * self._gated(u, lw["shared_gate"], lw["shared_up"],
                          lw["shared_down"])
        return routed + shared, stats

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
        cache writes, attention, the convolution and the state's update a
        window at a time (a window's ``w`` picks the delta-rule kernel).
        No head."""
        shapes = [win[0].shape for win in windows]
        wrote = window_positions(windows)                    # [S, w] each
        x = params["embed"][lay_rows([win[0] for win in windows])] \
            .astype(jnp.float32)
        ang = rope_angles(lay_rows(wrote), self._inv_freq)[:, None]
        rotary = (jnp.cos(ang), jnp.sin(ang))                # [T, 1, rot/2]
        token_valid = lay_rows([jnp.broadcast_to(win[3], win[0].shape)
                                for win in windows])
        n_moe = len(MOE_STATS)

        # ONE jitted function a kind of layer (the layers of a kind have
        # the same shapes, each its own weights): a program traces and
        # lowers one attention and one DeltaNet block, not every one
        @functools.partial(jax.jit, static_argnames="full")
        def layer(lw, x, layer_pools, windows, wrote, rotary, token_valid,
                  stats, full: bool):
            u = self._rms_centred(x, lw["attn_norm"])
            gdn = stats[n_moe:]
            if full:
                mixed, layer_pools = self._attention_layer(
                    lw, u, layer_pools, windows, shapes, wrote, rotary)
            else:
                mixed, layer_pools, gdn = self._delta_layer(
                    lw, u, layer_pools, windows, shapes, gdn)
            h = x + mixed
            ffn, counts = self._expert_layer(
                self._rms_centred(h, lw["ffn_norm"]), lw, token_valid)
            moe = stats[:n_moe].at[:3].add(counts[:3]).at[3].max(counts[3])
            return h + ffn, tuple(layer_pools), jnp.concatenate([moe, gdn])

        stats = jnp.zeros((len(self.walk_stats),), jnp.int32)
        new_pools = []
        for i, (lw, layer_pools) in enumerate(zip(params["layers"], pools)):
            x, layer_pools, stats = layer(
                lw, x, layer_pools, windows, wrote, rotary, token_valid,
                stats, full=self.mixers[i] == FULL)
            new_pools.append(layer_pools)
        return split_rows(x, shapes), tuple(new_pools), stats

    def logits(self, params, hidden):
        """The head over the rows the caller picked out of a walk's
        hidden rows: [..., D] → [..., V] float32 logits."""
        return self._mm(self._rms_centred(hidden, params["final_norm"]),
                        params["head"])
