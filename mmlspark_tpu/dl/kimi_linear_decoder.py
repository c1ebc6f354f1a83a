"""A decoder that mixes Kimi Delta Attention (KDA) layers with latent
attention (MLA) layers that take no positions, a dropless expert layer with
a shared expert after every mixer but the first, for
``serving.llm.LLMEngine``: the block Kimi-Linear publishes, built from a
config dict with that model's ``config.json`` keys.

With ``x`` a token's hidden state (float32), ``RMS`` RMSNorm in float32,
every matrix product on operands of the serving type with float32
accumulation, and layer ``i`` MLA where ``i + 1`` is in
``linear_attn_config["full_attn_layers"]``, KDA where it is in
``["kda_layers"]`` (1-indexed, as published):

- block: ``h = x + Mixer(RMS(x))``, ``y = h + FFN(RMS(h))``; the first
  ``first_k_dense_replace`` layers' ``FFN`` a gated SiLU MLP, the others'
  an expert layer; a final ``RMS``, an untied head; no biases but the
  output gate's.
- KDA (``num_heads`` heads of ``head_dim``): ``[q | k | v] = u W_qkv``;
  each through its own causal depthwise convolution of
  ``short_conv_kernel_size`` taps and SiLU (one convolution over the
  channels ``[q | k | v]``, rounded to the serving type first as the tail
  holds them); ``q`` and ``k`` normalised to unit length a head (``q`` also
  over ``sqrt(dk)``); ``beta = sigmoid(u W_b)`` a head; a decay for each
  KEY CHANNEL ``g = -exp(A_log_h) softplus(W_fb (W_fa u) + dt_bias)`` in
  float32; the gated delta rule with that decay over a float32 state a
  head (``dl.pallas_kda``); ``RMS_head(o) * w * sigmoid(W_gb (W_ga u) +
  b_g)``; ``W_o``. Its cache is TWO rows a sequence: the state ``[H, dk,
  dv]`` float32 and the convolution's TAIL, the last ``taps - 1`` inputs
  ``[taps - 1, 3 H dk]`` of the serving type, carried from window to
  window, so that a prompt cut into any windows gives the same rows; a
  slot at position 0 starts from zeros whatever its rows hold.
- MLA (``dl.latent_attention``): the query without low rank
  (``q_lora_rank`` null), no rotary anywhere (``mla_use_nope``): ONE
  latent array a token.
- expert layer: ``p = sigmoid(u W_r)`` in float32 over all
  ``num_experts``; the ``num_experts_per_token`` best by ``p`` plus a
  correction bias that CHOOSES and does not weight
  (``models.moe.route_top_k``, one group); their ``p`` renormalised to sum
  to 1 (``moe_renormalize``) and times ``routed_scaling_factor``; the
  layer is TOLD which experts it holds (``experts_held``, a range),
  computes their terms with one grouped product over the pairs sorted by
  expert (``models.moe.dropless_moe``) and leaves the absent ones out;
  plus the ``num_shared_experts`` shared experts' gated SiLU MLP, ungated.

The interface the engine asks of a decoder (``serving.llm``):
``cache_spec()`` (one ``"token"`` entry for an MLA layer, two ``"seq"``
entries for a KDA layer, ``dl.paged_kv``), ``max_window()``,
``program_key()``, ``walk`` — which takes the slots' state rows after the
arguments every decoder's walk takes — and ``logits``; ``walk_stats`` names
the counts a walk returns.

Parameters are a plain dict: ``embed`` [V, D], ``head`` [D, V],
``final_norm`` [D] and ``layers``, a list of dicts: always ``attn_norm``,
``ffn_norm``; a dense layer ``gate``/``up`` [D, I], ``down`` [I, D]; an
expert layer ``router`` [D, E], ``router_bias`` [E], ``exp_gate``/
``exp_up`` [n, D, F], ``exp_down`` [n, F, D] over the held experts,
``shared_gate``/``shared_up`` [D, Fs], ``shared_down`` [Fs, D]; a KDA
layer ``qkv`` [D, 3 H dk], ``b`` [D, H], ``f_a`` [D, dv], ``f_b`` [dv, H
dk], ``g_a`` [D, dv], ``g_b`` [dv, H dv], ``g_bias`` [H dv], ``conv`` [3 H
dk, taps], ``A_log`` [H], ``dt_bias`` [H dk], ``o_norm`` [dv], ``o`` [H
dv, D]; an MLA layer ``q``, ``kv_a``, ``kv_a_norm``, ``kv_b``, ``o``
(``dl.latent_attention``). Every matrix is applied as ``x @ w``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models.moe import (MOE_STATS, add_moe_stats, dropless_moe,
                          route_top_k)
from .decoder_blocks import (DictDecoder, causal_conv_tail, lay_rows,
                             split_rows, window_positions)
from .latent_attention import LatentAttention
from .pallas_kda import kda_rule

__all__ = ["KimiLinearDecoder", "KDA", "MLA", "KDA_STATS"]

KDA = "kda"
MLA = "mla"
#: the counts of the KDA and MLA layers, after the expert layers' ``moe_*``:
#: decoding rows and window rows through the KDA rule (each decoding row
#: reads and writes its state row, a layer), the windows (each reads and
#: writes its state row once, a layer), those that start from a carried
#: state, and the cached positions the decoding rows' latent attention
#: reads (a row at position ``p`` reads ``p + 1``), all summed over layers
KDA_STATS = ("kda_step_rows", "kda_chunk_rows", "kda_windows",
             "kda_windows_carried", "latent_decode_positions")


class KimiLinearDecoder(DictDecoder):
    """See the module docstring. ``config`` keeps whatever else it holds
    (``source``, ``reduced``, ``assumed``, ``published``, ``deployment``);
    ``dtype`` is the type of the weights, the matrix products' operands,
    the latent cache and the convolution's tail; ``max_window`` the widest
    prefill window the walk is given (no wider than the latent kernel
    takes)."""

    walk_stats = tuple(f"moe_{name}" for name in MOE_STATS) + KDA_STATS

    def __init__(self, config: dict, *, dtype=jnp.bfloat16,
                 max_window: int = 512):
        self.config = dict(config)
        c = self.config
        self.dtype = jnp.dtype(dtype)
        self.width = int(c["hidden_size"])
        self.depth = int(c["num_hidden_layers"])
        lin = c["linear_attn_config"]
        full = {int(i) for i in lin["full_attn_layers"]}
        kda = {int(i) for i in lin["kda_layers"]}
        if full & kda or full | kda != set(range(1, self.depth + 1)):
            raise ValueError("full_attn_layers and kda_layers have to name "
                             f"each of the layers 1..{self.depth} once")
        self.mixers = tuple(MLA if i + 1 in full else KDA
                            for i in range(self.depth))
        self.heads = int(lin["num_heads"])
        self.dk = self.dv = int(lin["head_dim"])
        self.taps = int(lin["short_conv_kernel_size"])
        self.mla = LatentAttention(c, self.dtype)
        self.dense_layers = int(c["first_k_dense_replace"])
        self.experts = int(c["num_experts"])
        self.top_k = int(c["num_experts_per_token"])
        self.renorm = bool(c["moe_renormalize"])
        self.scaling = float(c["routed_scaling_factor"])
        self.experts_held = tuple(int(e) for e in c.get(
            "experts_held", (0, self.experts)))
        self.eps = float(c["rms_norm_eps"])
        self._max_window = min(int(max_window), self.mla.max_window())

    # -- what the engine asks ------------------------------------------------
    @property
    def conv_channels(self) -> int:
        return 3 * self.heads * self.dk

    def cache_spec(self) -> tuple:
        """An MLA layer caches ONE latent array a token; a KDA layer a
        state and a convolution tail a SEQUENCE."""
        latent = (((self.mla.cache_width,), self.dtype),)
        kda = (((self.heads, self.dk, self.dv), jnp.dtype(jnp.float32),
                "seq"),
               ((self.taps - 1, self.conv_channels), self.dtype, "seq"))
        return tuple(latent if m == MLA else kda for m in self.mixers)

    def max_window(self) -> int:
        return self._max_window

    def program_key(self) -> dict:
        c = self.config
        keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
                "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "mla_use_nope", "intermediate_size",
                "moe_intermediate_size", "num_experts",
                "num_experts_per_token", "num_shared_experts",
                "first_k_dense_replace", "moe_renormalize",
                "routed_scaling_factor", "num_hidden_layers", "vocab_size")
        return {"decoder": "kimi_linear", "dtype": self.dtype.name,
                "layers": list(self.mixers),
                "linear_attn_config": dict(c["linear_attn_config"]),
                "experts_held": list(self.experts_held),
                "max_window": self._max_window, **{k: c[k] for k in keys}}

    # -- the mixers ----------------------------------------------------------
    # each takes ``u`` [T, D], the rows of all the windows end to end: its
    # projections and its gates run once over them; the cache writes,
    # attention, the convolution and the state's update a window at a time
    def _kda_layer(self, lw, u, pools, windows, shapes, stats):
        T = u.shape[0]
        H, dk, dv = self.heads, self.dk, self.dv
        kd = H * dk
        state, tail = pools
        mixed = self._mm(u, lw["qkv"]).astype(tail.dtype)
        beta = jax.nn.sigmoid(self._mm(u, lw["b"]))              # [T, H]
        f = self._mm(self._mm(u, lw["f_a"]), lw["f_b"])
        g = (-jnp.exp(lw["A_log"].astype(jnp.float32))[:, None]
             * jax.nn.softplus(f.reshape(T, H, dk) + lw["dt_bias"].astype(
                 jnp.float32).reshape(H, dk)))                   # [T, H, dk]
        gate = self._mm(self._mm(u, lw["g_a"]), lw["g_b"]) \
            + lw["g_bias"].astype(jnp.float32)
        taps_w = lw["conv"].astype(jnp.float32)             # [3 kd, taps]
        outs = []
        for (_, _, pos, valid, srows), (S, w), m, b, gw in zip(
                windows, shapes, split_rows(mixed, shapes),
                split_rows(beta, shapes), split_rows(g, shapes)):
            lens = jnp.sum(jnp.broadcast_to(valid, (S, w)),
                           axis=1).astype(jnp.int32)
            conv, tail = causal_conv_tail(m, tail, srows, pos, lens, taps_w,
                                          jax.nn.silu)
            q = conv[..., :kd].reshape(S, w, H, dk)
            k = conv[..., kd:2 * kd].reshape(S, w, H, dk)
            v = conv[..., 2 * kd:].reshape(S, w, H, dv)
            q = q * jax.lax.rsqrt(
                jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            with jax.named_scope("kda"):
                o, state = kda_rule(
                    q.astype(self.dtype), k.astype(self.dtype),
                    v.astype(self.dtype), gw, b, state, srows, pos, lens)
            outs.append(o)
            rows, live = jnp.sum(lens), lens > 0
            stats = stats + jnp.stack([
                rows if w == 1 else 0, 0 if w == 1 else rows,
                0 if w == 1 else jnp.sum(live),
                0 if w == 1 else jnp.sum((pos > 0) & live), 0
            ]).astype(jnp.int32)
        o = lay_rows(outs)                                  # [T, H, dv]
        o = self._rms(o, lw["o_norm"]).reshape(T, H * dv) \
            * jax.nn.sigmoid(gate)
        return self._mm(o, lw["o"]), (state, tail), stats

    def _mla_layer(self, lw, u, pools, tables, shapes, rotary, stats):
        (pool,) = pools
        mixed, pool = self.mla(lw, u, pool, tables, shapes, rotary)
        read = sum(jnp.sum(jnp.where(jnp.any(valid, axis=-1), pos + 1, 0))
                   for (_, pos, valid, _), (_, w) in zip(tables, shapes)
                   if w == 1)
        stats = stats.at[-1].add(jnp.asarray(read, jnp.int32))
        return mixed, (pool,), stats

    def _expert_layer(self, u, lw, valid):
        """``FFN(u)`` of an expert layer over ``u`` [T, D] float32: this
        holder's routed terms plus the shared experts'; and the counts."""
        logits = jnp.matmul(u, lw["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        with jax.named_scope("experts"):
            experts, weights = route_top_k(
                jax.nn.sigmoid(logits), top_k=self.top_k,
                bias=lw["router_bias"].astype(jnp.float32))
            if self.renorm:
                weights = weights / (jnp.sum(weights, axis=-1,
                                             keepdims=True) + 1e-20)
            routed, stats = dropless_moe(
                u.astype(self.dtype), experts, weights * self.scaling,
                lw["exp_gate"], lw["exp_up"], lw["exp_down"],
                held=self.experts_held, valid=valid,
                num_experts=self.experts)
        return routed + self._gated(u, lw["shared_gate"], lw["shared_up"],
                                    lw["shared_down"]), stats

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
        latent writes and attention, the convolution and the state's
        update a window at a time (a window's ``w`` picks the KDA
        kernel). No head."""
        shapes = [win[0].shape for win in windows]
        wrote = window_positions(windows)                    # [S, w] each
        x = params["embed"][lay_rows([win[0] for win in windows])] \
            .astype(jnp.float32)
        rotary = self.mla.rotary(lay_rows(wrote))
        token_valid = lay_rows([jnp.broadcast_to(win[3], win[0].shape)
                                for win in windows])
        tables = tuple((rows, pos, jnp.broadcast_to(valid, toks.shape), at)
                       for (toks, rows, pos, valid, _), at
                       in zip(windows, wrote))
        n_moe = len(MOE_STATS)

        # ONE jitted function a kind of layer (the layers of a kind have
        # the same shapes, each its own weights): a program traces and
        # lowers one block of each kind, not every one
        @functools.partial(jax.jit, static_argnames=("mixer", "dense"))
        def layer(lw, x, layer_pools, windows, tables, rotary, token_valid,
                  stats, mixer: str, dense: bool):
            u = self._rms(x, lw["attn_norm"])
            own = stats[n_moe:]
            if mixer == MLA:
                mixed, layer_pools, own = self._mla_layer(
                    lw, u, layer_pools, tables, shapes, rotary, own)
            else:
                mixed, layer_pools, own = self._kda_layer(
                    lw, u, layer_pools, windows, shapes, own)
            h = x + mixed
            v = self._rms(h, lw["ffn_norm"])
            if dense:
                return h + self._gated(v, lw["gate"], lw["up"], lw["down"]), \
                    tuple(layer_pools), jnp.concatenate([stats[:n_moe], own])
            ffn, counts = self._expert_layer(v, lw, token_valid)
            moe = add_moe_stats(stats[:n_moe], counts)
            return h + ffn, tuple(layer_pools), jnp.concatenate([moe, own])

        stats = jnp.zeros((len(self.walk_stats),), jnp.int32)
        new_pools = []
        for i, (lw, layer_pools) in enumerate(zip(params["layers"], pools)):
            x, layer_pools, stats = layer(
                lw, x, layer_pools, windows, tables, rotary, token_valid,
                stats, mixer=self.mixers[i], dense=i < self.dense_layers)
            new_pools.append(layer_pools)
        return split_rows(x, shapes), tuple(new_pools), stats

    def logits(self, params, hidden):
        """The head over the rows the caller picked out of a walk's
        hidden rows: [..., D] → [..., V] float32 logits."""
        return self._mm(self._rms(hidden, params["final_norm"]),
                        params["head"])
