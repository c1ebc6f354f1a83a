"""A decoder of latent-attention blocks with dropless expert layers, for
``serving.llm.LLMEngine``: the block DeepSeek-V2 publishes
(arXiv:2405.04434), built from a config dict with that model's
``config.json`` keys.

With ``x`` a token's hidden state and ``RMS`` RMSNorm in float32, every
matrix product on operands of the serving type with float32 accumulation
and the residual stream kept in float32:

- block: ``h = x + Attn(RMS(x))``, ``y = h + FFN(RMS(h))``; the first
  ``first_k_dense_replace`` layers' ``FFN`` is a gated SiLU MLP, the
  others' an expert layer; a final ``RMS``, then an untied head;
- multi-head latent attention (``dl.latent_attention``): ``c_q = RMS(W_qa
  u)``; per head ``[q_nope, q_rope] = W_qb c_q``; ``[c, k_rope] = W_kva
  u``, ``c_kv = RMS(c)``; ``q_rope`` and the one shared ``k_rope`` rotated
  by the position (YaRN frequencies, the rotary numbers taken as
  interleaved pairs). The cache holds ``[c_kv, k_rope]`` a token a layer,
  ONE array. The paged walk computes the ABSORBED form — the same
  function as expanding ``[k_nope, v] = W_kvb c_kv`` per head (a test
  holds the two together), with all heads of a slot reading the same
  cached numbers (``pallas_paged_attention.paged_latent_attention``);
- expert layer: ``g = softmax(W_g u)`` in float32 over all the routed
  experts, group-limited greedy top-k (``models.moe.route_top_k``),
  weights ``routed_scaling_factor * g_e`` not renormalised, plus the
  shared experts' MLP. The layer is TOLD which experts it holds
  (``experts_held``, a range): it routes over all of them, computes its
  own experts' terms with one grouped product over the pairs sorted by
  expert (``models.moe.dropless_moe``) and leaves the absent ones out —
  one holder's share of an expert-parallel layer, without the exchange.

The interface the engine asks of a decoder (``serving.llm``):
``cache_spec()``, ``max_window()``, ``program_key()``, and through
``apply(variables, ..., method=...)`` ``walk`` (a window's hidden rows, no
head) and ``logits`` (the head over the rows the caller picked);
``walk_stats`` names the counts a walk returns beside its hidden rows.

Parameters are a plain dict (no flax): ``embed`` [V, D], ``head`` [D, V],
``final_norm`` [D] and ``layers``, a list of dicts — ``attn_norm``,
``ffn_norm``, ``q_a``, ``q_a_norm``, ``q_b``, ``kv_a``, ``kv_a_norm``,
``kv_b``, ``o``, then ``gate``/``up``/``down`` (dense) or ``router``,
``shared_gate``/``shared_up``/``shared_down`` and ``exp_gate``/``exp_up``
[n, D, F] / ``exp_down`` [n, F, D] over the held experts. Every matrix is
applied as ``x @ w``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models.moe import (MOE_STATS, add_moe_stats, dropless_moe,
                          route_top_k)
from .decoder_blocks import (DictDecoder, lay_rows, split_rows,
                             window_positions)
from .latent_attention import LatentAttention

__all__ = ["LatentMoEDecoder"]


class LatentMoEDecoder(DictDecoder):
    """See the module docstring. ``config`` keeps whatever else it holds
    (``source``, ``reduced``, ``assumed``); ``dtype`` is the type of the
    weights, the matrix products' operands and the cache."""

    walk_stats = tuple(f"moe_{name}" for name in MOE_STATS)

    def __init__(self, config: dict, *, dtype=jnp.bfloat16):
        self.config = dict(config)
        c = self.config
        self.dtype = jnp.dtype(dtype)
        self.width = int(c["hidden_size"])
        self.mla = LatentAttention(c, self.dtype)
        self.cache_width = self.mla.cache_width
        self.depth = int(c["num_hidden_layers"])
        self.dense_layers = int(c["first_k_dense_replace"])
        self.experts_held = tuple(int(e) for e in c.get(
            "experts_held", (0, c["n_routed_experts"])))
        self.eps = float(c["rms_norm_eps"])

    # -- what the engine asks ------------------------------------------------
    def cache_spec(self) -> tuple:
        """Each layer caches one array: the normalised latent and the
        rotated shared key, ``latent + rope`` numbers a token in
        ``cache_width`` lanes."""
        return ((((self.cache_width,), self.dtype),),) * self.depth

    def max_window(self) -> int:
        return self.mla.max_window()

    def program_key(self) -> dict:
        c = self.config
        keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
                "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "n_shared_experts", "n_group",
                "topk_group", "num_experts_per_tok", "num_hidden_layers",
                "first_k_dense_replace", "vocab_size", "rope_theta",
                "routed_scaling_factor")
        return {"decoder": "latent_moe", "dtype": self.dtype.name,
                "experts_held": list(self.experts_held),
                "rope_scaling": dict(c["rope_scaling"]),
                **{k: c[k] for k in keys}}

    # -- pieces (RMSNorm, the product, the gated MLP: DictDecoder) -----------
    def _expert_layer(self, u, lw, valid):
        """``FFN(u)`` of an expert layer over ``u`` [T, D] float32: this
        holder's routed terms plus the shared experts'; and the counts."""
        c = self.config
        logits = jnp.matmul(u, lw["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        experts, probs = route_top_k(
            jax.nn.softmax(logits, axis=-1),
            top_k=int(c["num_experts_per_tok"]), groups=int(c["n_group"]),
            keep_groups=int(c["topk_group"]))
        routed, stats = dropless_moe(
            u.astype(self.dtype), experts,
            probs * float(c["routed_scaling_factor"]), lw["exp_gate"],
            lw["exp_up"], lw["exp_down"], held=self.experts_held,
            valid=valid, num_experts=int(c["n_routed_experts"]))
        return routed + self._gated(u, lw["shared_gate"], lw["shared_up"],
                                    lw["shared_down"]), stats

    # -- the walk -------------------------------------------------------------
    #: the walk takes any number of windows in one call
    several_windows = True

    def walk(self, params, windows, pools):
        """A tuple of WINDOWS, each ``(toks [S, w], rows, pos [S], valid)``
        — [S, w] token ids at per-slot global positions ``[pos[s], pos[s]
        + w)`` → ``(a tuple of [S, w, D] float32 hidden rows after the
        last block, one a window; updated pools; int32 counts named by
        ``walk_stats``)``, reading and writing the latent pools IN PLACE
        through each window's block table. The decode step (``w`` = 1), a
        prefill window, or both in one call.

        Everything that acts a row at a time (norms, the projections, the
        absorbed query, the output projection, the router and the experts)
        runs ONCE over all the windows' rows laid end to end, so each
        weight is read once and a held expert sees the pairs of all the
        rows; per layer and window the ``[c_kv, k_rope]`` entries are
        scattered first (``valid`` False sends a row's write to the trash
        block), then every row attends its slot's chain up to itself. No
        head: the caller picks the rows a token is sampled from and asks
        :meth:`logits` for those alone."""
        shapes = [win[0].shape for win in windows]
        wrote = window_positions(windows)                    # [S, w] each
        x = params["embed"][lay_rows([win[0] for win in windows])] \
            .astype(jnp.float32)                             # [T, D]
        rotary = self.mla.rotary(lay_rows(wrote))            # [T, R/2]
        token_valid = lay_rows([jnp.broadcast_to(win[3], win[0].shape)
                                for win in windows])
        tables = tuple((rows, pos, valid, at) for (_, rows, pos, valid), at
                       in zip(windows, wrote))

        # ONE jitted function a kind of layer (the layers of a kind have
        # the same shapes, each its own weights): a program traces and
        # lowers one expert layer, not every one
        @functools.partial(jax.jit, static_argnames="dense")
        def layer(lw, x, pool, tables, rotary, token_valid, dense: bool):
            mixed, pool = self.mla(lw, self._rms(x, lw["attn_norm"]), pool,
                                   tables, shapes, rotary)
            h = x + mixed
            u = self._rms(h, lw["ffn_norm"])
            if dense:
                return h + self._gated(u, lw["gate"], lw["up"],
                                       lw["down"]), pool, None
            ffn, counts = self._expert_layer(u, lw, token_valid)
            return h + ffn, pool, counts

        stats = jnp.zeros((len(MOE_STATS),), jnp.int32)
        new_pools = []
        for i, (lw, (pool,)) in enumerate(zip(params["layers"], pools)):
            x, pool, counts = layer(lw, x, pool, tables, rotary,
                                    token_valid,
                                    dense=i < self.dense_layers)
            new_pools.append((pool,))
            if counts is not None:
                stats = add_moe_stats(stats, counts)
        return split_rows(x, shapes), tuple(new_pools), stats

    def logits(self, params, hidden):
        """The head over the rows the caller picked out of a walk's
        hidden rows: [..., D] → [..., V] float32 logits (the final norm,
        per row, then the untied head)."""
        return self._mm(self._rms(hidden, params["final_norm"]),
                        params["head"])
