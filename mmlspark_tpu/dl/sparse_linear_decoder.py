"""A decoder that mixes block-sparse grouped-query attention layers with
lightning (linear) attention layers, for ``serving.llm.LLMEngine``: the
block MiniCPM-SALA publishes, built from a config dict with that model's
``config.json`` keys and driven by its ``mixer_types``.

With ``x`` a token's hidden state, ``RMS`` RMSNorm in float32, every matrix
product on operands of the serving type with float32 accumulation, the
residual stream kept in float32, and ``r = scale_depth / sqrt(published
num_hidden_layers)``:

- ``x = scale_emb * E[token]``; block: ``h = x + r * Mixer(RMS(x))``, ``y =
  h + r * MLP(RMS(h))`` with a gated SiLU MLP; the head reads ``RMS(x) /
  (hidden_size / dim_model_base)``; untied, no biases.
- ``lightning-attn``: ``q, k, v = W u`` as ``[heads, hd]``; per-head RMSNorm
  on q and k; rotary positions on both (``rotate_half`` pairing); ``q *
  hd^-0.5``; per head a state ``S`` [hd, hd] float32 a SEQUENCE, ``S_t =
  lam S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``, ``lam = exp(-slope)``
  (:func:`lightning_slopes`); ``o`` through a per-head RMSNorm, times
  ``sigmoid(W_g u)``, then ``W_o``. Its cache is ONE row of the layer's
  state pool (``dl.pallas_lightning``), not an entry a token.
- ``minicpm4`` (InfLLM-v2 block-sparse attention): ``q`` [heads, hd] over
  ``k, v`` [kv_heads, hd], per-head RMSNorm on q and k, no positions,
  scale ``hd^-0.5``, causal. A query whose context (itself included) is at
  most ``dense_len`` attends all of it. Beyond: COMPRESSED keys ``ck_j =
  mean(k[stride*j : stride*j + kernel_size])``; per query head ``p =
  softmax_j(q . ck_j * hd^-0.5)`` over the compressed keys that lie wholly
  at or before the query; summed over the query heads that share a key
  head; max-pooled onto blocks of ``block_size`` tokens (a block takes the
  largest ``p`` of the compressed keys that overlap it); the first
  ``init_blocks`` blocks and the blocks that hold any of the last
  ``window_size`` tokens always chosen; the ``topk`` best blocks a key
  head in all (ties: the earlier block); softmax attention over the
  tokens of the chosen blocks. ``o`` times ``sigmoid(W_g u)``, then
  ``W_o``. Its cache: an entry a token (the key heads side by side, each
  head's key and value side by side) and one compressed key every
  ``stride`` tokens, which rests with the block that holds its LAST
  token (so compressed key ``j`` is row ``j + 1`` of the chain's rows);
  the paged walk scores with ``pallas_paged_attention.select_scores``,
  chooses here, and attends the chosen blocks with
  ``sparse_block_attention`` — below ``dense_len`` the list is every
  block up to the query and the same kernel walks the whole chain.

The interface the engine asks of a decoder (``serving.llm``):
``cache_spec()`` (here with all three kinds of entry, ``dl.paged_kv``),
``max_window()``, ``program_key()``, ``walk`` — which takes the slots'
state rows after the arguments every decoder's walk takes — and ``logits``;
``walk_stats`` names the counts a walk returns.

Parameters are a plain dict: ``embed`` [V, D], ``head`` [D, V],
``final_norm`` [D] and ``layers``, a list of dicts: ``attn_norm``,
``ffn_norm``, ``q``, ``k``, ``v``, ``g``, ``o``, ``q_norm``, ``k_norm``
[hd], ``gate``/``up``/``down``, and ``o_norm`` [hd] in a lightning layer.
Every matrix is applied as ``x @ w``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .decoder_blocks import (DictDecoder, lay_rows, rope_angles,
                             rotate_half, split_rows, window_positions)
from .paged_kv import scatter_positions, scatter_rows
from .pallas_lightning import lightning_attention
from .pallas_paged_attention import select_scores, sparse_block_attention

__all__ = ["SparseLinearDecoder", "lightning_slopes", "SPARSE", "LIGHTNING"]

SPARSE = "minicpm4"
LIGHTNING = "lightning-attn"
_FORCED = 1e30       # the score of a block that is always chosen


def published_depth(cfg: dict) -> int:
    return int(cfg.get("published", {}).get(
        "num_hidden_layers", cfg["num_hidden_layers"]))


def lightning_slopes(cfg: dict, layer: int) -> np.ndarray:
    """``[lightning_nh]`` float32 decay slopes of held layer ``layer``:
    the Lightning-Attention convention ``2^(-8 (h + 1) / heads)`` times
    its per-layer factor ``1 - l / (L - 1) + 1e-5``, ``l`` the layer's
    PUBLISHED index (``layer_offset`` + ``layer``) and ``L`` the published
    depth: early layers forget fast, late ones hardly."""
    H = int(cfg["lightning_nh"])
    L = published_depth(cfg)
    l = int(cfg.get("layer_offset", 0)) + int(layer)
    base = 2.0 ** (-8.0 * (np.arange(H, dtype=np.float64) + 1.0) / H)
    return (base * (1.0 - l / max(L - 1, 1) + 1e-5)).astype(np.float32)


class SparseLinearDecoder(DictDecoder):
    """See the module docstring. ``config`` keeps whatever else it holds
    (``source``, ``reduced``, ``assumed``, ``published``, ``deployment``);
    ``dtype`` is the type of the weights, the matrix products' operands
    and the key/value cache; ``max_window`` the widest prefill window the
    walk is given (the scores of a window against every compressed key
    are held at once), cut to what the lists of its rows leave of the
    scalar memory."""

    walk_stats = ("sparse_blocks_chosen", "sparse_blocks_in_chain",
                  "sparse_dense_rows")

    def __init__(self, config: dict, *, dtype=jnp.bfloat16,
                 max_window: int = 256):
        self.config = dict(config)
        c = self.config
        self.dtype = jnp.dtype(dtype)
        self.width = int(c["hidden_size"])
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.hd = int(c["head_dim"])
        self.l_heads = int(c["lightning_nh"])
        self.l_hd = int(c["lightning_head_dim"])
        if int(c["lightning_nkv"]) != self.l_heads:
            raise ValueError("lightning_nkv has to equal lightning_nh")
        self.mixers = tuple(c["mixer_types"])
        self.depth = int(c["num_hidden_layers"])
        if len(self.mixers) != self.depth or \
                set(self.mixers) - {SPARSE, LIGHTNING}:
            raise ValueError("mixer_types has to name a mixer of "
                             f"{SPARSE!r} | {LIGHTNING!r} for each layer")
        self.eps = float(c["rms_norm_eps"])
        sp = c["sparse_config"]
        self.stride = int(sp["kernel_stride"])
        if int(sp["kernel_size"]) != 2 * self.stride:
            raise ValueError("kernel_size has to be twice kernel_stride")
        self.block = int(sp["block_size"])
        self.topk = int(sp["topk"])
        self.init_blocks = int(sp["init_blocks"])
        self.window = int(sp["window_size"])
        self.dense_len = int(sp["dense_len"])
        self.res_scale = float(c["scale_depth"]) / math.sqrt(
            published_depth(c))
        self.head_div = self.width / float(c["dim_model_base"])
        self._inv_freq = (1.0 / float(c["rope_theta"]) ** (
            np.arange(0, self.l_hd, 2, dtype=np.float64) / self.l_hd)
        ).astype(np.float32)
        self._slopes = [lightning_slopes(c, i) for i in range(self.depth)]
        # a window's lists ride the sparse kernel's scalar prefetch: two
        # int32 a (row, key head, entry), held under three quarters of the
        # 1 MiB of scalar memory (first chip sweep, PR 33: 512 rows of 2
        # x 128 entries asked for 1.00 MiB and were refused)
        entries = max(self.topk, -(-self.dense_len // self.block))
        fits = (768 << 10) // (self.kv_heads * entries * 8)
        fits = fits // 64 * 64 if fits >= 64 else max(fits, 1)
        self._max_window = min(int(max_window), fits)

    # -- what the engine asks ------------------------------------------------
    def cache_spec(self) -> tuple:
        # a token's key heads side by side, each head's key and value
        # side by side: a chosen block of one head is one copy
        width = self.kv_heads * self.hd
        sparse = (((2 * width,), self.dtype),
                  ((width,), self.dtype, ("every", self.stride)))
        state = (((self.l_heads, self.l_hd, self.l_hd),
                  jnp.dtype(jnp.float32), "seq"),)
        return tuple(sparse if m == SPARSE else state for m in self.mixers)

    def max_window(self) -> int:
        return self._max_window

    def program_key(self) -> dict:
        c = self.config
        keys = ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "lightning_nh",
                "lightning_head_dim", "num_hidden_layers", "vocab_size",
                "rope_theta", "scale_emb", "scale_depth", "dim_model_base")
        return {"decoder": "sparse_linear", "dtype": self.dtype.name,
                "mixer_types": list(self.mixers),
                "sparse_config": dict(c["sparse_config"]),
                "layer_offset": int(c.get("layer_offset", 0)),
                "published_depth": published_depth(c),
                "max_window": self._max_window,
                **{k: c[k] for k in keys}}

    # -- block selection -----------------------------------------------------
    def _choose(self, scores, t, rows, block_len: int):
        """The list each (token, key head) attends. ``scores`` [S, G, w, R,
        NCR] the scoring pass's ``q . ck`` (column ``c`` the chain's row
        ``c``: compressed key ``c - 1``), ``t`` [S, w] the tokens'
        positions. Returns ``(phys, logical [S, w, G, K], dense [S, w])``
        as ``sparse_block_attention`` takes them."""
        S, G, w, R, ncr = scores.shape
        st, bs = self.stride, self.block
        per = bs // st
        nblk = ncr // per
        col = jnp.arange(ncr)
        # key c - 1 = tokens [st (c - 1), st (c + 1)): whole by token t
        usable = (col >= 1) & (st * (col + 1) - 1 <= t[..., None])
        p = jax.nn.softmax(jnp.where(usable[:, None, :, None], scores,
                                     -jnp.inf), axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p).sum(axis=3)   # [S, G, w, ncr]
        # block m overlaps keys per*m - 1 .. per*m + per - 1: columns
        # per*m .. per*m + per. Pooled with the columns on the SUBLANES (a
        # reshape to [..., nblk, per] with the columns minor lays 4
        # numbers on 128 lanes: first chip runs, PR 33, 0.12 of 3.1 busy
        # seconds a layer)
        cols = p.reshape(S * G * w, ncr).T                # [ncr, rows]
        inner = cols.reshape(nblk, per, -1).max(axis=1)
        nxt = jnp.concatenate(
            [cols[per::per], jnp.zeros((1, cols.shape[1]), p.dtype)])
        blk = jnp.maximum(inner, nxt).T.reshape(S, G, w, nblk)
        m = jnp.arange(nblk)
        last = t // bs                                    # the query's own
        forced = (m < self.init_blocks) | (
            m * bs + bs - 1 >= t[..., None] - (self.window - 1))
        cand = jnp.where((m <= last[..., None])[:, None],
                         jnp.where(forced[:, None], _FORCED, blk), -1.0)
        k = min(self.topk, nblk)
        K = min(max(self.topk, -(-self.dense_len // bs)), nblk)
        value, chosen = jax.lax.top_k(cand, k)
        chosen = jnp.where(value >= 0, chosen, -1)
        chosen = jnp.pad(chosen, ((0, 0),) * 3 + ((0, K - k),),
                         constant_values=-1)
        dense = t + 1 <= self.dense_len                   # [S, w]
        every = jnp.arange(K)
        every = jnp.where(every <= last[..., None], every, -1)
        # either list has its real entries first (``top_k`` puts the -1s
        # last), which is all the kernel asks: no sort
        logical = jnp.where(dense[:, None, :, None], every[:, None], chosen)
        seen = logical >= 0
        entry = jnp.clip(logical * bs // block_len, 0, rows.shape[1] - 1)
        block = jnp.take_along_axis(
            jnp.broadcast_to(rows[:, None, None, :], (S, G, w,
                                                      rows.shape[1])),
            entry, axis=-1)
        phys = block * (block_len // bs) + logical * bs % block_len // bs
        count = seen.sum(axis=-1, keepdims=True)
        tail = jnp.take_along_axis(phys, jnp.maximum(count - 1, 0), axis=-1)
        phys = jnp.where(seen, phys, jnp.where(count > 0, tail, 0))
        return (jnp.transpose(phys, (0, 2, 1, 3)),
                jnp.transpose(logical, (0, 2, 1, 3)), dense)

    def _compressed(self, kv_pool, ck_pool, rows, pos, lens, w: int):
        """Write the compressed keys that the window's real rows
        ``[pos, pos + lens)`` complete: column ``c`` (key ``c - 1``) is
        whole when token ``stride * (c + 1) - 1`` is written; the mean of
        its ``2 * stride`` keys as they rest in the pool, in float32."""
        st = self.stride
        G, hd = self.kv_heads, self.hd
        NB, BL = kv_pool.shape[:2]
        n = w // st + 1
        col = jnp.maximum(pos // st, 1)[:, None] + jnp.arange(n)[None]
        end = st * (col + 1) - 1                          # [S, n]
        done = (end >= pos[:, None]) & (end < (pos + lens)[:, None])
        tok = st * (col - 1)[..., None] + jnp.arange(2 * st)   # [S, n, 2st]
        entry = jnp.clip(tok // BL, 0, rows.shape[1] - 1)
        flat = jnp.take_along_axis(rows[:, None, :], entry, axis=2) * BL \
            + tok % BL
        # rows first, heads after: a pool viewed [tokens, G, 2 hd] would be
        # laid out anew, all of it, in every call (second chip runs, PR 33:
        # 0.13 of 3.3 busy seconds a layer)
        keys = kv_pool.reshape(NB * BL, G * 2 * hd)[flat]
        keys = keys.reshape(*keys.shape[:3], G, 2 * hd)[..., :hd]
        mean = jnp.mean(keys.astype(jnp.float32), axis=2)  # [S, n, G, hd]
        mean = mean.reshape(*mean.shape[:2], G * hd)
        return scatter_rows(ck_pool, rows, col, mean.astype(ck_pool.dtype),
                            done)

    # -- the mixers ----------------------------------------------------------
    # each takes ``u`` [T, D], the rows of all the windows end to end: its
    # projections and its gate run once over them; the cache writes, the
    # choosing and attention run a window at a time
    def _sparse_layer(self, lw, u, pools, windows, shapes, wrote, stats):
        T = u.shape[0]
        H, G, hd = self.heads, self.kv_heads, self.hd
        R = H // G
        kv_pool, ck_pool = pools
        BL = kv_pool.shape[1]
        q = self._rms(self._mm(u, lw["q"]).reshape(T, G, R, hd),
                      lw["q_norm"]).astype(self.dtype)
        k = self._rms(self._mm(u, lw["k"]).reshape(T, G, hd),
                      lw["k_norm"])
        v = self._mm(u, lw["v"]).reshape(T, G, hd)
        entry = jnp.concatenate([k, v], axis=-1).reshape(T, G * 2 * hd)
        scale = hd ** -0.5
        outs = []
        for (_, rows, pos, valid, _), (S, w), q, entry, t in zip(
                windows, shapes, split_rows(q, shapes),
                split_rows(entry.astype(kv_pool.dtype), shapes), wrote):
            valid = jnp.broadcast_to(valid, (S, w))
            ((kv_pool,),) = scatter_positions(
                ((kv_pool,),), rows, t, ((entry,),), valid=valid)
            lens = jnp.sum(valid, axis=1).astype(jnp.int32)
            ck_pool = self._compressed(kv_pool, ck_pool, rows, pos, lens, w)
            scores = select_scores(
                jnp.transpose(q, (0, 2, 1, 3, 4)).reshape(S, G, w * R, hd),
                ck_pool, rows, scale=scale)
            phys, logical, dense = self._choose(
                scores.reshape(S, G, w, R, -1), t, rows, BL)
            K = phys.shape[-1]

            def attend(listed: int, q=q, phys=phys, logical=logical, t=t,
                       kv_pool=kv_pool):
                n = t.size
                return sparse_block_attention(
                    q.reshape(n, G, R, hd), kv_pool,
                    phys.reshape(n, G, K)[..., :listed],
                    logical.reshape(n, G, K)[..., :listed],
                    t.reshape(n), block_size=self.block, scale=scale)

            # lists are as long as a dense row needs (dense_len /
            # block_size); where no real row of the window is dense,
            # ``topk`` entries hold every list (the real entries come
            # first) and the grid is shorter
            narrow = min(self.topk, K)
            out = attend(K) if narrow == K else jax.lax.cond(
                jnp.any(dense & valid), lambda: attend(K),
                lambda: attend(narrow))
            outs.append(out.reshape(S, w, H * hd))
            real = valid.astype(jnp.int32)
            stats = stats + jnp.stack([
                jnp.sum((logical >= 0).sum(axis=(2, 3)) * real),
                jnp.sum((t // self.block + 1) * G * real),
                jnp.sum(dense * real)]).astype(jnp.int32)
        out = lay_rows(outs).astype(jnp.float32) \
            * jax.nn.sigmoid(self._mm(u, lw["g"]))
        return self._mm(out, lw["o"]), (kv_pool, ck_pool), stats

    def _lightning_layer(self, lw, u, state, windows, shapes, cos, sin,
                         slopes):
        T = u.shape[0]
        H, hd = self.l_heads, self.l_hd
        q = self._rms(self._mm(u, lw["q"]).reshape(T, H, hd), lw["q_norm"])
        k = self._rms(self._mm(u, lw["k"]).reshape(T, H, hd), lw["k_norm"])
        v = self._mm(u, lw["v"]).reshape(T, H, hd)
        q = rotate_half(q, cos, sin) * hd ** -0.5
        k = rotate_half(k, cos, sin)
        outs = []
        for (_, _, pos, valid, srows), (S, w), q, k, v in zip(
                windows, shapes, *(split_rows(a.astype(self.dtype), shapes)
                                   for a in (q, k, v))):
            lens = jnp.sum(jnp.broadcast_to(valid, (S, w)),
                           axis=1).astype(jnp.int32)
            o, state = lightning_attention(q, k, v, state, srows, pos, lens,
                                           slopes)
            outs.append(o)
        o = self._rms(lay_rows(outs), lw["o_norm"]).reshape(T, H * hd) \
            * jax.nn.sigmoid(self._mm(u, lw["g"]))
        return self._mm(o, lw["o"]), state

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
        the slots' rows in the state pools (the trash row for a slot that
        is not there). A slot at position 0 starts from no state. The
        decode step (``w`` = 1), a prefill window, or both in one call:
        the matrices run ONCE over all the windows' rows laid end to end,
        the cache writes, the choosing, attention and the state's update
        a window at a time (a window's ``w`` picks the lightning kernel).
        No head."""
        shapes = [win[0].shape for win in windows]
        wrote = window_positions(windows)                    # [S, w] each
        x = params["embed"][lay_rows([win[0] for win in windows])] \
            .astype(jnp.float32) * float(self.config["scale_emb"])
        ang = rope_angles(lay_rows(wrote), self._inv_freq)[:, None]
        cos, sin = jnp.cos(ang), jnp.sin(ang)                # [T, 1, hd/2]
        # ONE jitted function a kind of layer (the layers of a kind have
        # the same shapes, each its own weights and slopes): a program
        # traces and lowers one sparse and one lightning layer, not twelve
        @functools.partial(jax.jit, static_argnames="sparse")
        def layer(lw, x, layer_pools, windows, wrote, rotary, stats, slopes,
                  sparse: bool):
            cos, sin = rotary
            u = self._rms(x, lw["attn_norm"])
            if sparse:
                mixed, layer_pools, stats = self._sparse_layer(
                    lw, u, layer_pools, windows, shapes, wrote, stats)
            else:
                mixed, state = self._lightning_layer(
                    lw, u, layer_pools[0], windows, shapes, cos, sin, slopes)
                layer_pools = (state,)
            h = x + self.res_scale * mixed
            return h + self.res_scale * self._gated(
                self._rms(h, lw["ffn_norm"]), lw["gate"], lw["up"],
                lw["down"]), tuple(layer_pools), stats

        stats = jnp.zeros((len(self.walk_stats),), jnp.int32)
        new_pools = []
        for i, (lw, layer_pools) in enumerate(zip(params["layers"], pools)):
            x, layer_pools, stats = layer(
                lw, x, layer_pools, windows, wrote, (cos, sin), stats,
                self._slopes[i], sparse=self.mixers[i] == SPARSE)
            new_pools.append(layer_pools)
        return split_rows(x, shapes), tuple(new_pools), stats

    def logits(self, params, hidden):
        """The head over the rows the caller picked out of a walk's
        hidden rows: [..., D] → [..., V] float32 logits."""
        return self._mm(self._rms(hidden, params["final_norm"])
                        / self.head_div, params["head"])
