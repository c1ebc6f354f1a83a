"""Plain reference for the DeepSeek-V2 configuration: seeded weights, the
decoder's forward pass in straightforward ``jax.numpy`` (float32, highest
matmul precision, no cache, no kernel, no batching, one sequence at a
time), and the comparison that decides ``correct`` for its cells.
Imports nothing of the program and takes nothing the program made: the
benchmark makes the weights here and hands the same numbers to the program
(``drivers/doc_qa.py``).

Follows DeepSeek-AI (arXiv:2405.04434) as the family's
``modeling_deepseek.py`` computes it, the PUBLISHED form of every equation:

- block: ``h = x + Attn(RMS(x))``, ``y = h + FFN(RMS(h))``; a final RMSNorm,
  then an untied head;
- multi-head latent attention, NOT absorbed: ``c_q = RMS(W_qa u)``,
  ``[q_nope, q_rope] = W_qb c_q`` per head, ``[c, k_rope] = W_kva u``,
  ``c_kv = RMS(c)``, ``[k_nope, v] = W_kvb c_kv`` per head; ``q_rope`` and
  the one shared ``k_rope`` rotated (the 64 numbers taken as 32 interleaved
  pairs); score ``(q_nope.k_nope + q_rope.k_rope) * s``;
- YaRN: inverse frequencies blended between ``theta^(-2i/64)`` and that
  over ``factor`` by the linear ramp between the dimensions at which
  ``original_max_position_embeddings`` positions make ``beta_fast`` and
  ``beta_slow`` rotations; ``m(t) = 0.1 t ln(factor) + 1``; the table is
  scaled by ``m(mscale) / m(mscale_all_dim)``; ``s = (nope + rope)^-0.5 *
  m(mscale_all_dim)^2``;
- expert layer: ``g = softmax(W_g u)`` in float32, a group's score its
  largest ``g``, the ``topk_group`` best groups kept, then the
  ``num_experts_per_tok`` best experts among them, weights
  ``routed_scaling_factor * g_e`` (not renormalised), plus the shared
  experts' gated-SiLU MLP; dropless.

Departures, each forced by the cut (``configs/deepseek-v2.json``) or by the
device's memory, none of the mathematics: (1) ``experts_held``: the layer
routes over all the experts and adds the terms of the held ones only (a
loop over them with a mask), as the chip's share of the deployment does;
(2) the embedding and the head hold ``vocab_size`` rows, the slice; (3) the
sequence is computed in blocks (heads in groups, query rows in blocks, one
expert at a time) so that it fits beside the weights, which stay in the
serving type and are widened a matrix at a time; (4) weights are random.

Weights are a dict: ``embed``, ``head``, ``final_norm`` and ``layers``, one
dict a layer, every matrix applied as ``x @ w``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references.resnet50 import round_to, seed_key  # noqa: F401

HIGHEST = lax.Precision.HIGHEST
# What is compared: the mean of the margins and the share of positions
# where the token put first is not the reference's first. Not the largest
# margin: with sparse experts a rounding that swaps a token's sixth and
# seventh expert moves its logits as far as a fault does, so the largest
# of a thousand margins reads the same for bfloat16 as for e4m3 (PERF.md
# section 6); it is printed among the details.
NUMBERS = ("argmax_margin_mean", "argmax_flipped_share")
NOT_CORRECT = 1e30     # what a comparison with nothing to compare reads
# Faults of the path, planted in the reference put in the program's place.
FAULTS = ("expert_term_missing", "no_group_limit", "last_chunk_missing")
HEAD_GROUP = 16        # heads computed at a time
ROW_BLOCK = 512        # query rows computed at a time


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    lo, hi = (int(e) for e in cfg.get(
        "experts_held", (0, cfg["n_routed_experts"])))
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "vh": int(cfg["v_head_dim"]),
        "kvr": int(cfg["kv_lora_rank"]), "qr": int(cfg["q_lora_rank"]),
        "F": int(cfg["intermediate_size"]),
        "Fe": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["n_routed_experts"]), "lo": lo, "hi": hi,
        "G": int(cfg["n_group"]), "Gk": int(cfg["topk_group"]),
        "K": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["n_shared_experts"]),
        "L": int(cfg["num_hidden_layers"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "V": int(cfg["vocab_size"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"])}


def layer_shapes(cfg: dict, i: int) -> dict:
    """``{name: (spread, shape)}`` of layer ``i``'s leaves; a spread of
    None marks an RMSNorm scale (drawn about 1).

    Every matrix is drawn at ``1 / fan_in`` so that a unit-RMS input gives
    unit outputs, with three exceptions, each so that a random model has
    something to check (rehearsed on the CPU at full width, PERF.md §6):
    ``q_b`` at 2 so that scores spread near 2.2 (attention that picks
    positions, not an average of ten thousand); ``o`` at 3, so that what
    attention reads is a third of the stream and a missing stretch of
    the cache shows; the router at 4 / fan_in, logits spread 2, so that a
    token's six weights are 0.4 to 2.5 and the gaps between neighbours
    are wide against bfloat16's noise."""
    d = dims(cfg)
    D, H = d["D"], d["H"]
    out = {"attn_norm": (None, (D,)), "ffn_norm": (None, (D,)),
           "q_a": (D ** -0.5, (D, d["qr"])), "q_a_norm": (None, (d["qr"],)),
           "q_b": (math.sqrt(2.0 / d["qr"]),
                   (d["qr"], H * (d["nope"] + d["rope"]))),
           "kv_a": (D ** -0.5, (D, d["kvr"] + d["rope"])),
           "kv_a_norm": (None, (d["kvr"],)),
           "kv_b": (d["kvr"] ** -0.5,
                    (d["kvr"], H * (d["nope"] + d["vh"]))),
           "o": (3.0 * (H * d["vh"]) ** -0.5, (H * d["vh"], D))}
    if i < d["dense"]:
        F = d["F"]
        out.update(gate=(D ** -0.5, (D, F)), up=(D ** -0.5, (D, F)),
                   down=(F ** -0.5, (F, D)))
        return out
    Fe, Fs, n = d["Fe"], d["shared"] * d["Fe"], d["hi"] - d["lo"]
    out.update(
        router=(2.0 * D ** -0.5, (D, d["E"])),
        shared_gate=(D ** -0.5, (D, Fs)), shared_up=(D ** -0.5, (D, Fs)),
        shared_down=(Fs ** -0.5, (Fs, D)),
        exp_gate=(D ** -0.5, (n, D, Fe)), exp_up=(D ** -0.5, (n, D, Fe)),
        exp_down=(Fe ** -0.5, (n, Fe, D)))
    return out


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"embed": (1.0, (d["V"], d["D"])),
            "head": (d["D"] ** -0.5, (d["D"], d["V"])),
            "final_norm": (None, (d["D"],))}


def parameter_count(cfg: dict) -> int:
    shapes = list(top_shapes(cfg).values())
    for i in range(dims(cfg)["L"]):
        shapes += layer_shapes(cfg, i).values()
    return sum(math.prod(shape) for _, shape in shapes)


@functools.partial(jax.jit, static_argnames=("spread", "shape", "dtype"))
def _draw(key, *, spread, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return (1.0 + 0.1 * x if spread is None else spread * x).astype(dtype)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight from the seed, on the default device, in the type the
    configuration serves them in; a leaf at a time, so that the float32
    draw of the largest (one layer's experts) is all that lives beside
    them."""
    dtype = jnp.dtype(cfg["param_dtype"])
    base = seed_key(seed)

    def leaves(shapes: dict, key) -> dict:
        return {name: _draw(jax.random.fold_in(key, j), spread=spread,
                            shape=tuple(shape), dtype=dtype)
                for j, (name, (spread, shape)) in enumerate(
                    sorted(shapes.items()))}

    out = leaves(top_shapes(cfg), jax.random.fold_in(base, 0))
    out["layers"] = [
        leaves(layer_shapes(cfg, i), jax.random.fold_in(base, i + 1))
        for i in range(dims(cfg)["L"])]
    return out


# -------------------------------------------------------------------- YaRN
def _mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The ``rope / 2`` inverse frequencies as ``modeling_deepseek.py``'s
    ``DeepseekV2YarnRotaryEmbedding`` blends them."""
    dim, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exps
    inter = 1.0 / (float(rs["factor"]) * base ** exps)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(
            int(rs["original_max_position_embeddings"])
            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1: the unscaled frequency stays
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    head = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return head ** -0.5 * _mscale(float(rs["factor"]),
                                  float(rs["mscale_all_dim"])) ** 2


def rope_tables(cfg: dict, positions):
    """``(cos, sin)``, each ``[len, rope / 2]``, at whole ``positions``."""
    rs = cfg["rope_scaling"]
    scale = _mscale(float(rs["factor"]), float(rs["mscale"])) / _mscale(
        float(rs["factor"]), float(rs["mscale_all_dim"]))
    ang = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(cfg))[None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate(x, cos, sin):
    """DeepSeek's pairing: the last axis is ``rope / 2`` interleaved pairs
    ``(x[2i], x[2i+1])``, pair ``i`` turned by its angle; the result has
    the first members in its first half and the second in its second (the
    layout ``modeling_deepseek.py`` leaves them in: only dot products of
    two rotated vectors are ever taken)."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ------------------------------------------------------------ the forward
def _identity(x):
    return x


def _mm(a, b, round_fn):
    return jnp.matmul(round_fn(a), round_fn(b.astype(jnp.float32)),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _gated(u, gate, up, down, round_fn):
    return _mm(jax.nn.silu(_mm(u, gate, round_fn)) * _mm(u, up, round_fn),
               down, round_fn)


@functools.partial(jax.jit, static_argnames=("eps", "kvr", "round_fn"))
def _latents(x, lw, cos, sin, *, eps, kvr, round_fn):
    """What attention needs of every position before the heads part:
    the query's latent, the normalised key-value latent and the rotated
    shared key."""
    u = _rms(x, lw["attn_norm"], eps)
    c_q = _rms(_mm(u, lw["q_a"], round_fn), lw["q_a_norm"], eps)
    kv = _mm(u, lw["kv_a"], round_fn)
    c_kv = _rms(kv[:, :kvr], lw["kv_a_norm"], eps)
    return c_q, c_kv, rotate(kv[:, kvr:], cos, sin)


@functools.partial(jax.jit, static_argnames=(
    "nope", "rope", "vh", "scale", "round_fn", "fault", "block"))
def _head_group(c_q, c_kv, k_rope, cos, sin, q_b, kv_b, o, prompt_len,
                chunk_start, *, nope, rope, vh, scale, round_fn, fault,
                block):
    """A group of heads over the whole sequence, causal, in the published
    (not absorbed) form, query rows a block at a time; returns the group's
    part of ``W_o concat(out)``, ``[T, D]``."""
    T = c_q.shape[0]
    hg = q_b.shape[1] // (nope + rope)
    q = _mm(c_q, q_b, round_fn).reshape(T, hg, nope + rope)
    kv = _mm(c_kv, kv_b, round_fn).reshape(T, hg, nope + vh)
    q_rope = rotate(q[..., nope:], cos[:, None], sin[:, None])
    qh = jnp.concatenate([q[..., :nope], q_rope], -1).transpose(1, 0, 2)
    kh = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (T, hg, rope))],
        -1).transpose(1, 0, 2)                              # [hg, T, 192]
    vh_ = kv[..., nope:].transpose(1, 0, 2)                 # [hg, T, vh]
    col = jnp.arange(T)[None, :]

    def rows(start):
        row = start + jnp.arange(block)[:, None]
        qb = lax.dynamic_slice_in_dim(qh, start, block, axis=1)
        s = jnp.matmul(round_fn(qb), round_fn(kh).transpose(0, 2, 1),
                       precision=HIGHEST) * scale           # [hg, block, T]
        hole = None
        if fault == "last_chunk_missing":
            # rows that decode read the cache for see nothing where the
            # prompt's last prefill chunk should be: c_kv = k_rope = 0
            hole = ((row >= prompt_len) & (col >= chunk_start)
                    & (col < prompt_len))[None]
            s = jnp.where(hole, 0.0, s)
        p = jax.nn.softmax(jnp.where((col <= row)[None], s, -jnp.inf), -1)
        if hole is not None:
            p = jnp.where(hole, 0.0, p)
        return jnp.matmul(round_fn(p), round_fn(vh_), precision=HIGHEST)

    out = lax.map(rows, jnp.arange(0, T, block))     # [T/block, hg, block, vh]
    out = out.transpose(0, 2, 1, 3).reshape(T, hg * vh)
    return _mm(out, o, round_fn)


def routing_weights(g, *, groups: int, keep_groups: int, top_k: int,
                    scale: float, group_limit: bool = True):
    """``[T, E]``: ``scale * g`` at each token's chosen experts, nought
    elsewhere. ``group_limited_greedy``: a group's score is the largest
    ``g`` of its experts, the ``keep_groups`` best groups stay, the
    ``top_k`` best experts among them are chosen (ties: the lower index);
    the weights are not renormalised."""
    T, E = g.shape
    if group_limit:
        best = jnp.max(g.reshape(T, groups, E // groups), axis=-1)
        _, kept = lax.top_k(best, keep_groups)
        mask = jnp.zeros((T, groups), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        g_in = jnp.where(jnp.repeat(mask, E // groups, axis=1), g, 0.0)
    else:
        g_in = g
    _, chosen = lax.top_k(g_in, top_k)
    picked = jnp.zeros((T, E), bool).at[
        jnp.arange(T)[:, None], chosen].set(True)
    return jnp.where(picked, scale * g, 0.0)


@functools.partial(jax.jit, static_argnames=(
    "eps", "groups", "keep_groups", "top_k", "scale", "round_fn", "fault"))
def _route(h, lw, *, eps, groups, keep_groups, top_k, scale, round_fn,
           fault):
    u = _rms(h, lw["ffn_norm"], eps)
    g = jax.nn.softmax(_mm(u, lw["router"], round_fn), axis=-1)
    return u, routing_weights(
        g, groups=groups, keep_groups=keep_groups, top_k=top_k, scale=scale,
        group_limit=fault != "no_group_limit")


@functools.partial(jax.jit, static_argnames=("round_fn",))
def _expert_term(u, weight, gate, up, down, *, round_fn):
    return weight[:, None] * _gated(u, gate, up, down, round_fn)


@functools.partial(jax.jit, static_argnames=("eps", "round_fn"))
def _dense_ffn(h, lw, *, eps, round_fn):
    u = _rms(h, lw["ffn_norm"], eps)
    return h + _gated(u, lw["gate"], lw["up"], lw["down"], round_fn)


@functools.partial(jax.jit, static_argnames=("round_fn",))
def _shared(h, u, routed, lw, *, round_fn):
    return h + routed + _gated(u, lw["shared_gate"], lw["shared_up"],
                               lw["shared_down"], round_fn)


def moe_layer(h, lw, d: dict, *, held=None, with_shared: bool = True,
              round_fn=_identity, fault: str | None = None):
    """``h + FFN(RMS(h))`` of an expert layer with the experts ``held``
    (a ``(lo, hi)`` range of the published ones; the weights' leading axis
    is that range): routed over all of them, the held ones' terms added
    one expert at a time, the rest left out."""
    lo, hi = held or (d["lo"], d["hi"])
    u, weights = _route(
        h, lw, eps=d["eps"], groups=d["G"], keep_groups=d["Gk"],
        top_k=d["K"], scale=d["route_scale"], round_fn=round_fn, fault=fault)
    routed = jnp.zeros_like(h)
    for e in range(lo, hi):
        if fault == "expert_term_missing" and e == lo:
            continue
        routed = routed + _expert_term(
            u, weights[:, e], lw["exp_gate"][e - lo], lw["exp_up"][e - lo],
            lw["exp_down"][e - lo], round_fn=round_fn)
    if not with_shared:
        return h + routed
    return _shared(h, u, routed, lw, round_fn=round_fn)


@functools.partial(jax.jit, static_argnames=("eps", "round_fn"))
def _logits(x, rows, norm, head, *, eps, round_fn):
    return _mm(_rms(x[rows], norm, eps), head, round_fn)


def forward(weights: dict, cfg: dict, tokens, rows, *, pad_to: int = 0,
            pad_rows_to: int = 0, round_fn=_identity,
            fault: str | None = None, prompt_len: int = 0,
            chunk_start: int = 0):
    """Logits ``[len(rows), vocab]`` (float32) at positions ``rows`` of
    ONE token sequence, each row seeing the tokens up to itself.
    ``pad_to`` pads the sequence (to a multiple of ``ROW_BLOCK``) and
    ``pad_rows_to`` the rows, so that one compiled shape serves every
    length; ``round_fn`` is applied to both operands of every matrix
    product; ``fault`` plants one of ``FAULTS``."""
    d = dims(cfg)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    n_rows = len(rows)
    T = max(int(pad_to), len(tokens))
    block = min(ROW_BLOCK, T)
    T = -(-T // block) * block
    tok = np.zeros(T, np.int32)
    tok[:len(tokens)] = tokens
    rws = np.zeros(max(int(pad_rows_to), n_rows), np.int32)
    rws[:n_rows] = rows
    cos, sin = rope_tables(cfg, np.arange(T))
    x = weights["embed"][jnp.asarray(tok)].astype(jnp.float32)
    hg = min(HEAD_GROUP, d["H"])
    per_q, per_kv = d["nope"] + d["rope"], d["nope"] + d["vh"]
    for i, lw in enumerate(weights["layers"]):
        c_q, c_kv, k_rope = _latents(x, lw, cos, sin, eps=d["eps"],
                                     kvr=d["kvr"], round_fn=round_fn)
        h = x
        for g in range(0, d["H"], hg):
            h = h + _head_group(
                c_q, c_kv, k_rope, cos, sin,
                lw["q_b"][:, g * per_q:(g + hg) * per_q],
                lw["kv_b"][:, g * per_kv:(g + hg) * per_kv],
                lw["o"][g * d["vh"]:(g + hg) * d["vh"]],
                jnp.int32(prompt_len), jnp.int32(chunk_start),
                nope=d["nope"], rope=d["rope"], vh=d["vh"],
                scale=softmax_scale(cfg), round_fn=round_fn,
                fault=fault if fault == "last_chunk_missing" else None,
                block=block)
        if i < d["dense"]:
            x = _dense_ffn(h, lw, eps=d["eps"], round_fn=round_fn)
        else:
            x = moe_layer(h, lw, d, round_fn=round_fn, fault=fault)
    logits = _logits(x, jnp.asarray(rws), weights["final_norm"],
                     weights["head"], eps=d["eps"], round_fn=round_fn)
    return logits[:n_rows]


# ------------------------------------------------------------- comparison
E4M3 = round_to("float8_e4m3fn", scaled=True)


def sample_margins(weights: dict, cfg: dict, prompt, served, *,
                   pad: dict | None = None, variant: str | None = None,
                   chunk_start: int = 0, want=None) -> tuple:
    """Teacher-forced along ONE served stream (``prompt`` then the
    ``served`` tokens): at every generated position, the reference's
    largest logit less the reference's logit of the token put first there.
    Who put it first: the program (``variant`` None: the served token
    itself), or a control in the program's place at the same prompt and
    tokens: ``"e4m3"`` (the reference with both operands of every matrix
    product rounded to scaled e4m3) or one of ``FAULTS``. ``want`` takes
    the reference's logits where a caller kept them. Returns ``(margins,
    the reference's gap between its two best, the reference's logits)``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, n = len(prompt), len(served)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = np.arange(p - 1, p - 1 + n)
    pad = pad or {}
    if want is None:
        want = forward(weights, cfg, tokens, rows, **pad)
    if variant is None:
        first = jnp.asarray(served)
    else:
        kw = {"round_fn": E4M3} if variant == "e4m3" else {
            "fault": variant, "prompt_len": p, "chunk_start": chunk_start}
        first = jnp.argmax(forward(weights, cfg, tokens, rows, **pad, **kw),
                           axis=-1)
    best = lax.top_k(want, 2)[0]
    margin = best[:, 0] - jnp.take_along_axis(
        want, first[:, None], axis=-1)[:, 0]
    return (np.asarray(margin, np.float64),
            np.asarray(best[:, 0] - best[:, 1], np.float64), want)


def compare(weights: dict, cfg: dict, samples: list, limits: dict, *,
            pad: dict | None = None, variant: str | None = None,
            details: dict | None = None, kept: dict | None = None) -> list:
    """The cell's comparison over ``samples``: ``(prompt, served tokens,
    where the prompt's last prefill chunk began)`` of finished requests.
    Returns ``[(name, value, limit), ...]`` for ``NUMBERS``; ``details``
    takes what is read and not compared; ``kept`` keeps the reference's
    logits by sample from one variant to the next."""
    read = []
    for i, (prompt, served, chunk_start) in enumerate(samples):
        margin, gap, want = sample_margins(
            weights, cfg, prompt, served, pad=pad, variant=variant,
            chunk_start=chunk_start,
            want=None if kept is None else kept.get(i))
        if kept is not None:
            kept[i] = want
        read.append((margin, gap))
    margins = [m for m, _ in read]
    flat = np.concatenate(margins) if margins else np.zeros(0)
    if not flat.size or not np.all(np.isfinite(flat)):
        got = {name: NOT_CORRECT for name in NUMBERS}
    else:
        got = {"argmax_margin_mean": float(flat.mean()),
               "argmax_flipped_share": float(np.mean(flat > 0))}
    if details is not None and flat.size:
        gaps = np.concatenate([g for _, g in read])
        served = [np.asarray(s) for _, s, _ in samples]
        details.update(
            positions=int(flat.size), sequences=len(margins),
            margin_max=float(flat.max()),
            margin_p99=float(np.quantile(flat, 0.99)),
            per_sequence_max=[float(m.max()) for m in margins],
            top2_gap_median=float(np.median(gaps)),
            top2_gap_p10=float(np.quantile(gaps, 0.1)),
            repeats_previous_share=float(np.mean(np.concatenate(
                [s[1:] == s[:-1] for s in served]))),
            distinct_share=float(np.mean(
                [len(set(s.tolist())) / len(s) for s in served])))
    return [(name, got[name], limits[name]) for name in NUMBERS]
