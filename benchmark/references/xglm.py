"""Plain reference for the XGLM configurations: seeded weights, the
decoder's forward pass in straightforward ``jax.numpy`` (float32, highest
matmul precision, no cache, no batching, no kernel, a layer at a time),
and the comparison that decides ``correct`` for the generate cell.
Imports nothing of the program and takes nothing the program made: the
benchmark makes the weights here and hands the same numbers to the
program (``drivers/generate.py``).

Follows Lin et al. (arXiv:2112.10668) as ``transformers``'
``XGLMForCausalLM`` computes it: token embedding times ``sqrt(d_model)``
plus fixed sinusoidal positions (``sin`` half then ``cos`` half), pre-LN
blocks (LayerNorm, multi-head attention with biases on q, k, v and out,
residual, LayerNorm, fc1, activation, fc2, residual), a LayerNorm after
the last block, and a head tied to the token embedding with no bias.
The four constants the configuration file lists (``activation_function``,
``layer_norm_eps``, ``position_offset``, ``position_denominator``) are
read from it, so the same code is the published model (tested against
``transformers``) and the model as the program runs it.

Weights are one dict of arrays stacked over the layers (``q_w`` is
``[layers, d_model, d_model]``, applied as ``x @ q_w[i]``), in the type
the configuration serves them in.
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
NUMBERS = ("argmax_margin_max", "argmax_margin_mean")


def weight_shapes(cfg: dict) -> dict:
    """``{name: (spread, shape)}`` of every leaf; a spread of None marks
    a LayerNorm scale (drawn about 1).

    XGLM's ``init_std`` on every matrix would leave a random model with
    nothing to check: attention all but uniform, and the tied head
    putting one token first at every position. So the embedding keeps
    ``init_std`` and the projections are drawn so that, at any width,
    every branch carries signal and greedy streams do not collapse
    (rehearsed at full width on the CPU, PERF.md section 6, PR 28):
    scores spread near 1.8; each feed-forward branch adds a spread near
    12 against the embedding's 0.9, so that the input token's own logit
    through the tied head stays under two spreads of the logits."""
    d, f = int(cfg["d_model"]), int(cfg["ffn_dim"])
    n, v = int(cfg["num_layers"]), int(cfg["vocab_size"])
    qk = math.sqrt(1.8 / d)
    vo = math.sqrt(1.0 / d)
    out = {"embed": (float(cfg["init_std"]), (v, d)),
           "ln_f_scale": (None, (d,)), "ln_f_bias": (0.1, (d,)),
           "fc1_w": (math.sqrt(0.81 / d), (n, d, f)), "fc1_b": (0.1, (n, f)),
           "fc2_w": (math.sqrt(477.0 / f), (n, f, d)), "fc2_b": (0.1, (n, d))}
    for name, spread in (("q", qk), ("k", qk), ("v", vo), ("o", vo)):
        out[f"{name}_w"] = (spread, (n, d, d))
        out[f"{name}_b"] = (0.1, (n, d))
    for name in ("ln1", "ln2"):
        out[f"{name}_scale"] = (None, (n, d))
        out[f"{name}_bias"] = (0.1, (n, d))
    return out


# GELU's output has a mean, the same in every hidden unit and at every
# position; through fc2 it becomes one vector added everywhere, and the
# logits of neighbouring positions then agree to a half. fc2's columns
# are drawn to sum to nought over the hidden units, which maps that mean
# to nought.
CENTERED = ("fc2_w",)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight from the seed in ONE jitted call on the default
    device, in the type the configuration serves them in."""
    shapes = weight_shapes(cfg)
    names = sorted(shapes)
    dtype = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for i, name in enumerate(names):
            spread, shape = shapes[name]
            x = jax.random.normal(keys[i], shape, jnp.float32)
            if name in CENTERED:
                x = x - jnp.mean(x, axis=-2, keepdims=True)
            out[name] = (1.0 + 0.1 * x if spread is None
                         else spread * x).astype(dtype)
        return out

    return draw(seed_key(seed))


def positions(cfg: dict, pos):
    """XGLM's sinusoid at integer positions ``pos`` -> ``[len, d_model]``:
    ``sin`` half then ``cos`` half of ``(pos + offset) * 10000 ** (-i /
    denominator)``."""
    half = int(cfg["d_model"]) // 2
    denom = {"half_dim": half, "half_dim_minus_1": half - 1}[
        cfg["position_denominator"]]
    freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                   * (-math.log(10000.0) / denom))
    ang = (pos + int(cfg["position_offset"])).astype(jnp.float32)[:, None] \
        * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# one object each: they are static arguments of the jitted block
ACTIVATIONS = {"gelu": functools.partial(jax.nn.gelu, approximate=False),
               "gelu_tanh": functools.partial(jax.nn.gelu, approximate=True)}
NOT_CORRECT = 1e30     # what a comparison with nothing to compare reads


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _identity(x):
    return x


def _matmul(a, b, round_fn):
    return jnp.matmul(round_fn(a), round_fn(b), precision=HIGHEST)


# A fault of the path, planted in the reference put in the program's
# place (``compare``'s controls): what the rows past the prompt see.
FAULTS = ("last_chunk_missing",)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "act",
                                             "round_fn", "fault"))
def _block(x, lw, prompt_len, chunk_start, *, heads, eps, act, round_fn,
           fault):
    """One pre-LN block over a whole sequence ``[T, d]``, causal."""
    T, d = x.shape
    hd = d // heads
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    h = _layer_norm(x, f32["ln1_scale"], f32["ln1_bias"], eps)

    def proj(name):
        y = _matmul(h, f32[f"{name}_w"], round_fn) + f32[f"{name}_b"]
        return y.reshape(T, heads, hd).transpose(1, 0, 2)    # [H, T, hd]

    q, k, v = proj("q"), proj("k"), proj("v")
    row = jnp.arange(T)[:, None]
    col = jnp.arange(T)[None, :]
    causal = col <= row

    def attend(k, v, broken=None):
        s = jnp.matmul(round_fn(q), round_fn(k).transpose(0, 2, 1),
                       precision=HIGHEST) * (hd ** -0.5)
        if broken is not None:       # never written: k = v = 0 there
            s = jnp.where(broken[None], 0.0, s)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        if broken is not None:
            p = jnp.where(broken[None], 0.0, p)
        return jnp.matmul(round_fn(p), round_fn(v), precision=HIGHEST)

    o = attend(k, v)
    past = row >= prompt_len         # rows that decode read the cache for
    if fault == "last_chunk_missing":
        # the prompt's last prefill chunk never reached the cache
        hole = past & (col >= chunk_start) & (col < prompt_len)
        o = jnp.where(past[None], attend(k, v, hole), o)
    o = o.transpose(1, 0, 2).reshape(T, d)
    x = x + _matmul(o, f32["o_w"], round_fn) + f32["o_b"]
    h = _layer_norm(x, f32["ln2_scale"], f32["ln2_bias"], eps)
    h = act(_matmul(h, f32["fc1_w"], round_fn) + f32["fc1_b"])
    return x + _matmul(h, f32["fc2_w"], round_fn) + f32["fc2_b"]


@functools.partial(jax.jit, static_argnames=("eps", "pad_id", "round_fn"))
def _head(x, rows, embed, ln_scale, ln_bias, *, eps, pad_id, round_fn):
    """Logits ``[len(rows), vocab]`` of the rows asked for, the pad id
    masked out as the served path masks it."""
    h = _layer_norm(x[rows], ln_scale.astype(jnp.float32),
                    ln_bias.astype(jnp.float32), eps)
    logits = _matmul(h, embed.astype(jnp.float32).T, round_fn)
    return logits.at[:, pad_id].set(-jnp.inf)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, tokens, pe, *, scale):
    return embed[tokens].astype(jnp.float32) * scale + pe


def forward(weights: dict, cfg: dict, tokens, rows, *, pad_to: int = 0,
            pad_rows_to: int = 0, round_fn=_identity,
            fault: str | None = None, prompt_len: int = 0,
            chunk_start: int = 0):
    """Logits ``[len(rows), vocab]`` (float32) at positions ``rows`` of
    ONE token sequence, each row seeing the tokens up to itself.
    ``pad_to`` pads the sequence and ``pad_rows_to`` the rows (with row
    0), so that one compiled shape serves every length; ``round_fn`` is
    applied to both operands of every matrix product (the identity for
    the reference, a lower-precision rounding for a control); ``fault``
    plants one of ``FAULTS`` behind ``prompt_len``."""
    if not bool(cfg["tie_word_embeddings"]):
        raise NotImplementedError("only the tied head is written down")
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    n_tok, n_rows = len(tokens), len(rows)
    T = max(int(pad_to), n_tok)
    R = max(int(pad_rows_to), n_rows)
    tok = np.full(T, int(cfg["pad_token_id"]), np.int32)
    tok[:n_tok] = tokens
    rws = np.zeros(R, np.int32)
    rws[:n_rows] = rows
    d = int(cfg["d_model"])
    scale = math.sqrt(d) if bool(cfg["scale_embedding"]) else 1.0
    x = _embed(weights["embed"], jnp.asarray(tok),
               positions(cfg, jnp.arange(T)), scale=scale)
    per_layer = [k for k in weights
                 if k not in ("embed", "ln_f_scale", "ln_f_bias")]
    for i in range(int(cfg["num_layers"])):
        x = _block(x, {k: weights[k][i] for k in per_layer},
                   jnp.int32(prompt_len), jnp.int32(chunk_start),
                   heads=int(cfg["attention_heads"]),
                   eps=float(cfg["layer_norm_eps"]),
                   act=ACTIVATIONS[cfg["activation_function"]],
                   round_fn=round_fn, fault=fault)
    logits = _head(x, jnp.asarray(rws), weights["embed"],
                   weights["ln_f_scale"], weights["ln_f_bias"],
                   eps=float(cfg["layer_norm_eps"]),
                   pad_id=int(cfg["pad_token_id"]), round_fn=round_fn)
    return logits[:n_rows]


E4M3 = round_to("float8_e4m3fn", scaled=True)


def sample_margins(weights: dict, cfg: dict, prompt, served, *,
                   pad: dict | None = None, variant: str | None = None,
                   chunk: int = 0) -> tuple:
    """Teacher-forced along ONE served stream (``prompt`` then the
    ``served`` tokens): at every generated position, the reference's
    largest logit less the reference's logit of the token put first
    there. Who put it first: the program (``variant`` None: the served
    token itself), or a control in the program's place at the same
    prompts and tokens: ``"e4m3"`` (the reference with both operands of
    every matrix product rounded to scaled e4m3) or one of ``FAULTS``.
    Returns ``(margins, the reference's gap between its two best)``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, n = len(prompt), len(served)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = np.arange(p - 1, p - 1 + n)
    pad = pad or {}
    want = forward(weights, cfg, tokens, rows, **pad)
    if variant is None:
        first = jnp.asarray(served)
    else:
        kw = {"round_fn": E4M3} if variant == "e4m3" else {
            "fault": variant, "prompt_len": p,
            "chunk_start": (p - 1) // chunk * chunk if chunk else p // 2}
        first = jnp.argmax(forward(weights, cfg, tokens, rows, **pad, **kw),
                           axis=-1)
    best = lax.top_k(want, 2)[0]
    margin = best[:, 0] - jnp.take_along_axis(
        want, first[:, None], axis=-1)[:, 0]
    return (np.asarray(margin, np.float64),
            np.asarray(best[:, 0] - best[:, 1], np.float64))


def compare(weights: dict, cfg: dict, samples: list, limits: dict, *,
            pad: dict | None = None, variant: str | None = None,
            chunk: int = 0, details: dict | None = None) -> list:
    """The generate cell's comparison over ``samples`` (``(prompt,
    served tokens)`` pairs of finished requests). Returns ``[(name,
    value, limit), ...]`` for ``NUMBERS``; ``details`` takes what is
    read and not compared."""
    read = [sample_margins(weights, cfg, prompt, served, pad=pad,
                           variant=variant, chunk=chunk)
            for prompt, served in samples]
    margins = [m for m, _ in read]
    flat = np.concatenate(margins) if margins else np.zeros(0)
    if not flat.size or not np.all(np.isfinite(flat)):
        # nothing served, or a served token the reference cannot score
        # (the masked pad id, a NaN): not correct, whatever the limits
        got = {name: NOT_CORRECT for name in NUMBERS}
    else:
        got = {"argmax_margin_max": float(flat.max()),
               "argmax_margin_mean": float(flat.mean())}
    if details is not None and flat.size:
        gaps = np.concatenate([g for _, g in read])
        served = [np.asarray(s) for _, s in samples]
        details.update(
            positions=int(flat.size), sequences=len(margins),
            flipped_share=float(np.mean(flat > 0)),
            margin_p99=float(np.quantile(flat, 0.99)),
            per_sequence_max=[float(m.max()) for m in margins],
            # what the served streams are like: the reference's gap
            # between its two best tokens, and how often a served token
            # repeats the one before it or one served earlier
            top2_gap_median=float(np.median(gaps)),
            top2_gap_p10=float(np.quantile(gaps, 0.1)),
            repeats_previous_share=float(np.mean(np.concatenate(
                [s[1:] == s[:-1] for s in served]))),
            distinct_share=float(np.mean(
                [len(set(s.tolist())) / len(s) for s in served])))
    return [(name, got[name], limits[name]) for name in NUMBERS]
