"""Plain reference for the MiniCPM-SALA configuration: seeded weights, the
decoder's forward pass in straightforward ``jax.numpy`` (float32, highest
matmul precision, no cache, no kernel, no batching, one sequence at a
time), and the comparison that decides ``correct`` for its cells. Imports
nothing of the program and takes nothing the program made: the benchmark
makes the weights here and hands the same numbers to the program
(``drivers/long_doc_qa.py``).

The equations, from the catalog row's ``config`` and, for what it leaves to
the code, ``configs/minicpm-sala.json``'s ``assumed`` (every constant named
there with its convention). ``r = scale_depth / sqrt(published depth)``:

- ``x = scale_emb * E[token]``; block ``h = x + r Mixer(RMS(x))``, ``y = h +
  r MLP(RMS(h))``, gated SiLU MLP; logits ``W_head (RMS(x) / (hidden_size /
  dim_model_base))``; no biases, untied head.
- ``lightning-attn``: ``q, k, v = W u`` as ``[32, 128]``; per-head RMSNorm on
  q and k; rotary positions on q and k (pairs ``(i, i + 64)``); ``q /
  sqrt(128)``; per head ``S_t = lam S_{t-1} + k_t^T v_t``, ``o_t = q_t
  S_t``, ``lam = exp(-slope)``; per-head RMSNorm on ``o``; times
  ``sigmoid(W_g u)``; ``W_o``. Computed as the EXACT masked quadratic form
  of the recurrence in terms of ``SCAN_ROWS`` rows: inside a term ``((q
  k^T) * D) v`` with ``D[t, s] = lam^(t-s)``, ``s <= t``; between terms the
  state, carried along the sequence.
- ``minicpm4``: 32 query heads over 2 key-value heads of 128, per-head
  RMSNorm on q and k, no positions, scale ``1/sqrt(128)``, causal. A query
  whose context (itself included) is at most ``dense_len`` attends all of
  it. Beyond: compressed keys ``ck_j = mean(k[16 j : 16 j + 32])``; per
  query head ``p = softmax_j(q . ck_j / sqrt(128))`` over the ``j`` with
  ``16 j + 31 <= t``; summed over the 16 heads of a group; block ``m`` (64
  tokens) scores the largest ``p_j`` of the compressed keys that overlap it
  (``j = 4 m - 1 .. 4 m + 3``); block 0 and every block that holds one of
  the last 2,048 tokens score above all; the 64 best blocks of those up to
  the query's own (ties: the earlier block); softmax over the tokens ``<=
  t`` of the chosen blocks. ``o`` times ``sigmoid(W_g u)``, then ``W_o``.

Departures, each forced by the cut or by the device's memory, none of the
mathematics: (1) ``num_hidden_layers`` layers with the ``mixer_types``
given (the cut's slice of the published stack), the residual scale and the
decay slopes taken at the PUBLISHED depth and layer index; (2) the
sequence is computed ``ROW_BLOCK`` rows at a time (a sparse layer's keys
and values first, for all rows), attention ``ATTN_ROWS`` query rows at a
time, so that 65k positions fit beside the weights, which stay in the
serving type and are widened a matrix at a time; (3) weights are random.

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
NUMBERS = ("argmax_margin_mean", "argmax_flipped_share")
NOT_CORRECT = 1e30     # what a comparison with nothing to compare reads
PAD_ID = 0             # never served: masked out of every row of logits
# Faults of the path, planted in the reference put in the program's place:
# the chosen blocks cut to the first block and the local window (no top-k);
# the state not restored (zero at the document's end); the decay left out.
FAULTS = ("no_topk", "no_restore", "no_decay")
ROW_BLOCK = 2048       # rows of the sequence computed at a time
ATTN_ROWS = 64         # query rows of a sparse layer scored at a time
SCAN_ROWS = 512        # rows of one term of the lightning recurrence
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_FORCED = 1e30


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    sp = cfg["sparse_config"]
    published = cfg.get("published", {})
    return {
        "D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
        "H": int(cfg["num_attention_heads"]),
        "G": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "LH": int(cfg["lightning_nh"]), "lhd": int(cfg["lightning_head_dim"]),
        "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
        "mixers": tuple(cfg["mixer_types"]),
        "L_pub": int(published.get("num_hidden_layers",
                                   cfg["num_hidden_layers"])),
        "offset": int(cfg.get("layer_offset", 0)),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "scale_emb": float(cfg["scale_emb"]),
        "scale_depth": float(cfg["scale_depth"]),
        "head_div": int(cfg["hidden_size"]) / float(cfg["dim_model_base"]),
        "ks": int(sp["kernel_size"]), "st": int(sp["kernel_stride"]),
        "bs": int(sp["block_size"]), "topk": int(sp["topk"]),
        "init": int(sp["init_blocks"]), "window": int(sp["window_size"]),
        "dense_len": int(sp["dense_len"])}


def slopes(cfg: dict, layer: int) -> np.ndarray:
    """Held layer ``layer``'s decay slopes: ``2^(-8 (h + 1) / heads)``
    times ``1 - l / (L - 1) + 1e-5`` at the published index and depth."""
    d = dims(cfg)
    base = 2.0 ** (-8.0 * (np.arange(d["LH"]) + 1.0) / d["LH"])
    return (base * (1.0 - (d["offset"] + layer) / max(d["L_pub"] - 1, 1)
                    + 1e-5)).astype(np.float32)


def layer_shapes(cfg: dict, i: int) -> dict:
    """``{name: (spread, shape)}`` of layer ``i``'s leaves. A spread is the
    standard deviation of a zero-mean matrix, or ``("about", c)`` for an
    RMSNorm scale drawn as ``c + 0.1 N(0, 1)``.

    Every matrix is drawn at ``1 / fan_in`` so that a unit-RMS input gives
    unit outputs, with these exceptions, each so that a random model has
    something to check (``configs/minicpm-sala.json``, ``assumed.weights``):
    a sparse layer's ``q_norm`` about 2.5, so that scores spread 2.5 and a
    query's softmax over its 4,096 chosen tokens rests on a handful of them
    (a block that goes missing or is swapped then moves the output); its
    ``o`` at 7, a lightning layer's at 2.5, so that each mixer's branch is
    about a third of the stream it is added to."""
    d = dims(cfg)
    D, F = d["D"], d["F"]
    one = ("about", 1.0)
    out = {"attn_norm": (one, (D,)), "ffn_norm": (one, (D,)),
           "gate": (D ** -0.5, (D, F)), "up": (D ** -0.5, (D, F)),
           "down": (F ** -0.5, (F, D))}
    if d["mixers"][i] == SPARSE:
        hq, hk = d["H"] * d["hd"], d["G"] * d["hd"]
        out.update(q=(D ** -0.5, (D, hq)), k=(D ** -0.5, (D, hk)),
                   v=(D ** -0.5, (D, hk)), g=(D ** -0.5, (D, hq)),
                   o=(7.0 * hq ** -0.5, (hq, D)),
                   q_norm=(("about", 2.5), (d["hd"],)),
                   k_norm=(one, (d["hd"],)))
    else:
        hq = d["LH"] * d["lhd"]
        out.update(q=(D ** -0.5, (D, hq)), k=(D ** -0.5, (D, hq)),
                   v=(D ** -0.5, (D, hq)), g=(D ** -0.5, (D, hq)),
                   o=(2.5 * hq ** -0.5, (hq, D)),
                   q_norm=(one, (d["lhd"],)), k_norm=(one, (d["lhd"],)),
                   o_norm=(one, (d["lhd"],)))
    return out


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"embed": (1.0 / d["scale_emb"], (d["V"], d["D"])),
            "head": (d["head_div"] * d["D"] ** -0.5, (d["D"], d["V"])),
            "final_norm": (("about", 1.0), (d["D"],))}


def parameter_count(cfg: dict) -> int:
    shapes = list(top_shapes(cfg).values())
    for i in range(dims(cfg)["L"]):
        shapes += layer_shapes(cfg, i).values()
    return sum(math.prod(shape) for _, shape in shapes)


@functools.partial(jax.jit, static_argnames=("spread", "shape", "dtype"))
def _draw(key, *, spread, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if isinstance(spread, tuple):
        return (spread[1] + 0.1 * x).astype(dtype)
    return (spread * x).astype(dtype)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight from the seed, on the default device, in the type the
    configuration serves them in; a leaf at a time, so that the float32
    draw of the largest is all that lives beside them."""
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


# ------------------------------------------------------------ the forward
def _identity(x):
    return x


def _mm(a, b, round_fn):
    return jnp.matmul(round_fn(a), round_fn(b.astype(jnp.float32)),
                      precision=HIGHEST)


def _ein(spec, a, b, round_fn):
    return jnp.einsum(spec, round_fn(a), round_fn(b), precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _finish(x, u, attended, lw, *, r, eps, round_fn):
    """The rest of a block after its mixer's attention: the output gate,
    ``W_o``, the residual, the MLP and its residual. Also the branch's
    share: its RMS over the RMS of the stream it is added to."""
    mixed = r * _mm(attended * jax.nn.sigmoid(_mm(u, lw["g"], round_fn)),
                    lw["o"], round_fn)
    h = x + mixed
    v = _rms(h, lw["ffn_norm"], eps)
    y = h + r * _mm(jax.nn.silu(_mm(v, lw["gate"], round_fn))
                    * _mm(v, lw["up"], round_fn), lw["down"], round_fn)
    share = jnp.sqrt(jnp.mean(jnp.square(mixed)) / jnp.mean(jnp.square(x)))
    return y, share


@functools.partial(jax.jit, static_argnames=("G", "hd", "eps", "round_fn"))
def _keys_values(x, lw, *, G, hd, eps, round_fn):
    u = _rms(x, lw["attn_norm"], eps)
    k = _rms(_mm(u, lw["k"], round_fn).reshape(-1, G, hd), lw["k_norm"], eps)
    return k, _mm(u, lw["v"], round_fn).reshape(-1, G, hd)


def _allowed(q, t, ck, T, c: dict, round_fn, fault):
    """``[G, rows, T]``: the tokens each of ``rows`` queries at positions
    ``t`` may attend, a key head: all up to ``t`` at a context of at most
    ``dense_len``, else those of the chosen blocks."""
    G, st, bs = ck.shape[1], c["st"], c["bs"]
    per, nblk = bs // st, T // bs
    causal = jnp.arange(T)[None, :] <= t[:, None]                # [rows, T]
    sc = _ein("agrd,jgd->graj", q, ck, round_fn) * c["hd"] ** -0.5
    j = jnp.arange(ck.shape[0])
    whole = st * j[None, :] + c["ks"] - 1 <= t[:, None]          # [rows, Nc]
    p = jax.nn.softmax(jnp.where(whole[None, None], sc, -jnp.inf), axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p).sum(axis=1)             # [G, rows, Nc]
    # column j + 1 holds key j, which overlaps blocks (j + 1) // per - 1
    # (where j + 1 is a multiple of per) and (j + 1) // per
    cols = jnp.pad(p, ((0, 0), (0, 0), (1, per * nblk - ck.shape[0])))
    inner = cols[..., :per * nblk].reshape(G, -1, nblk, per).max(axis=-1)
    blk = jnp.maximum(inner, cols[..., per::per])
    m = jnp.arange(nblk)
    forced = (m[None] < c["init"]) | (
        m[None] * bs + bs - 1 >= t[:, None] - (c["window"] - 1))
    upto = m[None] <= (t // bs)[:, None]                         # [rows, nblk]
    if fault == "no_topk":
        chosen = jnp.broadcast_to((forced & upto)[None], blk.shape)
    else:
        cand = jnp.where(upto[None], jnp.where(forced[None], _FORCED, blk),
                         -1.0)
        value, index = lax.top_k(cand, min(c["topk"], nblk))
        chosen = jnp.zeros(cand.shape, bool)
        chosen = jnp.put_along_axis(chosen, index, value >= 0, axis=-1,
                                    inplace=False)
    dense = (t + 1 <= c["dense_len"])[None, :, None]
    return causal[None] & (dense | jnp.repeat(chosen, bs, axis=-1))


@functools.partial(jax.jit, static_argnames=(
    "consts", "rows", "round_fn", "fault"))
def _sparse_rows(x, start, k, v, ck, lw, *, consts, rows, round_fn, fault):
    """``ROW_BLOCK`` rows of a sparse layer from position ``start``,
    against the whole sequence's keys and values."""
    c = dict(consts)
    G, hd, T = c["G"], c["hd"], k.shape[0]
    R = c["H"] // G
    u = _rms(x, lw["attn_norm"], c["eps"])
    q = _rms(_mm(u, lw["q"], round_fn).reshape(-1, G, R, hd), lw["q_norm"],
             c["eps"])

    def some(i):
        qs = lax.dynamic_slice_in_dim(q, i * rows, rows)
        t = start + i * rows + jnp.arange(rows)
        ok = _allowed(qs, t, ck, T, c, round_fn, fault)          # [G, rows, T]
        s = _ein("agrd,tgd->grat", qs, k, round_fn) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -jnp.inf), axis=-1)
        return _ein("grat,tgd->agrd", p, v, round_fn)

    out = lax.map(some, jnp.arange(x.shape[0] // rows))
    return _finish(x, u, out.reshape(x.shape[0], G * R * hd), lw,
                   r=c["r"], eps=c["eps"], round_fn=round_fn)


def _rope(x, pos, theta):
    """Rotary positions on ``x`` [rows, heads, hd], pairs ``(i, i + hd /
    2)``, at whole positions ``pos`` [rows]."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, 2 * half, 2, dtype=jnp.float32)
                          / (2 * half))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "consts", "rows", "round_fn", "zero_at"))
def _lightning_rows(x, start, state, slope, lw, *, consts, rows, round_fn,
                    zero_at):
    """``ROW_BLOCK`` rows of a lightning layer from position ``start``,
    given the state before them; returns the state after them too. With
    ``zero_at`` the state is dropped where the term at that position
    begins (the fault ``no_restore``)."""
    c = dict(consts)
    H, hd = c["LH"], c["lhd"]
    u = _rms(x, lw["attn_norm"], c["eps"])
    pos = start + jnp.arange(x.shape[0])
    q = _rope(_rms(_mm(u, lw["q"], round_fn).reshape(-1, H, hd),
                   lw["q_norm"], c["eps"]), pos, c["theta"]) * hd ** -0.5
    k = _rope(_rms(_mm(u, lw["k"], round_fn).reshape(-1, H, hd),
                   lw["k_norm"], c["eps"]), pos, c["theta"])
    v = _mm(u, lw["v"], round_fn).reshape(-1, H, hd)
    i = jnp.arange(rows)
    lag = (i[:, None] - i[None, :]).astype(jnp.float32)
    decay = jnp.where(lag >= 0,
                      jnp.exp(-slope[:, None, None] * lag[None]), 0.0)
    into = jnp.exp(-slope[None, :] * (i[:, None] + 1.0))         # [rows, H]
    left = jnp.exp(-slope[None, :] * (rows - 1.0 - i[:, None]))
    through = jnp.exp(-slope * rows)                             # [H]

    def term(s, n):
        if zero_at is not None:
            s = jnp.where(start + n * rows == zero_at, 0.0, s)
        qc, kc, vc = (lax.dynamic_slice_in_dim(a, n * rows, rows)
                      for a in (q, k, v))
        a = _ein("thd,shd->hts", qc, kc, round_fn) * decay
        o = _ein("hts,shd->thd", a, vc, round_fn) \
            + _ein("thd,hde->the", qc, s, round_fn) * into[..., None]
        s = through[:, None, None] * s \
            + _ein("shd,she->hde", kc * left[..., None], vc, round_fn)
        return s, o

    state, out = lax.scan(term, state, jnp.arange(x.shape[0] // rows))
    out = _rms(out.reshape(-1, H, hd), lw["o_norm"], c["eps"])
    y, share = _finish(x, u, out.reshape(x.shape[0], H * hd), lw, r=c["r"],
                       eps=c["eps"], round_fn=round_fn)
    return y, state, share


@functools.partial(jax.jit, static_argnames=("eps", "div", "round_fn"))
def _logits(x, norm, head, *, eps, div, round_fn):
    return _mm(_rms(x, norm, eps) / div, head, round_fn)


def forward(weights: dict, cfg: dict, tokens, rows, *, pad_to: int = 0,
            pad_rows_to: int = 0, round_fn=_identity,
            fault: str | None = None, doc_len: int = 0,
            details: dict | None = None):
    """Logits ``[len(rows), vocab]`` (float32) at positions ``rows`` of
    ONE token sequence, each row seeing the tokens up to itself.
    ``pad_to`` pads the sequence and ``pad_rows_to`` the rows, so that one
    compiled shape serves every length; ``round_fn`` is applied to both
    operands of every matrix product; ``fault`` plants one of ``FAULTS``
    (``no_restore`` at position ``doc_len``); ``details`` takes each
    layer's mixer share of the stream."""
    d = dims(cfg)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    n_rows = len(rows)
    T = -(-max(int(pad_to), len(tokens)) // d["bs"]) * d["bs"]
    block = min(ROW_BLOCK, T)
    T = -(-T // block) * block
    attn_rows = math.gcd(block, ATTN_ROWS)
    scan_rows = math.gcd(block, SCAN_ROWS)
    zero_at = None
    if fault == "no_restore":
        zero_at = int(doc_len)
        scan_rows = math.gcd(scan_rows, zero_at)
    tok = np.zeros(T, np.int32)
    tok[:len(tokens)] = tokens
    rws = np.zeros(max(int(pad_rows_to), n_rows), np.int32)
    rws[:n_rows] = rows
    consts = tuple(sorted({
        **{k: d[k] for k in ("G", "H", "hd", "LH", "lhd", "eps", "theta",
                             "ks", "st", "bs", "topk", "init", "window",
                             "dense_len")},
        "r": d["scale_depth"] / math.sqrt(d["L_pub"])}.items()))
    starts = range(0, T, block)
    xs = [weights["embed"][jnp.asarray(tok[s:s + block])].astype(jnp.float32)
          * d["scale_emb"] for s in starts]
    shares = []
    for i, lw in enumerate(weights["layers"]):
        if d["mixers"][i] == SPARSE:
            kv = [_keys_values(x, lw, G=d["G"], hd=d["hd"], eps=d["eps"],
                               round_fn=round_fn) for x in xs]
            k = jnp.concatenate([a for a, _ in kv])
            v = jnp.concatenate([b for _, b in kv])
            part = k.reshape(T // d["st"], d["st"], d["G"], d["hd"]).mean(1)
            ck = (part[:-1] + part[1:]) / 2        # ks = 2 st tokens a key
            out = [_sparse_rows(
                x, jnp.int32(s), k, v, ck, lw, consts=consts, rows=attn_rows,
                round_fn=round_fn, fault=fault if fault == "no_topk" else
                None) for x, s in zip(xs, starts)]
            xs = [y for y, _ in out]
            shares.append(out[-1][1])
        else:
            slope = jnp.zeros(d["LH"]) if fault == "no_decay" \
                else jnp.asarray(slopes(cfg, i))
            state = jnp.zeros((d["LH"], d["lhd"], d["lhd"]), jnp.float32)
            nxt = []
            for x, s in zip(xs, starts):
                y, state, share = _lightning_rows(
                    x, jnp.int32(s), state, slope, lw, consts=consts,
                    rows=scan_rows, round_fn=round_fn, zero_at=zero_at)
                nxt.append(y)
            xs = nxt
            shares.append(share)
    if details is not None:
        details["mixer_share_of_stream"] = [round(float(s), 4)
                                            for s in shares]
    picked = jnp.concatenate(xs)[jnp.asarray(rws)]
    logits = _logits(picked, weights["final_norm"], weights["head"],
                     eps=d["eps"], div=d["head_div"], round_fn=round_fn)
    return logits[:n_rows]


# ------------------------------------------------------------- comparison
E4M3 = round_to("float8_e4m3fn", scaled=True)


def sample_margins(weights: dict, cfg: dict, prompt, served, *,
                   doc_len: int = 0, pad: dict | None = None,
                   variant: str | None = None, want=None,
                   details: dict | None = None) -> tuple:
    """Teacher-forced along ONE served stream (``prompt`` then the
    ``served`` tokens): at every generated position, the reference's
    largest logit less the reference's logit of the token put first there.
    Who put it first: the program (``variant`` None: the served token
    itself), or a control in the program's place at the same prompt and
    tokens: ``"e4m3"`` (the reference with both operands of every matrix
    product rounded to scaled e4m3) or one of ``FAULTS``. The pad id's
    logit is left out on every side: greedy serving never puts it first.
    ``want`` takes
    the reference's logits where a caller kept them. Returns ``(margins,
    the reference's gap between its two best, the reference's logits)``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, n = len(prompt), len(served)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = np.arange(p - 1, p - 1 + n)
    pad = pad or {}
    if want is None:
        want = forward(weights, cfg, tokens, rows, details=details,
                       **pad).at[:, PAD_ID].set(-jnp.inf)
    if variant is None:
        first = jnp.asarray(served)
    else:
        kw = {"round_fn": E4M3} if variant == "e4m3" else {
            "fault": variant, "doc_len": doc_len}
        first = jnp.argmax(forward(weights, cfg, tokens, rows, **pad, **kw)
                           .at[:, PAD_ID].set(-jnp.inf), axis=-1)
    best = lax.top_k(want, 2)[0]
    margin = best[:, 0] - jnp.take_along_axis(
        want, first[:, None], axis=-1)[:, 0]
    return (np.asarray(margin, np.float64),
            np.asarray(best[:, 0] - best[:, 1], np.float64), want)


def compare(weights: dict, cfg: dict, samples: list, limits: dict, *,
            pads: list | None = None, variant: str | None = None,
            details: dict | None = None, kept: dict | None = None) -> list:
    """The cell's comparison over ``samples``: ``(prompt, served tokens,
    the document's length)`` of finished requests; ``pads[i]`` pads sample
    ``i``'s forward pass. Returns ``[(name, value, limit), ...]`` for
    ``NUMBERS``; ``details`` takes what is read and not compared; ``kept``
    keeps the reference's logits by sample from one variant to the next."""
    read = []
    for i, (prompt, served, doc_len) in enumerate(samples):
        margin, gap, want = sample_margins(
            weights, cfg, prompt, served, doc_len=doc_len,
            pad=pads[i] if pads else None, variant=variant,
            want=None if kept is None else kept.get(i),
            details=details if i == 0 else None)
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
            per_sequence_mean=[float(m.mean()) for m in margins],
            per_sequence_max=[float(m.max()) for m in margins],
            top2_gap_median=float(np.median(gaps)),
            top2_gap_p10=float(np.quantile(gaps, 0.1)),
            distinct_share=float(np.mean(
                [len(set(s.tolist())) / len(s) for s in served])))
    return [(name, got[name], limits[name]) for name in NUMBERS]
