"""Plain reference for the Kimi-Linear configuration: seeded weights, the
decoder's forward pass in straightforward ``jax.numpy`` (float32, highest
matmul precision, KDA as its token-by-token recurrence, latent attention in
its EXPANDED form, no chunks, no cache, no kernel, no batching, one
sequence at a time), and the comparison that decides ``correct`` for its
cells. Imports nothing of the program and takes nothing the program made:
the benchmark makes the weights here and hands the same numbers to the
program (``drivers/long_reasoning.py``).

The equations, from the catalog row's ``config`` (``described_as``: "KDA
gated delta-rule linear (conv4); MLA NoPE global, 3 KDA : 1 MLA") and what
the family's code does where the config is silent (``configs/kimi-linear-
48b-a3b.json``, ``assumed``). ``RMS`` is RMSNorm with a weight; layer ``i``
is MLA where ``i + 1`` is in ``linear_attn_config["full_attn_layers"]``,
else KDA:

- block ``h = x + Mixer(RMS1(x))``, ``y = h + FFN(RMS2(h))``; the first
  ``first_k_dense_replace`` layers' ``FFN`` a gated SiLU MLP of
  ``intermediate_size``; final ``RMS``, an untied head.
- KDA (``H`` heads of ``d``): ``[q | k | v] = u W_qkv`` (the three
  projections side by side), each through a causal depthwise convolution of
  ``short_conv_kernel_size`` taps and SiLU; ``q^ = q / sqrt(sum q^2 +
  1e-6) / sqrt(d)``, ``k^ = k / sqrt(sum k^2 + 1e-6)``; ``beta = sigmoid(u
  W_b)`` a head; ``g = -exp(A_log_h) softplus(W_fb (W_fa u) + dt_bias)``,
  ``[H, d]``: a decay a KEY CHANNEL; per head a state ``S`` [d, d]: ``S <-
  Diag(exp g_t) S``; ``d_t = beta_t (v_t - S^T k^_t)``; ``S <- S + k^_t
  d_t^T``; ``o_t = S^T q^_t``; ``RMS_w(o_t) * sigmoid(W_gb (W_ga u) +
  b_g)`` a head, then ``W_o``.
- MLA (``H`` heads): ``[q_nope | q_pe] = u W_q`` a head (no query low
  rank); ``[c | k_pe] = u W_kva``, ``c_kv = RMS(c)``, ``[k_nope | v] = c_kv
  W_kvb`` a head; NO rotary (``mla_use_nope``): ``k = [k_nope | k_pe]``
  with ``k_pe`` shared by the heads; causal softmax of ``q . k /
  sqrt(nope + rope)``; ``(attn v) W_o``.
- expert layer: ``p = sigmoid(u W_r)`` over all ``num_experts``; the
  ``num_experts_per_token`` largest ``p + e_score_correction_bias`` (the
  bias chooses, it does not weight; one group); their ``p`` renormalised
  to sum to 1, times ``routed_scaling_factor``; ``sum_e w_e E_e(u)`` over
  the experts HELD (``experts_held``, a range: absent experts' terms are
  left out), ``E_e`` a gated-SiLU MLP; plus the shared expert's gated-SiLU
  MLP, ungated.

Departures, none of the mathematics: (1) ``num_hidden_layers`` layers,
``experts_held`` and ``vocab_size`` are this chip's share (the
configuration's file); (2) the sequence is computed ``ROW_BLOCK`` rows at a
time, attention ``ATTN_ROWS`` query rows at a time, the held experts one at
a time, so that 35k positions fit beside the weights, which stay in the
serving type and are widened a matrix at a time; (3) the values the
convolution reads are rounded to the serving type (``cache_dtype``) before
it, as the program's tail holds them; (4) weights are random; (5) the
three projections ``W_q``, ``W_k``, ``W_v`` are one matrix and their three
convolutions one convolution over the concatenated channels (the same
function).

Weights are a dict: ``embed`` [V, D], ``head`` [D, V], ``final_norm`` and
``layers``, one dict a layer, every matrix applied as ``x @ w``: always
``attn_norm``, ``ffn_norm``; a dense layer ``gate`` / ``up`` [D, I],
``down`` [I, D]; an expert layer ``router`` [D, E], ``router_bias`` [E],
``exp_gate`` / ``exp_up`` [n, D, F], ``exp_down`` [n, F, D],
``shared_gate`` / ``shared_up`` [D, Fs], ``shared_down`` [Fs, D]; a KDA
layer ``qkv`` [D, 3 H d], ``b`` [D, H], ``f_a`` [D, d], ``f_b`` [d, H d],
``g_a`` [D, d], ``g_b`` [d, H d], ``g_bias`` [H d], ``conv`` [3 H d, taps],
``A_log`` [H], ``dt_bias`` [H d], ``o_norm`` [d], ``o`` [H d, D]; an MLA
layer ``q`` [D, H (nope + rope)], ``kv_a`` [D, latent + rope],
``kv_a_norm`` [latent], ``kv_b`` [latent, H (nope + v)], ``o`` [H v, D].
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
# the decay averaged over a head's channels (the scalar rule's function);
# the delta term left out (d = beta v); the output gate left out; the
# convolution's tail zero where the prompt's last prefill window starts;
# the term of the held expert with the most pairs left out (in each
# block of rows); the choosing bias left out.
FAULTS = ("decay_averaged", "no_delta", "no_out_gate", "window_tail_zero",
          "expert_term_missing", "no_choice_bias")
ROW_BLOCK = 2048       # rows of the sequence computed at a time
ATTN_ROWS = 128        # query rows of an MLA layer scored at a time
CALIBRATION_ROWS = 2048  # tokens the routers' choosing biases are set on
CALIBRATION_STEPS = 400  # updates of a choosing bias
KDA, MLA = "kda", "mla"


# ------------------------------------------------------------------ shapes
def mixers(cfg: dict) -> tuple:
    full = {int(i) for i in cfg["linear_attn_config"]["full_attn_layers"]}
    return tuple(MLA if i + 1 in full else KDA
                 for i in range(int(cfg["num_hidden_layers"])))


def dims(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    lin = cfg["linear_attn_config"]
    held = cfg.get("experts_held") or (0, cfg["num_experts"])
    return {
        "D": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]), "types": mixers(cfg),
        "H": int(lin["num_heads"]), "d": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "Hm": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]), "C": int(cfg["kv_lora_rank"]),
        "I": int(cfg["intermediate_size"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "E": int(cfg["num_experts"]), "K": int(cfg["num_experts_per_token"]),
        "F": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["moe_intermediate_size"])
        * int(cfg["num_shared_experts"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "renorm": bool(cfg["moe_renormalize"]),
        "lo": int(held[0]), "hi": int(held[1]),
        "eps": float(cfg["rms_norm_eps"])}


def layer_shapes(cfg: dict, i: int) -> dict:
    """``{name: (spread, shape)}`` of layer ``i``'s leaves. A spread is the
    standard deviation of a zero-mean matrix, ``("about", c)`` for a vector
    drawn as ``c + 0.1 N(0, 1)``, or ``("log_uniform", lo, hi)`` for
    ``A_log`` (``log A`` uniform between ``log lo`` and ``log hi``).

    Every matrix is drawn at ``1 / fan_in`` so that a unit-RMS input gives
    unit outputs, with these exceptions, each so that a random model has
    something to check (``configs/kimi-linear-48b-a3b.json``,
    ``assumed.weights``): norm weights about 1 (the family's init is
    ones); an MLA layer's ``W_q`` at 2.5 (scores spread 2.5: a query's
    softmax rests on a handful of the thousands of positions it sees) and
    its ``W_o`` at 4, a KDA layer's ``W_o`` at 1.5 (each mixer's branch
    about a third of the stream); the router at 2 (sigmoid scores spread
    over (0.05, 0.95)) and its choosing bias drawn about 0, then set so
    that the layer's load is even (:func:`balance_router_bias`);
    ``exp_down`` at 2 (this chip's quarter of the
    routed sum about a third of the stream); ``A_log`` log-uniform over A
    in [1/1024, 1] with ``dt_bias`` about 1 (a head's channels forget over
    one to a thousand tokens; the family's init ``A ~ U(1, 16)`` would make
    ``exp(g)`` under ``e^-1`` on every channel of a random model, a state
    that holds one token)."""
    d = dims(cfg)
    D = d["D"]
    about = lambda c: ("about", c)
    out = {"attn_norm": (about(1.0), (D,)), "ffn_norm": (about(1.0), (D,))}
    if i < d["dense"]:
        out.update(gate=(D ** -0.5, (D, d["I"])), up=(D ** -0.5, (D, d["I"])),
                   down=(d["I"] ** -0.5, (d["I"], D)))
    else:
        n, F, Fs = d["hi"] - d["lo"], d["F"], d["Fs"]
        out.update(router=(2.0 * D ** -0.5, (D, d["E"])),
                   router_bias=(about(0.0), (d["E"],)),
                   exp_gate=(D ** -0.5, (n, D, F)),
                   exp_up=(D ** -0.5, (n, D, F)),
                   exp_down=(2.0 * F ** -0.5, (n, F, D)),
                   shared_gate=(D ** -0.5, (D, Fs)),
                   shared_up=(D ** -0.5, (D, Fs)),
                   shared_down=(Fs ** -0.5, (Fs, D)))
    if d["types"][i] == MLA:
        Hm, qk = d["Hm"], d["nope"] + d["rope"]
        out.update(q=(2.5 * D ** -0.5, (D, Hm * qk)),
                   kv_a=(D ** -0.5, (D, d["C"] + d["rope"])),
                   kv_a_norm=(about(1.0), (d["C"],)),
                   kv_b=(d["C"] ** -0.5, (d["C"], Hm * (d["nope"] + d["vd"]))),
                   o=(4.0 * (Hm * d["vd"]) ** -0.5, (Hm * d["vd"], D)))
    else:
        H, dh = d["H"], d["d"]
        kd = H * dh
        out.update(qkv=(D ** -0.5, (D, 3 * kd)), b=(D ** -0.5, (D, H)),
                   f_a=(D ** -0.5, (D, dh)), f_b=(dh ** -0.5, (dh, kd)),
                   g_a=(D ** -0.5, (D, dh)), g_b=(dh ** -0.5, (dh, kd)),
                   g_bias=(about(0.0), (kd,)),
                   conv=(d["taps"] ** -0.5, (3 * kd, d["taps"])),
                   A_log=(("log_uniform", 1.0 / 1024, 1.0), (H,)),
                   dt_bias=(about(1.0), (kd,)),
                   o_norm=(about(1.0), (dh,)),
                   o=(1.5 * kd ** -0.5, (kd, D)))
    return out


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"embed": (1.0, (d["V"], d["D"])),
            "head": (d["D"] ** -0.5, (d["D"], d["V"])),
            "final_norm": (("about", 1.0), (d["D"],))}


def parameter_count(cfg: dict) -> int:
    shapes = list(top_shapes(cfg).values())
    for i in range(dims(cfg)["L"]):
        shapes += layer_shapes(cfg, i).values()
    return sum(math.prod(shape) for _, shape in shapes)


@functools.partial(jax.jit, static_argnames=("spread", "shape", "dtype"))
def _draw(key, *, spread, shape, dtype):
    if isinstance(spread, tuple) and spread[0] == "log_uniform":
        lo, hi = math.log(spread[1]), math.log(spread[2])
        return jax.random.uniform(key, shape, jnp.float32, lo, hi) \
            .astype(dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if isinstance(spread, tuple):
        return (spread[1] + 0.1 * x).astype(dtype)
    return (spread * x).astype(dtype)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight from the seed, on the default device, in the type the
    configuration serves them in; a leaf at a time, so that the float32
    draw of the largest is all that lives beside them; then the choosing
    biases balanced (:func:`balance_router_bias`)."""
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
    return balance_router_bias(out, cfg, seed)


@functools.partial(jax.jit, static_argnames=("top_k", "steps"))
def _balanced_bias(p, bias, *, top_k: int, steps: int):
    """The choosing bias that spreads ``top_k`` choices of the rows of
    ``p`` [rows, E] evenly over the ``E`` experts: DeepSeek-V3's update
    without an auxiliary loss (an expert above its share of the choices
    has its bias lowered, one below raised), with a step that shrinks
    from 0.05 to 0.001."""
    E = p.shape[1]
    share = p.shape[0] * top_k / E

    def step(i, b):
        _, chosen = lax.top_k(p + b, top_k)
        load = jnp.zeros(E, jnp.float32).at[chosen.reshape(-1)].add(1.0)
        rate = 0.001 + 0.049 * (1.0 - i / steps)
        return b - rate * jnp.sign(load - share)

    return lax.fori_loop(0, steps, step, bias)


def balance_router_bias(weights: dict, cfg: dict, seed: int) -> dict:
    """Every expert layer's ``router_bias`` set as training sets
    ``e_score_correction_bias``: so that the layer's load over ALL its
    experts is even, here on ``CALIBRATION_ROWS`` tokens drawn from the
    seed, carried through the layers before it by this module's forward
    (a random router alone gives a few experts most of the pairs, since
    the rows share a common part). The drawn bias is the start."""
    d = dims(cfg)
    n = CALIBRATION_ROWS
    tok = np.random.default_rng([int(seed), 3]).integers(
        1, d["V"], size=n, dtype=np.int32)
    consts = mixer_consts(d)
    round_cache = _cache_round(cfg.get("cache_dtype", "float32"))
    x = weights["embed"][jnp.asarray(tok)].astype(jnp.float32)
    for i, lw in enumerate(weights["layers"]):
        if d["types"][i] == MLA:
            keys = _latent_keys(x, lw, consts=consts, round_fn=_identity)
            x, _ = _latent_rows(x, jnp.int32(0), *keys, lw, consts=consts,
                                rows=math.gcd(n, ATTN_ROWS),
                                round_fn=_identity)
        else:
            x, _, _, _ = _kda_rows(
                x, jnp.int32(0), jnp.zeros((d["H"], d["d"], d["d"])),
                jnp.zeros((d["taps"] - 1, 3 * d["H"] * d["d"])), lw,
                jnp.int32(-1), consts=consts, round_fn=_identity,
                round_cache=round_cache, fault=None)
        if i < d["dense"]:
            x = _dense(x, lw, eps=d["eps"], round_fn=_identity)
            continue
        u = _rms(x, lw["ffn_norm"], d["eps"])
        p = jax.nn.sigmoid(_mm(u, lw["router"], _identity))
        bias = _balanced_bias(p, lw["router_bias"].astype(jnp.float32),
                              top_k=d["K"], steps=CALIBRATION_STEPS)
        lw["router_bias"] = bias.astype(lw["router_bias"].dtype)
        x = moe_layer(x, lw, d)
    return weights


# ------------------------------------------------------------ the forward
def mixer_consts(d: dict) -> tuple:
    """The mixers' static sizes out of :func:`dims`, hashable for jit."""
    return tuple(sorted({k: d[k] for k in (
        "H", "d", "taps", "Hm", "nope", "rope", "vd", "C", "eps")}.items()))


def _identity(x):
    return x


@functools.lru_cache(maxsize=None)
def _cache_round(dtype_name: str):
    """Rounding to the type the program's convolution tail is kept in
    (one function a type, so that a jitted block is compiled once)."""
    return round_to(dtype_name)


def _mm(a, b, round_fn):
    return jnp.matmul(round_fn(a), round_fn(b.astype(jnp.float32)),
                      precision=HIGHEST)


def _ein(spec, a, b, round_fn):
    return jnp.einsum(spec, round_fn(a), round_fn(b), precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _gated(u, gate, up, down, round_fn):
    return _mm(jax.nn.silu(_mm(u, gate, round_fn)) * _mm(u, up, round_fn),
               down, round_fn)


@functools.partial(jax.jit, static_argnames=("consts", "round_fn"))
def _latent_keys(x, lw, *, consts, round_fn):
    """A block's keys and values, EXPANDED: ``k_nope``, ``v`` [rows, H, .]
    from ``c_kv``, and the shared ``k_pe`` [rows, rope]."""
    c = dict(consts)
    u = _rms(x, lw["attn_norm"], c["eps"])
    kv = _mm(u, lw["kv_a"], round_fn)
    c_kv = _rms(kv[:, :c["C"]], lw["kv_a_norm"], c["eps"])
    kv_b = _mm(c_kv, lw["kv_b"], round_fn).reshape(
        -1, c["Hm"], c["nope"] + c["vd"])
    return kv_b[..., :c["nope"]], kv_b[..., c["nope"]:], kv[:, c["C"]:]


@functools.partial(jax.jit, static_argnames=("consts", "rows", "round_fn"))
def _latent_rows(x, start, k_nope, v, k_pe, lw, *, consts, rows, round_fn):
    """``x + MLA(RMS1(x))`` for a block of rows from position ``start``,
    against the whole sequence's expanded keys and values; no rotary."""
    c = dict(consts)
    Hm, N = c["Hm"], c["nope"]
    T = k_nope.shape[0]
    u = _rms(x, lw["attn_norm"], c["eps"])
    q = _mm(u, lw["q"], round_fn).reshape(-1, Hm, N + c["rope"])
    scale = (N + c["rope"]) ** -0.5

    def some(i):
        qs = lax.dynamic_slice_in_dim(q, i * rows, rows)
        t = start + i * rows + jnp.arange(rows)
        ok = jnp.arange(T)[None, :] <= t[:, None]              # [rows, T]
        s = (_ein("ahn,thn->hat", qs[..., :N], k_nope, round_fn)
             + _ein("ahr,tr->hat", qs[..., N:], k_pe, round_fn)) * scale
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return _ein("hat,thv->ahv", p, v, round_fn)

    out = lax.map(some, jnp.arange(x.shape[0] // rows)) \
        .reshape(x.shape[0], Hm * c["vd"])
    mixed = _mm(out, lw["o"], round_fn)
    share = jnp.sqrt(jnp.mean(jnp.square(mixed)) / jnp.mean(jnp.square(x)))
    return x + mixed, share


@functools.partial(jax.jit, static_argnames=(
    "consts", "round_fn", "round_cache", "fault"))
def _kda_rows(x, start, state, tail, lw, zero_tail_at, *, consts, round_fn,
              round_cache, fault):
    """``x + KDA(RMS1(x))`` for a block of rows from position ``start``,
    given the state [H, d, d] and the convolution's last ``taps - 1``
    inputs before them; returns both after them too. The tail is dropped
    at position ``zero_tail_at`` (-1: nowhere), the fault
    ``window_tail_zero``; ``fault`` plants the others of the mixer."""
    c = dict(consts)
    H, dh, taps = c["H"], c["d"], c["taps"]
    kd, n = H * dh, x.shape[0]
    u = _rms(x, lw["attn_norm"], c["eps"])
    m = round_cache(_mm(u, lw["qkv"], round_fn))
    beta = jax.nn.sigmoid(_mm(u, lw["b"], round_fn))            # [n, H]
    f = _mm(_mm(u, lw["f_a"], round_fn), lw["f_b"], round_fn)
    g = -jnp.exp(lw["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f.reshape(n, H, dh) + lw["dt_bias"].astype(jnp.float32)
        .reshape(H, dh))                                        # [n, H, d]
    if fault == "decay_averaged":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    gate = _mm(_mm(u, lw["g_a"], round_fn), lw["g_b"], round_fn) \
        + lw["g_bias"].astype(jnp.float32)
    # the causal depthwise convolution: output t reads inputs t - taps + 1
    # .. t; an input before ``zero_tail_at`` is not seen from it on
    ext = jnp.concatenate([tail, m])                       # [taps - 1 + n]
    pos = start + jnp.arange(n)
    wc = lw["conv"].astype(jnp.float32)
    conv = jnp.zeros_like(m)
    for j in range(taps):
        src = pos - (taps - 1) + j
        seen = ~((pos >= zero_tail_at) & (src < zero_tail_at))
        conv = conv + jnp.where(seen[:, None], ext[j:j + n], 0.0) * wc[:, j]
    conv = jax.nn.silu(conv)
    q = conv[:, :kd].reshape(n, H, dh)
    k = conv[:, kd:2 * kd].reshape(n, H, dh)
    v = conv[:, 2 * kd:].reshape(n, H, dh)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dh ** -0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)

    def read(s, x):
        """``S^T x`` a head: products of the rounded operands, summed in
        float32 (a sum of products, written out: a matrix product a token
        on the device is a hundred times slower and no more exact)."""
        return jnp.sum(round_fn(s) * x[:, :, None], axis=1)

    q, k = round_fn(q), round_fn(k)

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[:, :, None] * s
        held = read(s, k_t) if fault != "no_delta" else jnp.zeros_like(v_t)
        d_t = b_t[:, None] * (v_t - held)
        s = s + k_t[:, :, None] * round_fn(d_t)[:, None, :]
        return s, read(s, q_t)

    state, o = lax.scan(token, state, (q, k, v, g, beta), unroll=4)
    o = _rms(o, lw["o_norm"], c["eps"]).reshape(n, kd)
    if fault != "no_out_gate":
        o = o * jax.nn.sigmoid(gate)
    mixed = _mm(o, lw["o"], round_fn)
    share = jnp.sqrt(jnp.mean(jnp.square(mixed)) / jnp.mean(jnp.square(x)))
    return x + mixed, state, ext[n:], share


def routing_weights(p, bias, *, top_k: int, renorm: bool, scale: float):
    """``[T, E]`` combine weights from sigmoid scores ``p``: the ``top_k``
    largest ``p + bias`` (ties: the lower index; ``bias`` None: by ``p``
    alone), their ``p`` renormalised to sum to 1 and times ``scale``, zero
    elsewhere."""
    _, chosen = lax.top_k(p if bias is None else p + bias, top_k)
    value = jnp.take_along_axis(p, chosen, axis=-1)
    if renorm:
        value = value / (jnp.sum(value, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None],
                                chosen].set(value * scale)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "renorm", "scale", "round_fn", "biased"))
def _route(h, lw, *, eps, top_k, renorm, scale, round_fn, biased):
    u = _rms(h, lw["ffn_norm"], eps)
    p = jax.nn.sigmoid(_mm(u, lw["router"], round_fn))
    bias = lw["router_bias"].astype(jnp.float32) if biased else None
    return u, routing_weights(p, bias, top_k=top_k, renorm=renorm,
                              scale=scale)


@functools.partial(jax.jit, static_argnames=("round_fn",))
def _expert_term(u, weight, gate, up, down, *, round_fn):
    return weight[:, None] * _gated(u, gate, up, down, round_fn)


@functools.partial(jax.jit, static_argnames=("round_fn",))
def _shared(h, u, routed, lw, *, round_fn):
    return h + routed + _gated(u, lw["shared_gate"], lw["shared_up"],
                               lw["shared_down"], round_fn)


@functools.partial(jax.jit, static_argnames=("eps", "round_fn"))
def _dense(h, lw, *, eps, round_fn):
    u = _rms(h, lw["ffn_norm"], eps)
    return h + _gated(u, lw["gate"], lw["up"], lw["down"], round_fn)


def moe_layer(h, lw, d: dict, *, held=None, with_shared: bool = True,
              round_fn=_identity, fault: str | None = None):
    """``h + MoE(RMS2(h))`` with the experts ``held`` (a ``(lo, hi)``
    range of the published ones; the weights' leading axis is that range):
    routed over all of them, the held ones' terms added one expert at a
    time, the rest left out; the shared expert."""
    lo, hi = held or (d["lo"], d["hi"])
    u, weights = _route(h, lw, eps=d["eps"], top_k=d["K"],
                        renorm=d["renorm"], scale=d["scale"],
                        round_fn=round_fn, biased=fault != "no_choice_bias")
    if fault == "expert_term_missing":
        # the held expert with the most pairs among these rows
        missing = lo + jnp.argmax(jnp.sum(weights[:, lo:hi] > 0, axis=0))
    routed = jnp.zeros_like(h)
    for e in range(lo, hi):
        weight = weights[:, e]
        if fault == "expert_term_missing":
            weight = jnp.where(e == missing, 0.0, weight)
        routed = routed + _expert_term(
            u, weight, lw["exp_gate"][e - lo], lw["exp_up"][e - lo],
            lw["exp_down"][e - lo], round_fn=round_fn)
    if not with_shared:
        return h + routed
    return _shared(h, u, routed, lw, round_fn=round_fn)


@functools.partial(jax.jit, static_argnames=("eps", "round_fn"))
def _logits(x, norm, head, *, eps, round_fn):
    return _mm(_rms(x, norm, eps), head, round_fn)


def forward(weights: dict, cfg: dict, tokens, rows, *, pad_to: int = 0,
            pad_rows_to: int = 0, round_fn=_identity,
            fault: str | None = None, window_start: int = 0,
            details: dict | None = None):
    """Logits ``[len(rows), vocab]`` (float32) at positions ``rows`` of
    ONE token sequence, each row seeing the tokens up to itself. Blocks of
    rows past the last row asked for are not computed. ``pad_to`` pads the
    sequence's keys and ``pad_rows_to`` the rows, so that one compiled
    shape serves every length; ``round_fn`` is applied to both operands of
    every matrix product; ``fault`` plants one of ``FAULTS`` (the window
    fault at ``window_start``); ``details`` takes each layer's mixer share
    of the stream."""
    d = dims(cfg)
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    n_rows = len(rows)
    T = max(int(pad_to), len(tokens))
    block = min(ROW_BLOCK, -(-T // 8) * 8)
    T = -(-T // block) * block
    attn_rows = math.gcd(block, ATTN_ROWS)
    tok = np.zeros(T, np.int32)
    tok[:len(tokens)] = tokens
    rws = np.zeros(max(int(pad_rows_to), n_rows), np.int32)
    rws[:n_rows] = rows
    consts = mixer_consts(d)
    zero_tail = window_start if fault == "window_tail_zero" else -1
    kda_fault = fault if fault in ("decay_averaged", "no_delta",
                                   "no_out_gate") else None
    round_cache = _cache_round(cfg.get("cache_dtype", "float32"))
    starts = range(0, int(rows.max()) + 1 if n_rows else 1, block)
    xs = [weights["embed"][jnp.asarray(tok[s:s + block])].astype(jnp.float32)
          for s in starts]
    shares = []
    for i, lw in enumerate(weights["layers"]):
        if d["types"][i] == MLA:
            kv = [_latent_keys(x, lw, consts=consts, round_fn=round_fn)
                  for x in xs]
            keys = [jnp.concatenate([a[j] for a in kv]) for j in range(3)]
            # the keys of the blocks not computed are never attended
            keys = [jnp.pad(a, ((0, T - a.shape[0]),) + ((0, 0),)
                            * (a.ndim - 1)) for a in keys]
            out = [_latent_rows(x, jnp.int32(s), *keys, lw, consts=consts,
                                rows=attn_rows, round_fn=round_fn)
                   for x, s in zip(xs, starts)]
            xs = [y for y, _ in out]
            shares.append(out[-1][1])
        else:
            state = jnp.zeros((d["H"], d["d"], d["d"]), jnp.float32)
            tail = jnp.zeros((d["taps"] - 1, 3 * d["H"] * d["d"]),
                             jnp.float32)
            nxt = []
            for x, s in zip(xs, starts):
                y, state, tail, share = _kda_rows(
                    x, jnp.int32(s), state, tail, lw, jnp.int32(zero_tail),
                    consts=consts, round_fn=round_fn,
                    round_cache=round_cache, fault=kda_fault)
                nxt.append(y)
            xs = nxt
            shares.append(share)
        if i < d["dense"]:
            xs = [_dense(x, lw, eps=d["eps"], round_fn=round_fn) for x in xs]
        else:
            xs = [moe_layer(x, lw, d, round_fn=round_fn, fault=fault)
                  for x in xs]
    if details is not None:
        details["mixer_share_of_stream"] = [round(float(s), 4)
                                            for s in shares]
    picked = jnp.concatenate(xs)[jnp.asarray(rws)]
    logits = _logits(picked, weights["final_norm"], weights["head"],
                     eps=d["eps"], round_fn=round_fn)
    return logits[:n_rows]


# ------------------------------------------------------------- comparison
E4M3 = round_to("float8_e4m3fn", scaled=True)


def compared_rows(p: int, n: int, most: int) -> np.ndarray:
    """Which of ``n`` served tokens after a prompt of ``p`` are scored:
    every one from the first (prefill's), at most ``most`` of them.
    Returns their positions' rows, ``p - 1 + i``."""
    return p - 1 + np.arange(min(n, most))


def sample_margins(weights: dict, cfg: dict, prompt, served, *,
                   window_start: int = 0, pad: dict | None = None,
                   most: int = 256, variant: str | None = None, want=None,
                   details: dict | None = None) -> tuple:
    """Teacher-forced along ONE served stream (``prompt`` then the
    ``served`` tokens): at the scored positions (:func:`compared_rows`),
    the reference's largest logit less the reference's logit of the token
    put first there. Who put it first: the program (``variant`` None: the
    served token itself), or a control in the program's place at the same
    prompt and tokens: ``"e4m3"`` (the reference with both operands of
    every matrix product rounded to scaled e4m3) or one of ``FAULTS``. The
    pad id's logit is left out on every side: greedy serving never puts it
    first. ``want`` takes the reference's logits where a caller kept them.
    Returns ``(margins, the reference's gap between its two best, the
    reference's logits)``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, n = len(prompt), len(served)
    tokens = np.concatenate([prompt, served[:-1]])
    rows = compared_rows(p, n, most)
    pad = dict(pad or {})
    pad.setdefault("pad_rows_to", most)
    if want is None:
        want = forward(weights, cfg, tokens, rows, details=details,
                       **pad).at[:, PAD_ID].set(-jnp.inf)
    if variant is None:
        first = jnp.asarray(served[rows - (p - 1)])
    else:
        kw = {"round_fn": E4M3} if variant == "e4m3" else {
            "fault": variant, "window_start": window_start}
        first = jnp.argmax(forward(weights, cfg, tokens, rows, **pad, **kw)
                           .at[:, PAD_ID].set(-jnp.inf), axis=-1)
    best = lax.top_k(want, 2)[0]
    margin = best[:, 0] - jnp.take_along_axis(
        want, first[:, None], axis=-1)[:, 0]
    return (np.asarray(margin, np.float64),
            np.asarray(best[:, 0] - best[:, 1], np.float64), want)


def compare(weights: dict, cfg: dict, samples: list, limits: dict, *,
            pad: dict | None = None, most: int = 256,
            variant: str | None = None, details: dict | None = None,
            kept: dict | None = None) -> list:
    """The cell's comparison over ``samples``: ``(prompt, served tokens,
    where the prompt's last prefill window started)``. Returns ``[(name,
    value, limit), ...]`` for ``NUMBERS``; ``details`` takes what is read
    and not compared; ``kept`` keeps the reference's logits by sample from
    one variant to the next."""
    read = []
    for i, (prompt, served, window_start) in enumerate(samples):
        margin, gap, want = sample_margins(
            weights, cfg, prompt, served, window_start=window_start,
            pad=pad, most=most, variant=variant,
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
            contexts=[len(p) + len(s) for p, s, _ in samples],
            margin_max=float(flat.max()),
            margin_p99=float(np.quantile(flat, 0.99)),
            per_sequence_mean=[float(m.mean()) for m in margins],
            per_sequence_max=[float(m.max()) for m in margins],
            top2_gap_median=float(np.median(gaps)),
            top2_gap_p10=float(np.quantile(gaps, 0.1)),
            distinct_share=float(np.mean(
                [len(set(s.tolist())) / len(s) for s in served])))
    return [(name, got[name], limits[name]) for name in NUMBERS]
