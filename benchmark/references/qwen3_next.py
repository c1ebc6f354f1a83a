"""Plain reference for the Qwen3-Next configuration: seeded weights, the
decoder's forward pass in straightforward ``jax.numpy`` (float32, highest
matmul precision, the gated delta rule as its token-by-token recurrence, no
chunks, no cache, no kernel, no batching, one sequence at a time), and the
comparison that decides ``correct`` for its cells. Imports nothing of the
program and takes nothing the program made: the benchmark makes the weights
here and hands the same numbers to the program (``drivers/chat.py``).

The equations, from the catalog row's ``config`` and the family's
``modeling_qwen3_next.py`` (a CPU test ties this file to
``transformers``' ``Qwen3NextForCausalLM``). ``N(x) = x rsqrt(mean x^2 +
eps) (1 + w)`` is the zero-centred RMSNorm; layer ``i`` is full attention
where ``(i + 1) % full_attention_interval == 0``, else gated DeltaNet:

- block ``h = x + Mixer(N1(x))``, ``y = h + MoE(N2(h))``; final ``N``, an
  untied head; no biases.
- gated DeltaNet (``Hk`` key heads, ``Hv`` value heads of ``dk`` / ``dv``):
  ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``; a causal depthwise
  convolution of ``linear_conv_kernel_dim`` taps and SiLU over the channels
  ``[q | k | v]``; each key head serves ``Hv / Hk`` value heads in turn
  (``repeat_interleave``); ``q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(dk)``,
  ``k^ = k / sqrt(sum k^2 + 1e-6)``, ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; per head a state ``S`` [dk, dv]: ``S <- exp(g_t)
  S``; ``d_t = beta_t (v_t - S^T k^_t)``; ``S <- S + k^_t d_t^T``; ``o_t =
  S^T q^_t``; ``RMSNorm_w(o_t) * silu(z_t)`` a head (plain weight), then
  ``W_out``.
- gated attention (``H`` query heads on ``G`` key heads of ``hd``): a head's
  ``2 hd`` columns of ``W_q`` are its query then its gate; ``q <- Nq(q)``,
  ``k <- Nk(k)`` over ``hd``; rotary positions (``rotate_half`` pairing) on
  the first ``partial_rotary_factor hd`` numbers of each head; causal
  softmax of ``q k^T / sqrt(hd)``; ``(attn * sigmoid(gate)) W_o``.
- expert layer: ``p = softmax(u W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest, renormalised to sum to 1; ``sum_e w_e
  E_e(u)`` over the experts HELD (``experts_held``, a range: absent
  experts' terms are left out), ``E_e`` a gated-SiLU MLP; plus
  ``sigmoid(u w_sg) E_shared(u)``.

Departures, none of the mathematics: (1) the columns of ``W_qkvz`` and
``W_ba`` are contiguous (``[q | k | v | z]``, ``[b | a]``) where the
family's code groups them by key head (``fix_query_key_value_ordering``):
a permutation of a random matrix's columns, which :func:`to_family_order`
applies for the test against ``transformers``; (2) ``num_hidden_layers``
layers, ``experts_held`` and ``vocab_size`` are this chip's share (the
configuration's file); (3) the sequence is computed ``ROW_BLOCK`` rows at
a time, attention ``ATTN_ROWS`` query rows at a time, the held experts one
at a time, so that 10k positions fit beside the weights, which stay in the
serving type and are widened a matrix at a time; (4) the values the
convolution reads are rounded to the serving type (``cache_dtype``) before
it, as the program's tail holds them; (5) weights are random; (6) no
multi-token prediction module (the published config has no key for one).

Weights are a dict: ``embed`` [V, D], ``head`` [D, V], ``final_norm`` and
``layers``, one dict a layer, every matrix applied as ``x @ w``: always
``attn_norm``, ``ffn_norm``, ``router`` [D, E], ``exp_gate`` / ``exp_up``
[n, D, F], ``exp_down`` [n, F, D], ``shared_gate`` / ``shared_up`` [D, Fs],
``shared_down`` [Fs, D], ``shared_router`` [D, 1]; a DeltaNet layer ``qkvz``
[D, 2 Hk dk + 2 Hv dv], ``ba`` [D, 2 Hv], ``conv`` [2 Hk dk + Hv dv, taps],
``A_log``, ``dt_bias`` [Hv], ``o_norm`` [dv], ``o`` [Hv dv, D]; an attention
layer ``q`` [D, H 2 hd], ``k``, ``v`` [D, G hd], ``q_norm``, ``k_norm`` [hd],
``o`` [H hd, D].
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
# no decay (g = 0); the delta term left out (d = beta v); the state zero
# where the prompt's last prefill window starts; the convolution's tail
# zero there; the attention gate left out; the first held expert's term
# left out; the restore left out (state and tail zero at the shared
# prefix's end).
FAULTS = ("no_decay", "no_delta", "window_state_zero", "window_tail_zero",
          "no_attn_gate", "expert_term_missing", "no_restore")
ROW_BLOCK = 2048       # rows of the sequence computed at a time
ATTN_ROWS = 256        # query rows of an attention layer scored at a time
FULL, LINEAR = "full_attention", "linear_attention"


# ------------------------------------------------------------------ shapes
def layer_types(cfg: dict) -> tuple:
    """The mixer of each held layer: ``layer_types`` where the file gives
    it, else the family's default from ``full_attention_interval``."""
    if cfg.get("layer_types"):
        return tuple(cfg["layer_types"])
    every = int(cfg["full_attention_interval"])
    return tuple(FULL if (i + 1) % every == 0 else LINEAR
                 for i in range(int(cfg["num_hidden_layers"])))


def dims(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    held = cfg.get("experts_held") or (0, cfg["num_experts"])
    hd = int(cfg["head_dim"])
    return {
        "D": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]), "types": layer_types(cfg),
        "H": int(cfg["num_attention_heads"]),
        "G": int(cfg["num_key_value_heads"]), "hd": hd,
        "rot": int(hd * float(cfg["partial_rotary_factor"])),
        "theta": float(cfg["rope_theta"]),
        "Hk": int(cfg["linear_num_key_heads"]),
        "Hv": int(cfg["linear_num_value_heads"]),
        "dk": int(cfg["linear_key_head_dim"]),
        "dv": int(cfg["linear_value_head_dim"]),
        "taps": int(cfg["linear_conv_kernel_dim"]),
        "E": int(cfg["num_experts"]), "K": int(cfg["num_experts_per_tok"]),
        "F": int(cfg["moe_intermediate_size"]),
        "Fs": int(cfg["shared_expert_intermediate_size"]),
        "lo": int(held[0]), "hi": int(held[1]),
        "renorm": bool(cfg.get("norm_topk_prob", True)),
        "eps": float(cfg["rms_norm_eps"])}


def layer_shapes(cfg: dict, i: int) -> dict:
    """``{name: (spread, shape)}`` of layer ``i``'s leaves. A spread is the
    standard deviation of a zero-mean matrix, ``("about", c)`` for a vector
    drawn as ``c + 0.1 N(0, 1)``, or ``("log_uniform", lo, hi)`` for
    ``A_log`` (``log A`` uniform between ``log lo`` and ``log hi``).

    Every matrix is drawn at ``1 / fan_in`` so that a unit-RMS input gives
    unit outputs, with these exceptions, each so that a random model has
    something to check (``configs/qwen3-next-80b-a3b.json``,
    ``assumed.weights``): the zero-centred norm weights about 0 (the
    family's init), an attention layer's ``q_norm`` about 1.5 (scores spread
    2.5: a query's softmax rests on a handful of the thousands of positions
    it sees), its ``o`` at 4 and a DeltaNet layer's at 1.2 (each mixer's
    branch about a third of the stream), the router at 2 (a token's ten
    weights 0.03-0.4 after renormalising), ``exp_down`` at 4 (this chip's
    quarter of the routed sum about a third of the stream), ``A_log``
    log-uniform over A in [1/1024, 1] with ``dt_bias`` about 1 (a head's
    state forgets over one to a thousand tokens: neither saturated, as the
    family's init ``A ~ U(0, 16)`` would be for a random ``a``, nor dead)."""
    d = dims(cfg)
    D, F, Fs, n = d["D"], d["F"], d["Fs"], d["hi"] - d["lo"]
    zero = ("about", 0.0)
    out = {"attn_norm": (zero, (D,)), "ffn_norm": (zero, (D,)),
           "router": (2.0 * D ** -0.5, (D, d["E"])),
           "exp_gate": (D ** -0.5, (n, D, F)),
           "exp_up": (D ** -0.5, (n, D, F)),
           "exp_down": (4.0 * F ** -0.5, (n, F, D)),
           "shared_gate": (D ** -0.5, (D, Fs)),
           "shared_up": (D ** -0.5, (D, Fs)),
           "shared_down": (1.5 * Fs ** -0.5, (Fs, D)),
           "shared_router": (D ** -0.5, (D, 1))}
    if d["types"][i] == FULL:
        hq, hk = d["H"] * d["hd"], d["G"] * d["hd"]
        out.update(q=(D ** -0.5, (D, 2 * hq)), k=(D ** -0.5, (D, hk)),
                   v=(D ** -0.5, (D, hk)), o=(4.0 * hq ** -0.5, (hq, D)),
                   q_norm=(("about", 1.5), (d["hd"],)),
                   k_norm=(zero, (d["hd"],)))
    else:
        kd, vd = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
        out.update(qkvz=(D ** -0.5, (D, 2 * kd + 2 * vd)),
                   ba=(D ** -0.5, (D, 2 * d["Hv"])),
                   conv=(d["taps"] ** -0.5, (2 * kd + vd, d["taps"])),
                   A_log=(("log_uniform", 1.0 / 1024, 1.0), (d["Hv"],)),
                   dt_bias=(("about", 1.0), (d["Hv"],)),
                   o_norm=(("about", 1.0), (d["dv"],)),
                   o=(1.2 * vd ** -0.5, (vd, D)))
    return out


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"embed": (1.0, (d["V"], d["D"])),
            "head": (d["D"] ** -0.5, (d["D"], d["V"])),
            "final_norm": (("about", 0.0), (d["D"],))}


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


def to_family_order(cfg: dict, qkvz, ba) -> tuple:
    """This file's contiguous ``[q | k | v | z]`` and ``[b | a]`` columns
    as the family's code lays them, grouped by key head (each group ``[q_g
    | k_g | v of its value heads | z of its value heads]``, ``[b | a]`` of
    its value heads): for loading these weights into ``transformers``."""
    d = dims(cfg)
    Hk, dk, dv, r = d["Hk"], d["dk"], d["dv"], d["Hv"] // d["Hk"]
    kd, vd = Hk * dk, d["Hv"] * dv
    q, k, v, z = np.split(np.asarray(qkvz), [kd, 2 * kd, 2 * kd + vd],
                          axis=1)
    D = q.shape[0]
    grouped = np.concatenate(
        [q.reshape(D, Hk, dk), k.reshape(D, Hk, dk),
         v.reshape(D, Hk, r * dv), z.reshape(D, Hk, r * dv)], axis=2)
    b, a = np.split(np.asarray(ba), 2, axis=1)
    ba_g = np.concatenate([b.reshape(D, Hk, r), a.reshape(D, Hk, r)], axis=2)
    return grouped.reshape(D, -1), ba_g.reshape(D, -1)


# ------------------------------------------------------------ the forward
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


def _norm(x, w, eps):
    """The zero-centred RMSNorm."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _gated(u, gate, up, down, round_fn):
    return _mm(jax.nn.silu(_mm(u, gate, round_fn)) * _mm(u, up, round_fn),
               down, round_fn)


def _rope(x, pos, theta, rot):
    """Rotary positions on the first ``rot`` numbers of ``x`` [rows, heads,
    hd], pairs ``(i, i + rot / 2)``, at whole positions ``pos`` [rows]."""
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("consts", "round_fn"))
def _keys_values(x, start, lw, *, consts, round_fn):
    c = dict(consts)
    u = _norm(x, lw["attn_norm"], c["eps"])
    pos = start + jnp.arange(x.shape[0])
    k = _norm(_mm(u, lw["k"], round_fn).reshape(-1, c["G"], c["hd"]),
              lw["k_norm"], c["eps"])
    return _rope(k, pos, c["theta"], c["rot"]), \
        _mm(u, lw["v"], round_fn).reshape(-1, c["G"], c["hd"])


@functools.partial(jax.jit, static_argnames=(
    "consts", "rows", "round_fn", "gated"))
def _attention_rows(x, start, k, v, lw, *, consts, rows, round_fn, gated):
    """``x + Attention(N1(x))`` for a block of rows from position
    ``start``, against the whole sequence's keys and values."""
    c = dict(consts)
    H, G, hd = c["H"], c["G"], c["hd"]
    R, T = H // G, k.shape[0]
    u = _norm(x, lw["attn_norm"], c["eps"])
    qg = _mm(u, lw["q"], round_fn).reshape(-1, H, 2 * hd)
    pos = start + jnp.arange(x.shape[0])
    q = _rope(_norm(qg[..., :hd], lw["q_norm"], c["eps"]), pos, c["theta"],
              c["rot"]).reshape(-1, G, R, hd)
    gate = qg[..., hd:].reshape(-1, H * hd)

    def some(i):
        qs = lax.dynamic_slice_in_dim(q, i * rows, rows)
        t = start + i * rows + jnp.arange(rows)
        ok = jnp.arange(T)[None, :] <= t[:, None]                # [rows, T]
        s = _ein("agrd,tgd->grat", qs, k, round_fn) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        return _ein("grat,tgd->agrd", p, v, round_fn)

    out = lax.map(some, jnp.arange(x.shape[0] // rows)) \
        .reshape(x.shape[0], H * hd)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    mixed = _mm(out, lw["o"], round_fn)
    share = jnp.sqrt(jnp.mean(jnp.square(mixed)) / jnp.mean(jnp.square(x)))
    return x + mixed, share


@functools.partial(jax.jit, static_argnames=(
    "consts", "round_fn", "round_cache", "decay", "delta"))
def _delta_rows(x, start, state, tail, lw, zero_state_at, zero_tail_at, *,
                consts, round_fn, round_cache, decay, delta):
    """``x + DeltaNet(N1(x))`` for a block of rows from position ``start``,
    given the state [Hv, dk, dv] and the convolution's last ``taps - 1``
    inputs before them; returns both after them too. The state is dropped
    at position ``zero_state_at`` and the tail at ``zero_tail_at`` (-1:
    nowhere), the faults ``window_state_zero``, ``window_tail_zero`` and
    ``no_restore``; ``decay`` False is ``no_decay``, ``delta`` False
    ``no_delta``."""
    c = dict(consts)
    Hk, Hv, dk, dv, taps = c["Hk"], c["Hv"], c["dk"], c["dv"], c["taps"]
    kd, vd, n = Hk * dk, Hv * dv, x.shape[0]
    u = _norm(x, lw["attn_norm"], c["eps"])
    qkvz = _mm(u, lw["qkvz"], round_fn)
    m = round_cache(qkvz[:, :2 * kd + vd])
    z = qkvz[:, 2 * kd + vd:].reshape(n, Hv, dv)
    ba = _mm(u, lw["ba"], round_fn)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(lw["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, Hv:] + lw["dt_bias"].astype(jnp.float32))
    if not decay:
        g = jnp.zeros_like(g)
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
    r = Hv // Hk
    q = jnp.repeat(conv[:, :kd].reshape(n, Hk, dk), r, axis=1)
    k = jnp.repeat(conv[:, kd:2 * kd].reshape(n, Hk, dk), r, axis=1)
    v = conv[:, 2 * kd:].reshape(n, Hv, dv)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)

    def read(s, x):
        """``S^T x`` a head: products of the rounded operands, summed in
        float32 (a sum of products, written out: a matrix product a token
        on the device is a hundred times slower and no more exact)."""
        return jnp.sum(round_fn(s) * x[:, :, None], axis=1)

    q, k = round_fn(q), round_fn(k)

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t, p = row
        s = jnp.where(p == zero_state_at, 0.0, s)
        s = jnp.exp(g_t)[:, None, None] * s
        held = read(s, k_t) if delta else jnp.zeros_like(v_t)
        d_t = b_t[:, None] * (v_t - held)
        s = s + k_t[:, :, None] * round_fn(d_t)[:, None, :]
        return s, read(s, q_t)

    state, o = lax.scan(token, state, (q, k, v, g, beta, pos), unroll=4)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * lax.rsqrt(var + c["eps"]) * lw["o_norm"].astype(jnp.float32) \
        * jax.nn.silu(z)
    mixed = _mm(o.reshape(n, vd), lw["o"], round_fn)
    share = jnp.sqrt(jnp.mean(jnp.square(mixed)) / jnp.mean(jnp.square(x)))
    return x + mixed, state, ext[n:], share


def routing_weights(p, *, top_k: int, renorm: bool):
    """``[T, E]`` combine weights from router probabilities: the ``top_k``
    largest (ties: the lower index), renormalised to sum to 1, zero
    elsewhere."""
    value, chosen = lax.top_k(p, top_k)
    if renorm:
        value = value / jnp.sum(value, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None],
                                chosen].set(value)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "renorm", "round_fn"))
def _route(h, lw, *, eps, top_k, renorm, round_fn):
    u = _norm(h, lw["ffn_norm"], eps)
    p = jax.nn.softmax(_mm(u, lw["router"], round_fn), axis=-1)
    return u, routing_weights(p, top_k=top_k, renorm=renorm)


@functools.partial(jax.jit, static_argnames=("round_fn",))
def _expert_term(u, weight, gate, up, down, *, round_fn):
    return weight[:, None] * _gated(u, gate, up, down, round_fn)


@functools.partial(jax.jit, static_argnames=("round_fn",))
def _shared(h, u, routed, lw, *, round_fn):
    gate = jax.nn.sigmoid(_mm(u, lw["shared_router"], round_fn))
    return h + routed + gate * _gated(u, lw["shared_gate"], lw["shared_up"],
                                      lw["shared_down"], round_fn)


def moe_layer(h, lw, d: dict, *, held=None, with_shared: bool = True,
              round_fn=_identity, fault: str | None = None):
    """``h + MoE(N2(h))`` with the experts ``held`` (a ``(lo, hi)`` range
    of the published ones; the weights' leading axis is that range):
    routed over all of them, the held ones' terms added one expert at a
    time, the rest left out; the shared expert behind its gate."""
    lo, hi = held or (d["lo"], d["hi"])
    u, weights = _route(h, lw, eps=d["eps"], top_k=d["K"],
                        renorm=d["renorm"], round_fn=round_fn)
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
def _logits(x, norm, head, *, eps, round_fn):
    return _mm(_norm(x, norm, eps), head, round_fn)


def forward(weights: dict, cfg: dict, tokens, rows, *, pad_to: int = 0,
            pad_rows_to: int = 0, round_fn=_identity,
            fault: str | None = None, doc_len: int = 0,
            window_start: int = 0, details: dict | None = None):
    """Logits ``[len(rows), vocab]`` (float32) at positions ``rows`` of
    ONE token sequence, each row seeing the tokens up to itself.
    ``pad_to`` pads the sequence and ``pad_rows_to`` the rows, so that one
    compiled shape serves every length; ``round_fn`` is applied to both
    operands of every matrix product; ``fault`` plants one of ``FAULTS``
    (``no_restore`` at position ``doc_len``, the window faults at
    ``window_start``); ``details`` takes each layer's mixer share of the
    stream."""
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
    consts = tuple(sorted({k: d[k] for k in (
        "H", "G", "hd", "rot", "theta", "Hk", "Hv", "dk", "dv", "taps",
        "eps")}.items()))
    zero_state = {"window_state_zero": window_start,
                  "no_restore": doc_len}.get(fault, -1)
    zero_tail = {"window_tail_zero": window_start,
                 "no_restore": doc_len}.get(fault, -1)
    round_cache = _cache_round(cfg.get("cache_dtype", "float32"))
    starts = range(0, T, block)
    xs = [weights["embed"][jnp.asarray(tok[s:s + block])].astype(jnp.float32)
          for s in starts]
    shares = []
    for i, lw in enumerate(weights["layers"]):
        if d["types"][i] == FULL:
            kv = [_keys_values(x, jnp.int32(s), lw, consts=consts,
                               round_fn=round_fn) for x, s in zip(xs, starts)]
            k = jnp.concatenate([a for a, _ in kv])
            v = jnp.concatenate([b for _, b in kv])
            out = [_attention_rows(
                x, jnp.int32(s), k, v, lw, consts=consts, rows=attn_rows,
                round_fn=round_fn, gated=fault != "no_attn_gate")
                for x, s in zip(xs, starts)]
            xs = [y for y, _ in out]
            shares.append(out[-1][1])
        else:
            state = jnp.zeros((d["Hv"], d["dk"], d["dv"]), jnp.float32)
            tail = jnp.zeros((d["taps"] - 1,
                              2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"]),
                             jnp.float32)
            nxt = []
            for x, s in zip(xs, starts):
                y, state, tail, share = _delta_rows(
                    x, jnp.int32(s), state, tail, lw, jnp.int32(zero_state),
                    jnp.int32(zero_tail), consts=consts, round_fn=round_fn,
                    round_cache=round_cache, decay=fault != "no_decay",
                    delta=fault != "no_delta")
                nxt.append(y)
            xs = nxt
            shares.append(share)
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


def sample_margins(weights: dict, cfg: dict, prompt, served, *,
                   doc_len: int = 0, window_start: int = 0,
                   pad: dict | None = None, variant: str | None = None,
                   want=None, details: dict | None = None) -> tuple:
    """Teacher-forced along ONE served stream (``prompt`` then the
    ``served`` tokens): at every generated position, the reference's
    largest logit less the reference's logit of the token put first there.
    Who put it first: the program (``variant`` None: the served token
    itself), or a control in the program's place at the same prompt and
    tokens: ``"e4m3"`` (the reference with both operands of every matrix
    product rounded to scaled e4m3) or one of ``FAULTS``. The pad id's
    logit is left out on every side: greedy serving never puts it first.
    ``want`` takes the reference's logits where a caller kept them.
    Returns ``(margins, the reference's gap between its two best, the
    reference's logits)``."""
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
            "fault": variant, "doc_len": doc_len,
            "window_start": window_start}
        first = jnp.argmax(forward(weights, cfg, tokens, rows, **pad, **kw)
                           .at[:, PAD_ID].set(-jnp.inf), axis=-1)
    best = lax.top_k(want, 2)[0]
    margin = best[:, 0] - jnp.take_along_axis(
        want, first[:, None], axis=-1)[:, 0]
    return (np.asarray(margin, np.float64),
            np.asarray(best[:, 0] - best[:, 1], np.float64), want)


def compare(weights: dict, cfg: dict, samples: list, limits: dict, *,
            pad: dict | None = None, variant: str | None = None,
            details: dict | None = None, kept: dict | None = None) -> list:
    """The cell's comparison over ``samples``: ``(prompt, served tokens,
    the shared prefix's length, where the prompt's last prefill window
    started)`` of finished requests. Returns ``[(name, value, limit),
    ...]`` for ``NUMBERS``; ``details`` takes what is read and not
    compared; ``kept`` keeps the reference's logits by sample from one
    variant to the next."""
    read = []
    for i, (prompt, served, doc_len, window_start) in enumerate(samples):
        margin, gap, want = sample_margins(
            weights, cfg, prompt, served, doc_len=doc_len,
            window_start=window_start, pad=pad, variant=variant,
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
        served = [np.asarray(s) for _, s, _, _ in samples]
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
