"""Mixture-of-Experts layer with expert parallelism over the ``ep`` axis.

No reference counterpart (SURVEY §2.14: EP absent there). Dense-dispatch
top-1 MoE: every device holds E/n local experts, receives the full token
batch (replicated), computes its experts' contributions for the tokens
routed to them, and a ``psum`` combines — router and combine are einsums
that XLA maps onto the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from ..parallel import collectives as _coll
from ..parallel.compat import shard_map as _shard_map


def init_moe_params(rng, num_experts: int, d_model: int, d_hidden: int):
    k1, k2, k3 = jax.random.split(rng, 3)
    scale = d_model ** -0.5
    return {
        "router": jax.random.normal(k1, (d_model, num_experts)) * scale,
        "w_in": jax.random.normal(
            k2, (num_experts, d_model, d_hidden)) * scale,
        "w_out": jax.random.normal(
            k3, (num_experts, d_hidden, d_model)) * (d_hidden ** -0.5),
    }


def load_balance_loss(logits, expert, valid=None):
    """Switch-Transformer auxiliary loss: ``E · Σ_e f_e · P_e`` where
    ``f_e`` is the fraction of tokens dispatched to expert e and
    ``P_e`` the mean router probability for e. Equals 1.0 at perfect
    uniformity; grows as routing collapses onto few experts. ``f`` is
    non-differentiable (argmax counts); gradients reach the router
    through ``P`` — the standard formulation.

    ``valid`` restricts both means to real tokens: pad positions embed
    identically, all route to one expert, and would otherwise dominate
    ``f`` on padded batches — the router would be trained by padding,
    not data."""
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    f = _expert_fraction(expert, E, valid)
    if valid is None:
        P = probs.mean(axis=0)
    else:
        v = valid.astype(jnp.float32)[:, None]
        P = (probs * v).sum(axis=0) / jnp.maximum(v.sum(), 1.0)
    return E * jnp.sum(f * P)


def _expert_fraction(expert, E: int, valid=None):
    """Fraction of (valid) tokens dispatched to each expert — shared by
    the balance loss and the aux output so their masking rules cannot
    diverge."""
    onehot = jax.nn.one_hot(expert, E)
    if valid is None:
        return onehot.mean(axis=0)
    v = valid.astype(jnp.float32)[:, None]
    return (onehot * v).sum(axis=0) / jnp.maximum(v.sum(), 1.0)


def _expert_positions(expert, E: int, valid=None):
    """Each token's arrival rank within its expert's queue (token
    order = batch order, the Switch first-come-first-served rule).
    ``valid`` excludes tokens (padding) from consuming queue slots —
    without it, a batch's pad positions all route to the same expert
    (identical embeddings) and can crowd real tokens past capacity."""
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)     # [T, E]
    if valid is not None:
        onehot = onehot * valid[:, None].astype(jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - 1
    return jnp.take_along_axis(ranks, expert[:, None], axis=1)[:, 0]


def _capacity(T: int, E: int, capacity_factor: float) -> int:
    """Static per-expert token budget C = ceil(T/E · cf), clamped to T."""
    return max(1, min(T, int(np.ceil(T / E * capacity_factor))))


def _capacity_ffn(x, eid, pos, keep, w_in, w_out, C: int):
    """Sort-free capacity dispatch: kept tokens scatter into per-expert
    [E_local, C, D] buffers (unique slots by construction — ``pos`` is
    the within-expert rank), the experts run as ONE batched matmul pair
    (E_local·C·D·H FLOPs — independent of the global expert count),
    and results gather back to token order. Overflowed/foreign tokens
    contribute zero (their residual path passes through unchanged).
    Scatter/gather are differentiable, so training flows exactly like
    the dense formulation."""
    E_loc, D = w_in.shape[0], x.shape[1]
    slot = jnp.where(keep, eid * C + jnp.minimum(pos, C - 1), 0)
    contrib = jnp.where(keep[:, None], x, 0.0)
    buf = jnp.zeros((E_loc * C, D), x.dtype).at[slot].add(contrib)
    h = jax.nn.gelu(jnp.einsum(
        "ecd,edh->ech", buf.reshape(E_loc, C, D), w_in))
    y = jnp.einsum("ech,ehd->ecd", h, w_out)
    out = y.reshape(E_loc * C, -1)[slot]
    return jnp.where(keep[:, None], out, 0.0)


# ------------------------------------------------------------- serving
# Dropless top-k routing and a grouped product over the experts HELD: what
# a serving decoder's expert layer needs (``dl.latent_moe_decoder``). The
# training encoder below keeps its top-1 argmax over logits.

#: names of the four numbers :func:`dropless_moe` counts, in order
MOE_STATS = ("pairs_held", "pairs_absent", "experts_touched",
             "expert_load_max")


def route_top_k(probs, *, top_k: int, groups: int = 1,
                keep_groups: int = 1):
    """Group-limited greedy top-k over router probabilities ``[T, E]``:
    a group's score is the largest probability of its ``E / groups``
    experts, the ``keep_groups`` best groups stay, and the ``top_k`` best
    experts among them are chosen (ties: the lower index). With one group
    it is plain top-k. Returns ``(experts [T, top_k] int32, their
    probabilities [T, top_k])``, not renormalised."""
    T, E = probs.shape
    masked = probs
    if groups > 1:
        best = jnp.max(probs.reshape(T, groups, E // groups), axis=-1)
        _, kept = jax.lax.top_k(best, keep_groups)
        in_kept = jnp.zeros((T, groups), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        masked = jnp.where(jnp.repeat(in_kept, E // groups, axis=1),
                           probs, 0.0)
    _, experts = jax.lax.top_k(masked, top_k)
    return experts.astype(jnp.int32), jnp.take_along_axis(
        probs, experts, axis=1)


def dropless_moe(x, experts, weights, w_gate, w_up, w_down, *,
                 held: tuple, valid=None):
    """The held experts' part of a gated-SiLU expert layer, dropless.

    ``x`` [T, D] tokens; ``experts``/``weights`` [T, k] each token's
    chosen experts (ids over ALL the layer's experts) and combine
    weights; ``w_gate``/``w_up`` [n, D, F] and ``w_down`` [n, F, D] the
    experts ``held = (lo, hi)``, ``n = hi - lo`` of them. Every
    token-expert pair whose expert is held is computed — no capacity, no
    token left out — and pairs of absent experts are left out: the
    result is this holder's partial sum ``Σ_{e held} weight_e E_e(x)``
    ([T, D] float32), which is what expert parallelism asks of one
    holder before its exchange.

    The pairs are sorted by expert and the three products are grouped
    (``jax.lax.ragged_dot``: one pass over the held experts' weights,
    rows only where an expert has pairs). ``valid`` [T] bool leaves
    padding tokens out of the products and the counts. Also returns the
    counts :data:`MOE_STATS` as int32 [4]."""
    T, k = experts.shape
    lo, hi = int(held[0]), int(held[1])
    n = hi - lo
    flat = experts.reshape(-1)
    real = jnp.ones((T * k,), bool) if valid is None \
        else jnp.repeat(valid, k)
    mine = real & (flat >= lo) & (flat < hi)
    local = jnp.where(mine, flat - lo, n)          # absent pairs sort last
    order = jnp.argsort(local, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[local].add(1)[:n]
    token = jnp.arange(T * k, dtype=jnp.int32) // k
    xs = x[token[order]]                           # [T*k, D], by expert

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
    ys = grouped(h.astype(x.dtype), w_down)        # [T*k, D] float32
    # rows past the held pairs belong to no group: whatever they hold
    scale = jnp.where(mine, weights.reshape(-1), 0.0)[order]
    ys = jnp.where(mine[order][:, None], ys * scale[:, None], 0.0)
    out = ys[jnp.argsort(order)].reshape(T, k, -1).sum(axis=1)
    stats = jnp.stack([
        jnp.sum(mine), jnp.sum(real & ~mine), jnp.sum(sizes > 0),
        jnp.max(sizes)]).astype(jnp.int32)
    return out, stats


def moe_forward(params, x, *, return_aux: bool = False,
                capacity_factor: float | None = None, valid=None):
    """Single-device reference: x [T, D] → [T, D], top-1 routing.

    TRAINABLE end-to-end: experts get gradients through their outputs
    and the router through the chosen-expert probability multiplier
    (the Switch gating trick). ``return_aux=True`` additionally returns
    ``{"balance_loss", "expert_fraction"}`` — add ``balance_loss``
    (scaled ~1e-2) to the task loss to keep routing spread.

    ``capacity_factor=None`` (default) is the DENSE dispatch — every
    token through every expert, masked; exact, O(T·E·D·H), the
    equivalence oracle. A float switches to capacity dispatch:
    per-expert budget C = ceil(T/E · cf), tokens beyond it DROP (zero
    MoE contribution, residual unchanged), compute O(T·cf·D·H) —
    independent of E, the formulation that scales to real expert
    counts. With cf ≥ E the two are identical (no token can
    overflow). ``valid`` [T] bool marks real tokens: in capacity mode
    invalid (pad) tokens neither consume queue slots nor receive
    contributions; the dense path ignores it (pads are harmless there
    — their outputs die at the masked pool)."""
    logits = x @ params["router"]                     # [T, E]
    E = logits.shape[-1]
    expert = jnp.argmax(logits, axis=-1)
    gate = jax.nn.softmax(logits, axis=-1)
    gate_top = jnp.take_along_axis(gate, expert[:, None], axis=1)[:, 0]
    if capacity_factor is None:
        dispatch = jax.nn.one_hot(expert, E)          # [T, E]
        h = jnp.einsum("te,td,edh->teh", dispatch, x, params["w_in"])
        h = jax.nn.gelu(h)
        y = jnp.einsum("teh,ehd->td", h, params["w_out"])
    else:
        C = _capacity(x.shape[0], E, capacity_factor)
        pos = _expert_positions(expert, E, valid)
        keep = pos < C if valid is None else valid & (pos < C)
        y = _capacity_ffn(x, expert, pos, keep,
                          params["w_in"], params["w_out"], C)
    out = y * gate_top[:, None]
    if not return_aux:
        return out
    aux = {"balance_loss": load_balance_loss(logits, expert, valid),
           "expert_fraction": _expert_fraction(expert, E, valid)}
    return out, aux


def make_sharded_moe(mesh, *, axis: str = "ep",
                     return_aux: bool = False,
                     capacity_factor: float | None = None):
    """Expert-parallel forward: experts shard over ``axis``; tokens are
    replicated in, outputs psum-combined. Differentiable like the
    single-device reference (run under ``jit``); with ``return_aux``
    the replicated balance-loss aux rides out alongside.

    ``capacity_factor`` as in :func:`moe_forward`: None = dense-masked
    dispatch (exact; per-device compute O(T·E/n·D·H), scaling with the
    LOCAL expert count), a float = capacity dispatch (per-device
    compute O(T·cf/n·D·H) — independent of E, required at real expert
    widths). Routing/positions derive from the all-gathered logits, so
    every shard agrees on queue ranks and the result equals the
    single-device capacity path exactly."""
    n = int(mesh.shape[axis])

    def local(params, x, valid):
        # params' expert dims are local shards [E/n, ...]; the router
        # column block is this shard's experts
        shard = _coll.axis_index(axis)
        logits_local = x @ params["router"]           # [T, E/n]
        # global top-1 routing needs all logits: gather over the axis
        logits = _coll.allgather(logits_local, axis,
                                 gather_axis=1)       # [T, E]
        E = logits.shape[-1]
        e_per = E // n
        expert = jnp.argmax(logits, axis=-1)          # [T]
        gate = jax.nn.softmax(logits, axis=-1)
        gate_top = jnp.take_along_axis(gate, expert[:, None],
                                       axis=1)[:, 0]
        local_expert = expert - shard * e_per
        mine = (local_expert >= 0) & (local_expert < e_per)
        if capacity_factor is None:
            dispatch = jax.nn.one_hot(
                jnp.where(mine, local_expert, 0), e_per) \
                * mine[:, None]                       # [T, E/n]
            h = jnp.einsum("te,td,edh->teh", dispatch, x,
                           params["w_in"])
            h = jax.nn.gelu(h)
            y = jnp.einsum("teh,ehd->td", h, params["w_out"])
        else:
            C = _capacity(x.shape[0], E, capacity_factor)
            pos = _expert_positions(expert, E, valid)  # global ranks
            keep = mine & valid & (pos < C)
            y = _capacity_ffn(x, jnp.where(mine, local_expert, 0),
                              pos, keep, params["w_in"],
                              params["w_out"], C)
        y = y * gate_top[:, None]
        out = _coll.allreduce(y, axis)
        if not return_aux:
            return out
        # every shard holds the FULL gathered logits, so the aux is
        # computed identically everywhere — replicated by construction
        aux = {"balance_loss": load_balance_loss(logits, expert, valid),
               "expert_fraction": _expert_fraction(expert, E, valid)}
        return out, aux

    spec = {"router": P(None, axis), "w_in": P(axis),
            "w_out": P(axis)}
    out_specs = (P(), {"balance_loss": P(), "expert_fraction": P()}) \
        if return_aux else P()
    mapped = _shard_map(local, mesh=mesh, in_specs=(spec, P(), P()),
                           out_specs=out_specs, check_vma=False)

    def fn(params, x, valid=None):
        if valid is None:
            valid = jnp.ones(x.shape[0], bool)
        return mapped(params, x, valid)

    return fn


def init_moe_blocks(rng, depth: int, d_model: int, num_experts: int,
                    d_hidden: int):
    """Per-block MoE parameter trees for ``make_moe_text_encoder``."""
    keys = jax.random.split(rng, depth)
    return [init_moe_params(k, num_experts, d_model, d_hidden)
            for k in keys]


def moe_text_encoder_forward(module, variables, moe_blocks, ids,
                             moe_apply=None, *, with_aux: bool = False):
    """The REAL TextEncoder with each block's dense feed-forward swapped
    for a top-1 MoE: embed → per block (attention residual, then
    x + MoE(ln_2 x)) → final LN + pool. ``moe_apply(params, tokens)``
    defaults to the single-device :func:`moe_forward`; pass a
    ``make_sharded_moe(mesh)`` for expert parallelism — the attention
    trunk and routing math are identical either way, which is what the
    sharded-vs-single equivalence tests assert.

    ``with_aux=True``: ``moe_apply`` must be aux-returning (pass
    ``return_aux=True`` to either builder); the output dict gains
    ``balance_loss`` (mean over blocks — add it, scaled, to the task
    loss when TRAINING the MoE) and per-block ``expert_fraction``."""
    from ..dl.text_encoder import EncoderBlock

    moe_apply = moe_apply or functools.partial(moe_forward,
                                               return_aux=with_aux)
    block = EncoderBlock(module.heads, module.mlp_dim, module.width,
                         attention_fn=module.attention_fn,
                         dtype=module.dtype)
    x = module.apply(variables, ids, method="embed_ids")
    key_mask = ids != 0
    N, T = ids.shape
    W = module.width
    balance, fractions = [], []
    # pads must not consume capacity slots (capacity dispatch ranks
    # queues in flattened batch order; identical pad embeddings would
    # otherwise pile onto one expert ahead of real tokens)
    valid = key_mask.reshape(N * T)
    for i in range(module.depth):
        bvars = {"params": variables["params"][f"block{i}"]}
        x = block.apply(bvars, x, key_mask, method="attend")
        h = block.apply(bvars, x, method="pre_ffn_norm")
        y = moe_apply(moe_blocks[i],
                      h.reshape(N * T, W).astype(jnp.float32),
                      valid=valid)
        if with_aux:
            y, aux = y
            balance.append(aux["balance_loss"])
            fractions.append(aux["expert_fraction"])
        x = x + y.reshape(N, T, W).astype(x.dtype)
    out = module.apply(variables, x, ids, method="finalize")
    if with_aux:
        out["balance_loss"] = jnp.mean(jnp.stack(balance))
        out["expert_fraction"] = jnp.stack(fractions)
    return out


def make_moe_train_step(mesh, module, tx, *, axis: str = "ep",
                        balance_weight: float = 1e-2, loss_fn=None,
                        capacity_factor: float | None = 1.25):
    """Jitted expert-parallel TRAINING step for the MoE text encoder:
    (opt_state, variables, moe_blocks, ids, y) → updated (opt_state,
    variables, moe_blocks, loss, balance). Gradients flow to the
    attention trunk, the experts, AND the router (through the Switch
    gate multiplier); the load-balance aux (scaled by
    ``balance_weight``) keeps routing spread. Experts stay sharded over
    ``axis`` throughout — the optimizer update runs on the sharded
    leaves, so expert state never gathers.

    Training defaults to CAPACITY dispatch (``capacity_factor=1.25``,
    the Switch-Transformer setting): per-device expert compute is
    independent of the expert count, the formulation that scales;
    pass ``None`` for the exact dense-masked oracle."""
    import optax

    sharded = make_sharded_moe(mesh, axis=axis, return_aux=True,
                               capacity_factor=capacity_factor)
    loss_fn = loss_fn or (
        lambda pooled, t: jnp.mean((pooled.mean(-1) - t) ** 2))

    def loss_of(trainable, ids, y):
        variables, moe_blocks = trainable
        out = moe_text_encoder_forward(module, variables, moe_blocks,
                                       ids, moe_apply=sharded,
                                       with_aux=True)
        task = loss_fn(out["pooled"], y)
        return task + balance_weight * out["balance_loss"], \
            (task, out["balance_loss"])

    @jax.jit
    def step(opt_state, variables, moe_blocks, ids, y):
        (_, (task, balance)), grads = jax.value_and_grad(
            loss_of, has_aux=True)((variables, moe_blocks), ids, y)
        updates, opt_state = tx.update(grads, opt_state,
                                       (variables, moe_blocks))
        variables, moe_blocks = optax.apply_updates(
            (variables, moe_blocks), updates)
        return opt_state, variables, moe_blocks, task, balance

    return step


def make_moe_text_encoder(mesh, module, variables, moe_blocks, *,
                          axis: str = "ep",
                          capacity_factor: float | None = None):
    """Expert-parallel MoE text encoder: experts shard over ``axis``,
    attention stays replicated. Returns ``fn(ids) -> {"tokens",
    "pooled"}`` matching the single-device
    :func:`moe_text_encoder_forward` bit-for-bit up to psum ordering
    (pass the same ``capacity_factor`` to both for capacity mode)."""
    sharded = make_sharded_moe(mesh, axis=axis,
                               capacity_factor=capacity_factor)

    def forward(ids):
        return moe_text_encoder_forward(module, variables, moe_blocks,
                                        ids, moe_apply=sharded)
    return forward
