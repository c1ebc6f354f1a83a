"""The boosting loop: objectives → trees → scores, with all four boosting
modes, sampling, and early stopping.

Role of the reference's ``trainCore`` iteration loop
(``lightgbm/TrainUtils.scala:360-427``: update-one-iter, eval metrics, early
stopping, delegate hooks) — but the "update one iteration" is our own jitted
tree grower rather than a JNI call, and per-iteration score updates are O(n)
gathers instead of full re-predicts.

Boosting modes (reference ``boostingType`` param, ``LightGBMConstants``):
  gbdt — standard gradient boosting
  rf   — random forest: bagged trees on constant init scores, averaged
  dart — dropout: random subset of prior trees dropped when computing
         gradients, new tree + dropped trees rescaled (Rashmi & Gilad-Bachrach)
  goss — gradient one-side sampling: keep top-|g| rows, subsample the rest
         with amplification (1-a)/b
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.utils import stable_sigmoid
from ..obs import registry as _obs
from ..obs.tracing import tracer as _tracer
from ..utils.platform import target_platform
from .binning import bin_features, compute_bin_boundaries, bin_upper_value
from .booster import Booster
from .engine import Tree, TreeParams, grow_tree, tree_route_bins
from .objectives import Objective, get_objective
from ..parallel.compat import shard_map as _shard_map
from .sparse import (SparseData, bin_sparse, compute_sparse_bin_boundaries,
                     grow_tree_sparse, pad_sparse, sparse_route_bins)


@dataclasses.dataclass
class TrainConfig:
    objective: str = "regression"
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_depth: int = -1
    max_bin: int = 255
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    feature_fraction: float = 1.0
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0  # class-stratified bagging (binary)
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    boosting_type: str = "gbdt"
    top_rate: float = 0.2          # goss
    other_rate: float = 0.1        # goss
    drop_rate: float = 0.1         # dart
    max_drop: int = 50             # dart
    skip_drop: float = 0.5         # dart
    uniform_drop: bool = False     # dart (parity; sampling is uniform)
    dart_mode: str = "fused"       # fused: one dispatch/iter with device
                                   # delta buffers; stepwise: the reference
                                   # semantics oracle (host-applied drops)
    sparse_max_bin: int = 16       # bin cap for the padded-COO path
    num_class: int = 1
    sigmoid: float = 1.0
    alpha: float = 0.9             # quantile / huber
    fair_c: float = 1.0
    tweedie_variance_power: float = 1.5
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    boost_from_average: bool = True
    seed: int = 0
    bagging_seed: int = 3
    bin_sample_count: int = 200_000
    early_stopping_round: int = 0
    metric: str = ""
    is_provide_training_metric: bool = False
    verbosity: int = -1
    eval_freq: int = 1             # evaluate every k iterations (de-sync)
    scan_chunk: int = 8            # iterations fused per dispatch when
                                   # nothing observes per-iteration state
    parallelism: str = "data_parallel"  # | voting_parallel (PV-Tree)
    top_k: int = 20                # voting: local nominations per shard
    categorical_features: tuple = ()  # slot indexes with set-based splits
    cat_smooth: float = 10.0       # hessian smoothing in the cat sort
    max_cat_threshold: int = 32    # max categories in a split's left set
    max_delta_step: float = 0.0    # cap on leaf outputs (0 = off)
    improvement_tolerance: float = 0.0  # early stopping must beat this
    max_bin_by_feature: tuple = ()  # per-feature bin budgets (dense only)
    xgboost_dart_mode: bool = False
    # engine plumbing
    psum_axis: str | None = None
    fobj: Callable | None = None

    def __post_init__(self):
        from .objectives import canonical_objective
        self.objective = canonical_objective(self.objective)
        if self.categorical_features and self.max_cat_threshold <= 0:
            # all-False cap would silently disable every categorical
            # split (native LightGBM: CHECK_GT(max_cat_threshold, 0))
            raise ValueError(
                f"maxCatThreshold={self.max_cat_threshold} must be "
                "positive when categorical slots are declared")
        if self.xgboost_dart_mode and self.boosting_type == "dart":
            # the xgboost-style normalization constants are native
            # implementation details; wrong guessed semantics would be
            # worse than a loud gap. Inert (like the reference) when the
            # boosting type is not dart.
            raise NotImplementedError(
                "xgboostDartMode is not implemented; use the default "
                "DART normalization (new tree 1/(k+1), dropped k/(k+1))")
        if (self.pos_bagging_fraction != 1.0
                or self.neg_bagging_fraction != 1.0) \
                and self.objective != "binary":
            # label-sign stratification is meaningless outside binary;
            # native LightGBM restricts these params the same way
            raise ValueError(
                "posBaggingFraction/negBaggingFraction require the "
                f"binary objective (got {self.objective!r})")

    def tree_params(self) -> TreeParams:
        # rf: trees are averaged, never shrunk (LightGBM rf.hpp forces
        # shrinkage_rate = 1; a shrunk average can't move the init score)
        lr = 1.0 if self.boosting_type == "rf" else self.learning_rate
        return TreeParams(
            num_leaves=self.num_leaves, max_depth=self.max_depth,
            max_bin=self.max_bin, learning_rate=lr,
            lambda_l1=self.lambda_l1, lambda_l2=self.lambda_l2,
            min_data_in_leaf=self.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
            min_gain_to_split=self.min_gain_to_split,
            parallelism=("voting" if self.parallelism == "voting_parallel"
                         else "data"),
            top_k=self.top_k,
            cat_features=tuple(self.categorical_features),
            cat_smooth=self.cat_smooth,
            max_cat_threshold=self.max_cat_threshold,
            max_delta_step=self.max_delta_step)


def _score_update(c, d, coeff, cls):
    """`c += coeff·d` (into class column ``cls`` when c is [n, K]).

    The ONE arithmetic shape for every DART score update, used inline by
    the fused step and via the jitted ``_apply_weighted`` by the stepwise
    oracle: XLA/LLVM contract the mul+add into an FMA, so eager two-op
    updates round differently — sharing the compiled expression is what
    makes the two paths bit-comparable."""
    upd = d * coeff
    if c.ndim == 1:
        return c + upd
    return c.at[:, cls].add(upd)


_apply_weighted = jax.jit(_score_update)


def _dart_drop_set(rng, cfg: TrainConfig, n_flat: int) -> list[int]:
    """Host-side DART drop-set draw (LightGBM DartBooster::DroppingTrees):
    skip with probability skip_drop, else drop round(drop_rate·n) of the
    standing trees, capped at max_drop, uniformly without replacement.
    Shared by the stepwise and fused paths so both consume the identical
    RNG sequence — the fused path's bit-match guarantee starts here."""
    if n_flat == 0 or rng.random() < cfg.skip_drop:
        return []
    k_drop = min(cfg.max_drop, max(1, int(round(cfg.drop_rate * n_flat))))
    return sorted(rng.choice(n_flat, size=min(k_drop, n_flat),
                             replace=False).tolist())


# test instrumentation: when set to a dict, train() stashes its final
# running scores there (bit-match tests compare the device-maintained
# margin across boosting paths, which the booster recomputation can mask)
# and how it placed the binned training rows (``chip_smoke.py --chips 4``
# asserts they are spread over the mesh, not parked on the first device)
_debug_capture: dict | None = None


@dataclasses.dataclass
class TrainResult:
    booster: Booster
    evals: list[dict]
    best_iteration: int
    # de-sync diagnostics: host↔device transfers that happened inside the
    # boosting loop, split by cause. Small fixed-size tree pulls are
    # unavoidable (the booster lives on host); O(n) score pulls must NOT
    # scale with iteration count (review round 1 weak #5).
    host_pulls_bulk: int = 0      # O(n)-sized device→host copies
    host_pulls_scalar: int = 0    # scalar metric reads


@functools.partial(jax.jit, static_argnames=("top_n", "other_n"))
def _goss_mask(gmag, valid_mask, key, *, top_n: int, other_n: int,
               amplify: float):
    """GOSS row mask fully on device (review round 1 weak #5: the old
    host-side np.argsort serialized the device every iteration).

    Keeps the top_n rows by |gradient| at weight 1 and other_n uniformly
    sampled remaining rows amplified by (1-top_rate)/other_rate — the
    LightGBM GOSS estimator."""
    n = gmag.shape[0]
    gmag = gmag * valid_mask
    order = jnp.argsort(-gmag)
    rank = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    top = rank < top_n
    rest = (~top) & (valid_mask > 0)
    r = jnp.where(rest, jax.random.uniform(key, (n,)), -1.0)
    rorder = jnp.argsort(-r)
    rrank = jnp.zeros(n, jnp.int32).at[rorder].set(
        jnp.arange(n, dtype=jnp.int32))
    other = rest & (rrank < other_n)
    return top * 1.0 + other * jnp.float32(amplify)


def make_grower(*, mesh, mesh_axis: str | tuple | None, tp: TreeParams,
                multi: bool, num_features: int, num_bins: int = 0,
                dense_bins=None, sparse_binned=None):
    """ONE factory for every growth variant: dense or padded-COO data ×
    single-class or K-class-vmapped. Returns ``fn(g, h, feat_mask,
    row_mask) → (Tree, row_leaf)``; for ``multi`` g/h carry a leading
    class axis [K, n] and the Tree is stacked on K.

    With a mesh, rows shard over ``mesh_axis`` and the histogram
    reduction inside the grower becomes a real ``psum`` collective (the
    reference's socket allreduce, ``TrainUtils.scala:609-625``, on ICI).
    ``mesh_axis`` may be a TUPLE of axis names for a hierarchical mesh
    (e.g. ``("slice", "dp")``): rows shard over the product and the
    psum composes across both levels — ICI within a slice, DCN across
    slices (SURVEY §2.13).
    Binned data is threaded as explicit args — ``shard_map`` must not
    close over sharded arrays.
    """
    from jax.sharding import PartitionSpec as P
    sparse = sparse_binned is not None
    psax = mesh_axis if mesh is not None else None
    if sparse:
        data = (sparse_binned.indices, sparse_binned.ebins,
                sparse_binned.zero_bin)
        data_specs = (P(mesh_axis), P(mesh_axis), P())

        def body(i, e, z, g2, h2, fm, rm):
            def one(gk, hk):
                return grow_tree_sparse(
                    i, e, z, gk, hk, fm, rm, params=tp,
                    num_features=num_features, num_bins=num_bins,
                    psum_axis=psax)
            return jax.vmap(one)(g2, h2) if multi else one(g2, h2)
    else:
        data = (dense_bins,)
        data_specs = (P(mesh_axis),)

        def body(b, g2, h2, fm, rm):
            def one(gk, hk):
                return grow_tree(b, gk, hk, fm, rm, params=tp,
                                 num_features=num_features,
                                 psum_axis=psax)
            return jax.vmap(one)(g2, h2) if multi else one(g2, h2)

    if mesh is None:
        jitted = jax.jit(body)
        return lambda g2, h2, fm, rm: jitted(*data, g2, h2, fm, rm)
    gh_spec = P(None, mesh_axis) if multi else P(mesh_axis)
    mapped = _shard_map(
        body, mesh=mesh,
        in_specs=(*data_specs, gh_spec, gh_spec, P(), P(mesh_axis)),
        out_specs=(P(), gh_spec), check_vma=False)
    return lambda g2, h2, fm, rm: mapped(*data, g2, h2, fm, rm)


def _goss_row_select(key, *, top_n: int, other_n: int, amplify: float):
    """Row-selection hook for GOSS — one body for both fused builders."""
    def row_select(g, rm, it_dev):
        gmag = jnp.abs(g) if g.ndim == 1 else jnp.linalg.norm(g, axis=1)
        return _goss_mask(gmag, rm, jax.random.fold_in(key, it_dev),
                          top_n=top_n, other_n=other_n, amplify=amplify)
    return row_select


def _identity_row_select(g, rm, it_dev):
    return rm


def _chunk_scan(step_fn):
    """k boosting iterations as ONE dispatch: lax.scan over the fused
    step. Used only when nothing observes per-iteration state (no eval,
    no delegate) — a remote device pays a full round trip per dispatch,
    so chunking divides that cost by the chunk length. One body for both
    fused builders."""
    def chunk(scores, vscores, fms, rms, its):
        def body(carry, xs):
            sc, vs = carry
            fm, rm, it_d = xs
            new_sc, new_vs, tree_b = step_fn(sc, vs, fm, rm, it_d)
            return (new_sc, new_vs), tree_b
        (sc, vs), tree_stack = jax.lax.scan(body, (scores, vscores),
                                            (fms, rms, its))
        return sc, vs, tree_stack
    return chunk


def _fused_step_math(scores, vscores, fm, rm, it_dev, *, base, gh_fn,
                     row_select, grow_one, routed_vdelta, is_rf: bool,
                     K: int, has_valid: bool):
    """THE fused boosting-iteration math — gradients → row selection →
    growth → train/valid score updates — shared verbatim by the
    cross-fit-cached builder (``_build_fused``) and the per-fit closure
    builder (``make_fused_step``), so the two paths cannot drift.

    ``base``: init score (scalar for K==1, [K] otherwise); ``gh_fn(s)``
    → (grad, hess); ``row_select(g, rm, it)`` → effective row mask
    (GOSS sampling or identity); ``grow_one(g, h, fm, rm)`` → ([K,…]
    Tree stack, [K, n] train deltas); ``routed_vdelta(tree_b)`` → [K, nv]
    valid deltas."""
    # rf: gradients always at the constant init score (trees are
    # independent); gbdt/goss: at the running margin
    sfg = (jnp.zeros_like(scores) + base) if is_rf else scores
    g, h = gh_fn(sfg)
    rm2 = row_select(g, rm, it_dev)
    tree_b, delta_b = grow_one(g, h, fm, rm2)
    d = delta_b[0] if K == 1 else delta_b.T
    if is_rf:
        # running average of tree outputs around the init score:
        # scores = base + prev + (d - prev)/m with m = it + 1
        m = (it_dev + 1).astype(jnp.float32)
        new_scores = scores + (d - (scores - base)) / m
    else:
        new_scores = scores + d
    if has_valid:
        vd_b = routed_vdelta(tree_b)
        vd = vd_b[0] if K == 1 else vd_b.T
        if is_rf:
            m = (it_dev + 1).astype(jnp.float32)
            new_vscores = vscores + (vd - (vscores - base)) / m
        else:
            new_vscores = vscores + vd
    else:
        new_vscores = vscores
    return new_scores, new_vscores, tree_b


class _FusedStatics(NamedTuple):
    """Everything that shapes the fused boosting step's trace, as a
    hashable cross-fit cache key. Arrays ride the ``data`` pytree argument
    instead — a cached trace must never bake one fit's data in as
    constants, or the next same-shape fit would silently train on stale
    labels. Over-keying is safe (an extra cache entry); under-keying is
    not, so every config field the trace can see is here."""
    obj_key: tuple          # get_objective kwargs, incl. derived pos_weight
    tp: TreeParams          # growth statics (leaves, bins, reg, cats, …)
    boosting: str           # gbdt | goss | rf | dart
    K: int
    n: int
    F: int
    sparse: bool
    num_bins: int           # sparse bin count (0 on the dense path)
    has_valid: bool
    top_n: int              # goss statics (0/0/1.0 otherwise)
    other_n: int
    amplify: float


# LRU of (step, chunk_step) jitted callables. Re-jitting per fit retraces
# AND recompiles the whole fused program, paid by every fit in an AutoML
# sweep or CV fold. Bounded: each entry pins compiled executables.
_FUSED_CACHE: OrderedDict = OrderedDict()
_FUSED_CACHE_MAX = 16


def _statics_objective(st: _FusedStatics) -> Objective:
    name, num_class, alpha, fair_c, tvp, sigmoid, pos_weight, bfa = \
        st.obj_key
    return get_objective(name, num_class=num_class, alpha=alpha,
                         fair_c=fair_c, tweedie_variance_power=tvp,
                         sigmoid=sigmoid, pos_weight=pos_weight,
                         boost_from_average=bfa)


def _data_growers(st: _FusedStatics):
    """(grow_one, routed_vdelta) reading their arrays from the ``data``
    pytree — shared by the cached gbdt/goss/rf and dart builders."""
    arange_k = jnp.arange(st.K)

    def grow_one(data, g, h, fm, rm):
        if st.sparse:
            def one(gk, hk):
                return grow_tree_sparse(
                    data["si"], data["se"], data["sz"], gk, hk, fm, rm,
                    params=st.tp, num_features=st.F,
                    num_bins=st.num_bins, psum_axis=None)
        else:
            def one(gk, hk):
                return grow_tree(data["bins"], gk, hk, fm, rm,
                                 params=st.tp, num_features=st.F,
                                 psum_axis=None)
        if st.K == 1:
            t1, rl1 = one(g, h)
            tree_b = jax.tree.map(lambda a: a[None], t1)
            row_leaf_b = rl1[None]
        else:
            tree_b, row_leaf_b = jax.vmap(one)(g.T, h.T)
        # growth ran at learning_rate=1 (st.tp pins it) so the trace is
        # lr-independent — an AutoML learning-rate sweep reuses one
        # compiled step. The shrinkage lands here as a traced scalar;
        # bit-identical to the closure path's post-hoc multiply in
        # train()'s grow_one (identical operands through one isolated
        # f32 multiply — NOT to the old in-grower constant multiply,
        # which XLA fused with the leaf-output division and rounded
        # ~1 ulp differently; that is why shrinkage moved out of the
        # growers everywhere, see make_growers).
        tree_b = tree_b._replace(leaf_value=tree_b.leaf_value
                                 * data["lr"])
        return tree_b, tree_b.leaf_value[arange_k[:, None], row_leaf_b]

    def routed_vdelta(data, tree_b):
        if st.sparse:
            vleaf = jax.vmap(lambda t: sparse_route_bins(
                t, data["vi"], data["ve"], data["vz"],
                max_depth=st.tp.num_leaves))(tree_b)
        else:
            vleaf = jax.vmap(lambda t: tree_route_bins(
                t, data["vb"], max_depth=st.tp.num_leaves))(tree_b)
        return tree_b.leaf_value[arange_k[:, None], vleaf]

    return grow_one, routed_vdelta


def _build_fused(st: _FusedStatics):
    """(step, chunk_step) for one static configuration; both take the
    per-fit arrays as a leading ``data`` pytree. Bodies mirror the
    closure-based ``make_fused_step`` (kept for the delegate/fobj/mesh
    paths) — the math must stay identical between the two."""
    obj = _statics_objective(st)
    is_rf = st.boosting == "rf"
    is_goss = st.boosting == "goss"
    grow_one, routed_vdelta = _data_growers(st)

    def step_impl(data, scores, vscores, fm, rm, it_dev):
        return _fused_step_math(
            scores, vscores, fm, rm, it_dev, base=data["base"],
            gh_fn=lambda s: obj.grad_hess(s, data["y"], data["w"]),
            row_select=_goss_row_select(
                data["gkey"], top_n=st.top_n, other_n=st.other_n,
                amplify=st.amplify) if is_goss else _identity_row_select,
            grow_one=lambda g, h, fm2, rm2: grow_one(data, g, h, fm2,
                                                     rm2),
            routed_vdelta=lambda tb: routed_vdelta(data, tb),
            is_rf=is_rf, K=st.K, has_valid=st.has_valid)

    step = jax.jit(step_impl)

    @jax.jit
    def chunk_step(data, scores, vscores, fms, rms, its):
        return _chunk_scan(functools.partial(step_impl, data))(
            scores, vscores, fms, rms, its)

    return step, chunk_step


def _dart_sub_body(c, xs, coeff_fn, K: int):
    """Apply one (possibly padded) dropped tree's contribution to the
    carried scores, mirroring the stepwise loop's ascending per-tree
    order. ``coeff_fn(w)`` maps the tree's standing weight to the scalar
    coefficient exactly as the oracle computes it on host (barriers pin
    each scalar rounding step — XLA would otherwise carry the chain in
    excess precision); the padding mask multiplies last (exact: ×1 or
    ×±0, and ±0·d FMA-adds as an exact no-op)."""
    deltas, weights, idx, val = xs
    coeff = coeff_fn(weights[idx]) * val
    return _score_update(c, deltas[idx], coeff, jnp.mod(idx, K)), None


def _dart_step_math(scores, vscores, deltas_buf, vdeltas_buf,
                    weights_buf, didx, dval, new_w, factor,
                    feat_mask_dev, row_mask_dev, it_dev, *, gh_fn,
                    grow_one, routed_vdelta, K: int, has_valid: bool):
    """THE fused DART iteration — dropped-margin reconstruction →
    gradients → growth → new-tree add → standing-tree rescale → buffer
    updates — shared verbatim by the cross-fit-cached builder
    (``_build_dart``) and the per-fit closure builder
    (``make_dart_step``), so the two paths cannot drift. Bit-matches the
    stepwise oracle (``dart_mode="stepwise"``) by construction."""
    # 1) margin with dropped trees removed (gradients see it)
    eff, _ = jax.lax.scan(
        lambda c, xs: _dart_sub_body(
            c, (deltas_buf, weights_buf) + xs, lambda w: -w, K),
        scores, (didx, dval))
    g, h = gh_fn(eff)
    tree_b, delta_b = grow_one(g, h, feat_mask_dev, row_mask_dev)
    # 2) new tree enters at weight 1/(k+1), class-ascending
    new_scores = scores
    for k_cls in range(K):
        new_scores = _score_update(new_scores, delta_b[k_cls], new_w,
                                   jnp.int32(k_cls))
    if has_valid:
        vdelta_b = routed_vdelta(tree_b)
        new_vscores = vscores
        for k_cls in range(K):
            new_vscores = _score_update(new_vscores, vdelta_b[k_cls],
                                        new_w, jnp.int32(k_cls))
    else:
        vdelta_b = None
        new_vscores = vscores
    # 3) dropped trees' standing contribution rescales by k/(k+1).
    # Each scalar step is barriered to its own f32 rounding — the
    # stepwise oracle computes this coefficient on host in numpy f32,
    # and XLA would otherwise carry the chain in excess precision and
    # land 1 ulp away.
    fm1 = jax.lax.optimization_barrier(factor - 1.0)
    rescale = lambda w: jax.lax.optimization_barrier(  # noqa: E731
        w * fm1)
    new_scores, _ = jax.lax.scan(
        lambda c, xs: _dart_sub_body(
            c, (deltas_buf, weights_buf) + xs, rescale, K),
        new_scores, (didx, dval))
    if has_valid:
        new_vscores, _ = jax.lax.scan(
            lambda c, xs: _dart_sub_body(
                c, (vdeltas_buf, weights_buf) + xs, rescale, K),
            new_vscores, (didx, dval))
    # 4) buffers: slot in this iteration's deltas, fold the factor into
    # dropped weights (padded entries multiply by 1)
    slot = it_dev * K
    new_deltas = jax.lax.dynamic_update_slice(
        deltas_buf, delta_b, (slot, jnp.int32(0)))
    new_vdeltas = vdeltas_buf if vdelta_b is None else \
        jax.lax.dynamic_update_slice(vdeltas_buf, vdelta_b,
                                     (slot, jnp.int32(0)))
    new_weights = weights_buf.at[didx].multiply(
        jnp.where(dval > 0, factor, 1.0))
    new_weights = jax.lax.dynamic_update_slice(
        new_weights, jnp.broadcast_to(new_w, (K,)), (slot,))
    return (new_scores, new_vscores, new_deltas, new_vdeltas,
            new_weights, tree_b)


def _dart_chunk_scan(step_fn):
    """k fused-DART iterations as ONE dispatch — one body for both dart
    builders. ``step_fn`` is the 12-arg dart step."""
    def chunk(scores, vscores, deltas_buf, vdeltas_buf, weights_buf,
              feat_masks, row_masks, its, didxs, dvals, new_ws, factors):
        def body(carry, xs):
            out = step_fn(*carry, *xs[3:], *xs[:3])
            return out[:5], out[5]
        carry, tree_stack = jax.lax.scan(
            body,
            (scores, vscores, deltas_buf, vdeltas_buf, weights_buf),
            (feat_masks, row_masks, its, didxs, dvals, new_ws, factors))
        return carry + (tree_stack,)
    return chunk


def _build_dart(st: _FusedStatics):
    """Cross-fit-cacheable fused-DART (step, chunk) — the dart twin of
    ``_build_fused``."""
    obj = _statics_objective(st)
    grow_one, routed_vdelta = _data_growers(st)

    def dart_impl(data, scores, vscores, deltas_buf, vdeltas_buf,
                  weights_buf, didx, dval, new_w, factor, feat_mask_dev,
                  row_mask_dev, it_dev):
        return _dart_step_math(
            scores, vscores, deltas_buf, vdeltas_buf, weights_buf, didx,
            dval, new_w, factor, feat_mask_dev, row_mask_dev, it_dev,
            gh_fn=lambda s: obj.grad_hess(s, data["y"], data["w"]),
            grow_one=lambda g, h, fm, rm: grow_one(data, g, h, fm, rm),
            routed_vdelta=lambda tb: routed_vdelta(data, tb),
            K=st.K, has_valid=st.has_valid)

    # donate the O(T·n) buffers so each iteration updates them in place
    # (CPU lacks donation and would warn on every compile); +1 for the
    # leading data arg. Gate on the PLACEMENT platform, not the default
    # backend: under an active default_device(cpu) pin on a TPU-backed
    # process the computation lands on CPU and donation would warn on
    # every compile (and the cached entry bakes the decision in).
    donate = (3, 4, 5) if target_platform() == "tpu" else ()
    step = jax.jit(dart_impl, donate_argnums=donate)

    @functools.partial(jax.jit, donate_argnums=donate)
    def dart_chunk(data, scores, vscores, deltas_buf, vdeltas_buf,
                   weights_buf, feat_masks, row_masks, its, didxs,
                   dvals, new_ws, factors):
        return _dart_chunk_scan(functools.partial(dart_impl, data))(
            scores, vscores, deltas_buf, vdeltas_buf, weights_buf,
            feat_masks, row_masks, its, didxs, dvals, new_ws, factors)

    return step, dart_chunk


def _fused_cached(st: _FusedStatics):
    builder = _build_dart if st.boosting == "dart" else _build_fused
    fns = _FUSED_CACHE.get(st)
    if fns is None:
        fns = builder(st)
        _FUSED_CACHE[st] = fns
        while len(_FUSED_CACHE) > _FUSED_CACHE_MAX:
            _FUSED_CACHE.popitem(last=False)
    else:
        _FUSED_CACHE.move_to_end(st)
    return fns


def train(x: np.ndarray, y: np.ndarray, w: np.ndarray | None,
          config: TrainConfig,
          valid: tuple[np.ndarray, np.ndarray, np.ndarray | None]
          | None = None,
          init_booster: Booster | None = None,
          init_scores: np.ndarray | None = None,
          valid_init_scores: np.ndarray | None = None,
          feature_names: list[str] | None = None,
          grad_hess_override: Callable | None = None,
          valid_eval_fn: Callable | None = None,
          delegate=None, mesh=None,
          mesh_axis: str | tuple = "dp") -> TrainResult:
    """Training loop. x [n, F] float32 (NaN = missing), y [n].

    ``grad_hess_override`` lets the ranker inject lambdarank gradients (it
    receives raw scores and returns (grad, hess)). ``init_scores`` is the
    per-row warm start (reference ``initScoreCol``).

    ``mesh``: distributed data-parallel training — rows are sharded over
    ``mesh_axis`` and each tree's histogram build runs under ``shard_map``
    with a ``psum`` reduction, the TPU equivalent of the reference's
    socket-mesh histogram allreduce (``TrainUtils.scala:609-625``); rows are
    padded to the shard count with zero-weight masks (the SPMD version of
    the empty-partition ``ignore`` protocol, ``TrainUtils.scala:652-669``).
    """
    cfg = config
    sparse = isinstance(x, SparseData)
    n_real = x.n_rows if sparse else x.shape[0]
    pad_mask = None
    if mesh is not None:
        from ..parallel.sharding import pad_rows
        n_dev = int(np.prod([mesh.shape[a] for a in mesh_axis])) \
            if isinstance(mesh_axis, tuple) else int(mesh.shape[mesh_axis])
        if sparse:
            x, _ = pad_sparse(x, n_dev)
        else:
            x, _ = pad_rows(np.asarray(x, np.float32), n_dev)
        (y, w, init_scores), pad_np = pad_rows(
            [np.asarray(y, np.float32),
             None if w is None else np.asarray(w, np.float32),
             None if init_scores is None
             else np.asarray(init_scores, np.float32)], n_dev)
        pad_mask = pad_np
    n = x.n_rows if sparse else x.shape[0]
    F = x.num_features if sparse else x.shape[1]
    rng = np.random.default_rng(cfg.seed)
    bag_rng = np.random.default_rng(cfg.bagging_seed)
    w_np = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
    if pad_mask is not None:
        w_np = w_np * pad_mask

    pos_weight = cfg.scale_pos_weight
    if cfg.is_unbalance and cfg.objective == "binary":
        npos = float((y[:n_real] > 0).sum())
        nneg = float(n_real - npos)
        pos_weight = nneg / max(npos, 1.0)

    if cfg.fobj is not None:
        from .objectives import custom_objective
        obj = custom_objective(cfg.fobj)
    else:
        obj = get_objective(
            cfg.objective, num_class=cfg.num_class, alpha=cfg.alpha,
            fair_c=cfg.fair_c,
            tweedie_variance_power=cfg.tweedie_variance_power,
            sigmoid=cfg.sigmoid, pos_weight=pos_weight,
            boost_from_average=cfg.boost_from_average)

    K = max(obj.num_model_per_iter, 1)
    tp = cfg.tree_params()

    # ---- binning (host boundaries, device mapping)
    if sparse:
        if cfg.max_bin_by_feature:
            raise NotImplementedError(
                "maxBinByFeature is dense-only: the sparse binning's "
                "reserved zero-separator cuts cannot be truncated")
        sparse_b = min(cfg.sparse_max_bin, cfg.max_bin)
        # bin_sample_count is a ROW budget; the COO sampler works in
        # entries, so scale by the per-row entry capacity W
        entry_budget = cfg.bin_sample_count * max(x.indices.shape[1], 1)
        boundaries = compute_sparse_bin_boundaries(
            x, sparse_b, sample_cnt=entry_budget, seed=cfg.seed)
        # bins 1..(#cuts+1) for values, bin 0 for missing
        B_s = boundaries.shape[1] + 2
        for f in cfg.categorical_features:
            # identity binning for categorical slots (the sparse twin of
            # the dense loop below): category c → bin c+1 exactly, and
            # implicit zeros land in bin 1 = category 0. Cardinality is
            # bounded by the sparse bin budget.
            ent = x.values[x.indices == f]
            vals = ent[~np.isnan(ent)]
            if vals.size and (np.any(vals < 0)
                              or np.any(vals != np.floor(vals))):
                raise ValueError(
                    f"categorical slot {f} must hold non-negative "
                    "integer category ids (reference LightGBM "
                    "requirement); index labels first (ValueIndexer)")
            cap = boundaries.shape[1]
            if vals.size and vals.max() > cap:
                raise ValueError(
                    f"categorical slot {f} has category id "
                    f"{int(vals.max())} > {cap} (the effective sparse "
                    "bin budget, min(sparseMaxBin, maxBin)); raise "
                    "whichever is binding, or re-index the categories")
            boundaries[f] = np.arange(cap) + 0.5
        binned = bin_sparse(x, boundaries)
        bins = None
    else:
        boundaries = compute_bin_boundaries(x[:n_real], cfg.max_bin,
                                            sample_cnt=cfg.bin_sample_count,
                                            seed=cfg.seed)
        if cfg.max_bin_by_feature:
            # LightGBM max_bin_by_feature: per-feature bin budgets. A
            # budget of k bins keeps the first k-1 cuts (the rest become
            # +inf, i.e. empty bins — the scan just never splits there).
            budgets = tuple(cfg.max_bin_by_feature)
            if len(budgets) != F:
                raise ValueError(
                    f"maxBinByFeature has {len(budgets)} entries for "
                    f"{F} features")
            for f, budget in enumerate(budgets):
                if not budget:
                    continue
                if budget == 1:
                    # all cuts at +inf would silently disable the
                    # feature (LightGBM: max_bin_by_feature > 1)
                    raise ValueError(
                        f"maxBinByFeature[{f}]=1 would leave feature "
                        f"{f} unsplittable; use >= 2 (or 0 for the "
                        "default budget)")
                if f in cfg.categorical_features:
                    # identity binning would overwrite the budget below
                    raise ValueError(
                        f"maxBinByFeature cannot cap categorical slot "
                        f"{f}: categories bin by id (cap cardinality "
                        "by re-indexing instead)")
                if budget < cfg.max_bin:
                    boundaries[f, budget - 1:] = np.inf
        for f in cfg.categorical_features:
            # identity binning for categorical slots: category c (an
            # integer value) lands in bin c+1 exactly, so the engine's
            # per-bin histogram IS the per-category histogram (LightGBM
            # bins categories by id too). Cardinality is bounded by the
            # bin budget — sharing a bin would silently merge categories
            # and break text-format round trips.
            col = x[:n_real, f]
            vals = col[~np.isnan(col)]
            if vals.size and (np.any(vals < 0)
                              or np.any(vals != np.floor(vals))):
                raise ValueError(
                    f"categorical slot {f} must hold non-negative "
                    "integer category ids (reference LightGBM "
                    "requirement); index labels first (ValueIndexer)")
            if vals.size and vals.max() > cfg.max_bin - 2:
                raise ValueError(
                    f"categorical slot {f} has category id "
                    f"{int(vals.max())} > max_bin-2 = {cfg.max_bin - 2}; "
                    "raise maxBin or re-index the categories")
            k = boundaries.shape[1]
            boundaries[f] = np.arange(k) + 0.5
        bins = bin_features(jnp.asarray(x, jnp.float32),
                            jnp.asarray(boundaries))
    y_dev = jnp.asarray(y, jnp.float32)
    w_dev = jnp.asarray(w_np)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        row_sh = NamedSharding(mesh, P(mesh_axis))
        row2_sh = NamedSharding(mesh, P(mesh_axis, None))
        if sparse:
            binned = binned._replace(
                indices=jax.device_put(binned.indices, row2_sh),
                ebins=jax.device_put(binned.ebins, row2_sh))
        else:
            bins = jax.device_put(bins, row2_sh)
        y_dev = jax.device_put(y_dev, row_sh)
        w_dev = jax.device_put(w_dev, row_sh)
    if _debug_capture is not None:
        rows_dev = binned.indices if sparse else bins
        _debug_capture["rows_placement"] = {
            "devices": len(rows_dev.sharding.device_set),
            "global_shape": list(rows_dev.shape),
            "shard_shape": list(
                rows_dev.sharding.shard_shape(rows_dev.shape))}

    # ---- init scores
    if init_scores is not None:
        base_score = np.zeros(K, np.float32) if K > 1 else \
            np.float32(0.0)
        scores = jnp.asarray(init_scores, jnp.float32)
        if K > 1 and scores.ndim == 1:
            scores = jnp.broadcast_to(scores[:, None], (n, K))
    elif init_booster is not None and init_booster.num_trees > 0:
        init_raw = init_booster.raw_scores(x)
        scores = jnp.asarray(init_raw, jnp.float32).reshape(n, K) \
            if K > 1 else jnp.asarray(init_raw, jnp.float32)
        base_score = init_booster.init_score
    else:
        base = obj.init_score(np.asarray(y), w_np)
        base_score = np.asarray(base, np.float32)
        scores = jnp.broadcast_to(
            jnp.asarray(base_score, jnp.float32).reshape(1, -1),
            (n, K)).astype(jnp.float32)
        scores = scores[:, 0] if K == 1 else scores

    is_rf = cfg.boosting_type == "rf"
    is_dart = cfg.boosting_type == "dart"
    is_goss = cfg.boosting_type == "goss"

    if grad_hess_override is not None and n != n_real:
        # ranker/custom gradients were built for the unpadded rows
        _orig_override = grad_hess_override

        def grad_hess_override(s):
            g0, h0 = _orig_override(s[:n_real])
            pad = [(0, n - n_real)] + [(0, 0)] * (g0.ndim - 1)
            return jnp.pad(g0, pad), jnp.pad(h0, pad)

    trees: list[Tree] = []
    tree_class: list[int] = []           # class index of each tree
    tree_deltas: list[jnp.ndarray] = []  # dart: cached per-tree train deltas
    tree_vdeltas: list = []              # dart: cached per-tree valid deltas
    tree_weights: list[float] = []

    evals: list[dict] = []
    best_iter, best_metric, rounds_no_improve = -1, None, 0
    bag_mask = np.ones(n, np.float32)
    # class-stratified bagging (LightGBM pos/neg_bagging_fraction):
    # independent keep-rates per class for unbalanced binary data
    stratified_bag = (cfg.pos_bagging_fraction != 1.0
                      or cfg.neg_bagging_fraction != 1.0)
    bagging_active = cfg.bagging_fraction < 1.0 or stratified_bag
    if stratified_bag:
        bag_thresh = np.where(np.asarray(y, np.float32) > 0,
                              np.float32(cfg.pos_bagging_fraction),
                              np.float32(cfg.neg_bagging_fraction))

    def draw_bag() -> np.ndarray:
        """One host-RNG bagging draw (plain or class-stratified); every
        path draws through here so chunked/fused/stepwise consume the
        identical RNG sequence."""
        u = bag_rng.random(n)
        if stratified_bag:
            return (u < bag_thresh).astype(np.float32)
        return (u < cfg.bagging_fraction).astype(np.float32)
    # single source of truth for the pad/ignore mask: host copy feeds the
    # fused path's host-side bagging product, device copy everything else
    valid_mask_np = np.asarray(pad_mask, np.float32) \
        if pad_mask is not None else np.ones(n, np.float32)
    valid_mask_dev = jnp.asarray(valid_mask_np)
    goss_key = jax.random.PRNGKey(cfg.bagging_seed)
    pulls_bulk = pulls_scalar = 0
    eval_freq = max(int(cfg.eval_freq), 1)

    # validation setup
    if valid is not None:
        xv, yv, wv = valid
        if sparse:
            if not isinstance(xv, SparseData):
                raise TypeError("validation features must be SparseData "
                                "when training data is sparse")
            vbinned = bin_sparse(xv, boundaries)
            nv = xv.n_rows
        else:
            vbins = bin_features(jnp.asarray(xv, jnp.float32),
                                 jnp.asarray(boundaries))
            nv = xv.shape[0]
        yv_dev = jnp.asarray(yv, jnp.float32)
        wv_dev = jnp.ones(nv, jnp.float32) if wv is None \
            else jnp.asarray(wv, jnp.float32)
        if valid_init_scores is not None:
            # validation rows get the same per-row warm start as training
            # rows (reference initScoreCol applies to every scored row) so
            # early-stopping metrics see comparable margins
            vscores = jnp.asarray(valid_init_scores, jnp.float32)
            if K > 1 and vscores.ndim == 1:
                vscores = jnp.broadcast_to(vscores[:, None], (nv, K))
        else:
            vscores = jnp.broadcast_to(
                jnp.asarray(base_score, jnp.float32).reshape(1, -1),
                (nv, K)).astype(jnp.float32)
            vscores = vscores[:, 0] if K == 1 else vscores
            if init_booster is not None and init_booster.num_trees > 0:
                vraw = init_booster.raw_scores(xv)
                vscores = jnp.asarray(vraw, jnp.float32)
    else:
        vscores = jnp.float32(0.0)  # fused-step placeholder
    metric_name = cfg.metric or _default_metric(cfg.objective)

    def make_growers(tp):
        """(grow_single, grow_multi) for the current tree params; K-class
        growth runs as ONE vmapped jitted program (review round 1 item 8,
        'fold the K-class loop') — only the variant actually used gets
        built.

        Growth always runs at learning_rate=1 (shrinkage is applied by
        the caller as an isolated multiply on the finalized leaf_value
        buffer). Inside the grower XLA fuses a constant-lr multiply with
        the adjacent leaf-output division and rounds differently — the
        post-hoc multiply on identical operands is deterministic, which
        is what keeps the cached (lr-as-argument) and closure
        (lr-as-constant) paths bit-identical."""
        kw = dict(mesh=mesh, mesh_axis=mesh_axis,
                  tp=tp._replace(learning_rate=1.0), num_features=F)
        if sparse:
            kw.update(num_bins=B_s, sparse_binned=binned)
        else:
            kw.update(dense_bins=bins)
        if K > 1:
            return None, make_grower(multi=True, **kw)
        return make_grower(multi=False, **kw), None

    grow, grow_multi = make_growers(tp)

    if grad_hess_override is not None:
        def gh_fn(s, y, w):
            return grad_hess_override(s)
    else:
        gh_fn = obj.grad_hess
    arange_k = jnp.arange(K)

    def routed_vdelta(tree_b):
        if sparse:
            vleaf = jax.vmap(lambda t: sparse_route_bins(
                t, vbinned.indices, vbinned.ebins, vbinned.zero_bin,
                max_depth=cfg.num_leaves))(tree_b)
        else:
            vleaf = jax.vmap(lambda t: tree_route_bins(
                t, vbins, max_depth=cfg.num_leaves))(tree_b)
        return tree_b.leaf_value[arange_k[:, None], vleaf]

    def grow_one(g, h, feat_mask_dev, row_mask_dev):
        """Grow this iteration's K trees in one call → ([K,...] Tree stack,
        [K, n] per-class train deltas). Growth is lr-free; shrinkage is
        the same isolated multiply the cached path applies (see
        make_growers)."""
        if K == 1:
            t1, rl1 = grow(g, h, feat_mask_dev, row_mask_dev)
            tree_b = jax.tree.map(lambda a: a[None], t1)
            row_leaf_b = rl1[None]
        else:
            tree_b, row_leaf_b = grow_multi(g.T, h.T, feat_mask_dev,
                                            row_mask_dev)
        tree_b = tree_b._replace(leaf_value=tree_b.leaf_value
                                 * jnp.float32(tp.learning_rate))
        return tree_b, tree_b.leaf_value[arange_k[:, None], row_leaf_b]

    def make_fused_step():
        """ONE jitted program for a full gbdt/goss boosting iteration:
        gradients → (GOSS mask) → tree growth → train/valid deltas →
        score updates. Eager per-op dispatch between these pieces costs a
        device round-trip each — ruinous when the device is remote — so
        gbdt/goss/rf run as a single dispatch per iteration."""
        base_arr = np.asarray(base_score, np.float32).reshape(-1)
        base_const = jnp.float32(base_arr[0]) if K == 1 \
            else jnp.asarray(base_arr)
        goss_kw = dict(
            top_n=int(cfg.top_rate * n_real),
            other_n=int(cfg.other_rate * n_real),
            amplify=(1.0 - cfg.top_rate) / max(cfg.other_rate, 1e-12)) \
            if is_goss else None

        row_select = _goss_row_select(goss_key, **goss_kw) if is_goss \
            else _identity_row_select

        def step_impl(scores, vscores, feat_mask_dev, row_mask_dev,
                      it_dev):
            return _fused_step_math(
                scores, vscores, feat_mask_dev, row_mask_dev, it_dev,
                base=base_const, gh_fn=lambda s: gh_fn(s, y_dev, w_dev),
                row_select=row_select, grow_one=grow_one,
                routed_vdelta=routed_vdelta, is_rf=is_rf, K=K,
                has_valid=valid is not None)

        step = jax.jit(step_impl)
        chunk_step = jax.jit(_chunk_scan(step_impl))
        return step, chunk_step

    # ---- device-side DART (docs/limitations.md r2 gap): per-tree train/
    # valid deltas live in fixed-shape device buffers, the drop set is a
    # host-chosen padded index vector, and the whole iteration — dropped-
    # margin reconstruction → gradients → growth → new-tree add → standing-
    # tree rescale → buffer updates — is ONE jitted dispatch, the same
    # count as gbdt's fused step (and scan-chunkable the same way). The
    # stepwise path (dart_mode="stepwise") is kept as the semantics oracle:
    # both paths consume identical host RNG draws and apply identical
    # float32 operations in identical order, so results bit-match.
    T_max = cfg.num_iterations * K
    D_drop = max(1, min(int(cfg.max_drop), T_max))

    def make_dart_step():
        def dart_impl(scores, vscores, deltas_buf, vdeltas_buf,
                      weights_buf, didx, dval, new_w, factor,
                      feat_mask_dev, row_mask_dev, it_dev):
            return _dart_step_math(
                scores, vscores, deltas_buf, vdeltas_buf, weights_buf,
                didx, dval, new_w, factor, feat_mask_dev, row_mask_dev,
                it_dev, gh_fn=lambda s: gh_fn(s, y_dev, w_dev),
                grow_one=grow_one, routed_vdelta=routed_vdelta, K=K,
                has_valid=valid is not None)

        # donate the O(T·n) buffers so each iteration updates them in
        # place (CPU lacks donation and would warn on every compile);
        # placement platform, not default backend — see _build_dart
        donate = (2, 3, 4) if target_platform() == "tpu" else ()
        step = jax.jit(dart_impl, donate_argnums=donate)
        dart_chunk = functools.partial(jax.jit, donate_argnums=donate)(
            _dart_chunk_scan(dart_impl))
        return step, dart_chunk

    def dart_host_draw():
        """One fused-dart iteration's host bookkeeping, shared by the
        chunked and per-iteration paths (the bit-match guarantee needs
        both to perform identical float32 folds in identical order):
        draw the drop set, fold k/(k+1) into the host weight mirror,
        append the new trees' class/weight entries, and return the
        fixed-shape device inputs."""
        dropped = _dart_drop_set(rng, cfg, len(tree_class))
        didx = np.zeros(D_drop, np.int32)
        dval = np.zeros(D_drop, np.float32)
        didx[:len(dropped)] = dropped
        dval[:len(dropped)] = 1.0
        new_w = np.float32(1.0 / (len(dropped) + 1)) if dropped \
            else np.float32(1.0)
        factor = np.float32(len(dropped) / (len(dropped) + 1.0)) \
            if dropped else np.float32(1.0)
        for d in dropped:
            tree_weights[d] = np.float32(tree_weights[d] * factor)
        for k_cls in range(K):
            tree_class.append(k_cls)
            tree_weights.append(new_w)
        return didx, dval, new_w, factor

    dart_fused = is_dart and cfg.dart_mode != "stepwise"
    use_fused = not is_dart  # gbdt/goss/rf single-dispatch path
    fused_step = chunk_step = None
    # cross-fit trace reuse: the common path (single-chip, built-in
    # objective, no delegate) takes jitted callables from a module-level
    # LRU keyed by statics, with per-fit arrays threaded as arguments —
    # so a CV fold / AutoML sweep / repeat fit skips retrace+recompile.
    # Delegate LR schedules mutate tp mid-loop, custom fobj/ranker
    # gradients close over user state, and mesh paths shard_map over
    # placed data: those keep the per-fit closure builder.
    trace_cacheable = (mesh is None and delegate is None
                       and grad_hess_override is None and cfg.fobj is None)
    if trace_cacheable:
        goss_kw_c = dict(
            top_n=int(cfg.top_rate * n_real),
            other_n=int(cfg.other_rate * n_real),
            amplify=(1.0 - cfg.top_rate) / max(cfg.other_rate, 1e-12)) \
            if is_goss else dict(top_n=0, other_n=0, amplify=1.0)
        st_key = _FusedStatics(
            obj_key=(cfg.objective, cfg.num_class, cfg.alpha, cfg.fair_c,
                     cfg.tweedie_variance_power, cfg.sigmoid,
                     float(pos_weight), cfg.boost_from_average),
            # lr pinned to 1.0 in the KEY: cached growth is lr-free (the
            # real rate rides fdata["lr"]), so a learning-rate sweep
            # shares one compiled step
            tp=tp._replace(learning_rate=1.0), boosting=cfg.boosting_type,
            K=K, n=n, F=F,
            sparse=sparse, num_bins=(B_s if sparse else 0),
            has_valid=valid is not None, **goss_kw_c)
        base_arr_c = np.asarray(base_score, np.float32).reshape(-1)
        fdata = {"y": y_dev, "w": w_dev, "gkey": goss_key,
                 "lr": jnp.float32(tp.learning_rate),
                 "base": jnp.float32(base_arr_c[0]) if K == 1
                 else jnp.asarray(base_arr_c)}
        if sparse:
            fdata.update(si=binned.indices, se=binned.ebins,
                         sz=binned.zero_bin)
        else:
            fdata["bins"] = bins
        if valid is not None:
            if sparse:
                fdata.update(vi=vbinned.indices, ve=vbinned.ebins,
                             vz=vbinned.zero_bin)
            else:
                fdata["vb"] = vbins
    if use_fused and trace_cacheable:
        raw_step, raw_chunk = _fused_cached(st_key)

        def fused_step(s, vs, fm, rm, it):
            return raw_step(fdata, s, vs, fm, rm, it)

        def chunk_step(s, vs, fms, rms, its):
            return raw_chunk(fdata, s, vs, fms, rms, its)
    elif use_fused:
        fused_step, chunk_step = make_fused_step()
    dart_step = dart_chunk_step = None
    if dart_fused:
        if trace_cacheable:
            raw_dstep, raw_dchunk = _fused_cached(st_key)

            def dart_step(*args):
                return raw_dstep(fdata, *args)

            def dart_chunk_step(*args):
                return raw_dchunk(fdata, *args)
        else:
            dart_step, dart_chunk_step = make_dart_step()
        deltas_buf = jnp.zeros((T_max, n), jnp.float32)
        vdeltas_buf = jnp.zeros((T_max, nv), jnp.float32) \
            if valid is not None else jnp.zeros((T_max, 1), jnp.float32)
        weights_buf = jnp.ones(T_max, jnp.float32)

    # ---- observability (obs subsystem): the boosting loop is a span
    # tree (lightgbm.fit → boosting_round) in the JSON telemetry sink,
    # and every round's host wall time (dispatch + any blocking eval
    # sync — what the old private stopwatches measured) lands in the
    # process-wide per-round histogram. Spans are non-current with
    # explicit parentage: a loop body with breaks must not own ambient
    # context.
    _round_hist = _obs.histogram(
        "lightgbm_boosting_round_seconds",
        "host wall seconds per boosting round (chunked rounds record "
        "one sample per scan chunk), by dispatch mode")
    _round_mode = "stepwise" if (is_dart and not dart_fused) else "fused"
    _fit_span = _tracer.start_span(
        "lightgbm.fit", current=False, objective=cfg.objective,
        boosting=cfg.boosting_type, iterations=cfg.num_iterations,
        rows=n_real, features=F)

    # ---- chunked fast path: scan cfg.scan_chunk iterations per dispatch
    # when NOTHING observes per-iteration state — no eval/early stopping
    # (no valid set, no training metric) and no delegate hooks. The host
    # RNG calls (feature/bagging masks) happen in the same order as the
    # per-iteration loop, so chunked and unchunked runs are identical.
    chunk = max(int(cfg.scan_chunk), 1)
    if ((use_fused or dart_fused) and chunk > 1 and delegate is None
            and valid is None and not cfg.is_provide_training_metric):
        it = 0
        # only FULL chunks run through chunk_step: a partial tail would
        # retrace/recompile the whole scan program for its odd shape,
        # costing more than the dispatches it saves — the remainder runs
        # on the per-iteration fused step instead
        full_iters = (cfg.num_iterations // chunk) * chunk
        nf = max(1, int(round(cfg.feature_fraction * F)))
        while it < full_iters:
            k = chunk
            fms = np.ones((k, F), bool)
            didxs = np.zeros((k, D_drop), np.int32)
            dvals = np.zeros((k, D_drop), np.float32)
            new_ws = np.ones(k, np.float32)
            factors = np.ones(k, np.float32)
            for j in range(k):
                # host RNG draws in the per-iteration loop's order: the
                # drop set (dart) then the feature mask, both from `rng`.
                # The host weight-mirror fold happens inside the draw, so
                # iteration j can drop a tree iteration j-1 just added.
                if dart_fused:
                    (didxs[j], dvals[j], new_ws[j],
                     factors[j]) = dart_host_draw()
                if cfg.feature_fraction < 1.0:
                    fms[j] = False
                    fms[j, rng.choice(F, size=nf, replace=False)] = True
            if is_goss:
                rms = jnp.broadcast_to(valid_mask_dev, (k, n))
            elif (is_rf or cfg.bagging_freq > 0) and bagging_active:
                rms_np = np.empty((k, n), np.float32)
                for j in range(k):
                    if is_rf or (it + j) % max(cfg.bagging_freq, 1) == 0:
                        bag_mask = draw_bag()
                    rms_np[j] = bag_mask * valid_mask_np
                rms = jnp.asarray(rms_np)
            else:
                rms = jnp.broadcast_to(valid_mask_dev, (k, n))
            its = jnp.asarray(
                np.arange(it, it + k, dtype=np.int32))
            _chunk_span = _tracer.start_span(
                "boosting_round", parent=_fit_span, current=False,
                iteration=it, iterations=k, mode="chunked")
            if dart_fused:
                (scores, vscores, deltas_buf, vdeltas_buf, weights_buf,
                 tree_stack) = dart_chunk_step(
                    scores, vscores, deltas_buf, vdeltas_buf, weights_buf,
                    jnp.asarray(fms), rms, its, jnp.asarray(didxs),
                    jnp.asarray(dvals), jnp.asarray(new_ws),
                    jnp.asarray(factors))
                trees.append(tree_stack)  # host lists updated in j-loop
            else:
                scores, vscores, tree_stack = chunk_step(
                    scores, vscores, jnp.asarray(fms), rms, its)
                trees.append(tree_stack)      # leaves [k, K, ...]
                for _ in range(k):
                    for k_cls in range(K):
                        tree_class.append(k_cls)
                        tree_weights.append(1.0)
            _round_hist.observe(_tracer.end_span(_chunk_span).seconds,
                                mode="chunked")
            it += k
        iter_range = range(full_iters, cfg.num_iterations)
    else:
        iter_range = range(cfg.num_iterations)

    for it in iter_range:
        _round_span = _tracer.start_span(
            "boosting_round", parent=_fit_span, current=False,
            iteration=it, mode=_round_mode)
        if delegate is not None:
            # rf averages unshrunk trees (tree_params forces lr=1); a
            # delegate LR schedule must not silently re-shrink them
            lr = None if is_rf else delegate.get_learning_rate(it)
            if lr is not None and lr != tp.learning_rate:
                tp = tp._replace(learning_rate=float(lr))
                # growers are lr-free (make_growers pins lr=1), so only
                # the step closures — which bake the shrinkage constant —
                # need rebuilding on an LR-schedule change
                if use_fused:
                    fused_step, chunk_step = make_fused_step()
                if dart_fused:
                    dart_step, dart_chunk_step = make_dart_step()
            delegate.before_train_iteration(it)

        # ---- dart: drop trees for gradient computation (DART
        # normalization: k dropped trees rescale by k/(k+1), the new tree
        # enters at 1/(k+1))
        new_tree_weight = np.float32(1.0)
        dropped: list[int] = []
        eff_scores = scores
        dart_inputs = None
        if dart_fused:
            dart_inputs = dart_host_draw()
        elif is_dart:
            dropped = _dart_drop_set(rng, cfg, len(tree_class))
            if dropped:
                new_tree_weight = np.float32(1.0 / (len(dropped) + 1))
            for d in dropped:
                eff_scores = _apply_weighted(
                    eff_scores, tree_deltas[d],
                    np.float32(-tree_weights[d]),
                    np.int32(tree_class[d]))

        # ---- feature sampling
        feat_mask = np.ones(F, bool)
        if cfg.feature_fraction < 1.0:
            k = max(1, int(round(cfg.feature_fraction * F)))
            feat_mask = np.zeros(F, bool)
            feat_mask[rng.choice(F, size=k, replace=False)] = True

        feat_mask_dev = jnp.asarray(feat_mask)

        if dart_fused:
            # ---- fused dart iteration: ONE device dispatch, like gbdt's
            if cfg.bagging_freq > 0 and bagging_active:
                if it % max(cfg.bagging_freq, 1) == 0:
                    bag_mask = draw_bag()
                row_mask_dev = jnp.asarray(bag_mask) * valid_mask_dev
            else:
                row_mask_dev = valid_mask_dev
            didx, dval, new_w, factor = dart_inputs
            (scores, vscores, deltas_buf, vdeltas_buf, weights_buf,
             tree_b) = dart_step(
                scores, vscores, deltas_buf, vdeltas_buf, weights_buf,
                jnp.asarray(didx), jnp.asarray(dval), new_w,
                factor, feat_mask_dev, row_mask_dev, np.int32(it))
            trees.append(tree_b)  # host mirror updated by dart_host_draw
        elif fused_step is not None:
            # ---- fused gbdt/goss iteration: ONE device dispatch for
            # gradients + sampling + growth + deltas + score updates
            if is_goss:
                row_in = valid_mask_dev
            elif (is_rf or cfg.bagging_freq > 0) and bagging_active:
                if is_rf or it % max(cfg.bagging_freq, 1) == 0:
                    bag_mask = draw_bag()
                row_in = jnp.asarray(bag_mask * valid_mask_np)
            else:
                row_in = valid_mask_dev
            scores, vscores, tree_b = fused_step(
                scores, vscores, feat_mask_dev, row_in, np.int32(it))
            trees.append(tree_b)
            for k_cls in range(K):
                tree_class.append(k_cls)
                tree_weights.append(1.0)
        else:
            # ---- stepwise path: dart only (gbdt/goss/rf run fused).
            # Gradients at the dropped-tree margin chosen host-side.
            if grad_hess_override is not None:
                g, h = grad_hess_override(eff_scores)
            else:
                g, h = obj.grad_hess(eff_scores, y_dev, w_dev)

            # row sampling (padded rows always excluded: SPMD "ignore")
            if cfg.bagging_freq > 0 and bagging_active:
                if it % max(cfg.bagging_freq, 1) == 0:
                    bag_mask = draw_bag()
                row_mask_dev = jnp.asarray(bag_mask) * valid_mask_dev
            else:
                row_mask_dev = valid_mask_dev

            # grow this iteration's trees: K classes in ONE jitted call,
            # shrinkage applied inside grow_one (the shared site)
            tree_b, delta_b = grow_one(g, h, feat_mask_dev, row_mask_dev)
            vdelta_b = None
            if valid is not None:
                if sparse:
                    vleaf_b = jax.vmap(
                        lambda t: sparse_route_bins(
                            t, vbinned.indices, vbinned.ebins,
                            vbinned.zero_bin, max_depth=cfg.num_leaves))(
                                tree_b)
                else:
                    vleaf_b = jax.vmap(
                        lambda t: tree_route_bins(
                            t, vbins, max_depth=cfg.num_leaves))(tree_b)
                vdelta_b = tree_b.leaf_value[jnp.arange(K)[:, None],
                                             vleaf_b]
            # Trees stay ON DEVICE during the loop: a per-iteration host
            # pull is ~10 synchronous transfers, which serializes the
            # dispatch pipeline. One batched pull happens after the loop.
            trees.append(tree_b)
            for k_cls in range(K):
                delta = delta_b[k_cls]
                tree_class.append(k_cls)
                tree_weights.append(new_tree_weight)
                vdelta = None if vdelta_b is None else vdelta_b[k_cls]
                tree_deltas.append(delta)
                tree_vdeltas.append(vdelta)
                scores = _apply_weighted(scores, delta, new_tree_weight,
                                         np.int32(k_cls))
                if valid is not None:
                    vscores = _apply_weighted(vscores, vdelta,
                                              new_tree_weight,
                                              np.int32(k_cls))

        if is_dart and not dart_fused and dropped:
            # rescale dropped trees' standing contribution by k/(k+1)
            factor = np.float32(len(dropped) / (len(dropped) + 1.0))
            for d in dropped:
                coeff = np.float32(tree_weights[d]
                                   * (factor - np.float32(1.0)))
                scores = _apply_weighted(scores, tree_deltas[d], coeff,
                                         np.int32(tree_class[d]))
                if valid is not None and tree_vdeltas[d] is not None:
                    vscores = _apply_weighted(vscores, tree_vdeltas[d],
                                              coeff,
                                              np.int32(tree_class[d]))
                tree_weights[d] = np.float32(tree_weights[d] * factor)

        # ---- eval + early stopping (configurable cadence: eval_freq > 1
        # skips the device sync entirely on off iterations)
        do_eval = ((it + 1) % eval_freq == 0
                   or it == cfg.num_iterations - 1)
        if cfg.is_provide_training_metric and do_eval:
            train_metric = metric_name if metric_name != "ndcg" else "rmse"
            md = _eval_metric_device(
                train_metric, scores[:n_real], y_dev[:n_real],
                w_dev[:n_real], cfg)
            if md is not None:
                tm, pulls_scalar = float(md), pulls_scalar + 1
            else:
                pulls_bulk += 1
                tm = eval_metric(train_metric, np.asarray(scores)[:n_real],
                                 np.asarray(y)[:n_real], w_np[:n_real], cfg)
            evals.append({"iteration": it, "dataset": "train",
                          train_metric: tm})
        if valid is not None and do_eval:
            if valid_eval_fn is not None:
                pulls_bulk += 1
                m = valid_eval_fn(np.asarray(vscores), np.asarray(yv),
                                  None if wv is None else np.asarray(wv))
            else:
                md = _eval_metric_device(metric_name, vscores, yv_dev,
                                         wv_dev, cfg)
                if md is not None:
                    m, pulls_scalar = float(md), pulls_scalar + 1
                else:
                    pulls_bulk += 1
                    m = eval_metric(metric_name, np.asarray(vscores),
                                    np.asarray(yv),
                                    None if wv is None else np.asarray(wv),
                                    cfg)
            evals.append({"iteration": it, metric_name: m})
            tol = cfg.improvement_tolerance
            better = (best_metric is None
                      or (m > best_metric + tol
                          if _higher_better(metric_name)
                          else m < best_metric - tol))
            if better:
                best_metric, best_iter, rounds_no_improve = m, it, 0
            else:
                rounds_no_improve += 1
            if (cfg.early_stopping_round > 0
                    and rounds_no_improve >= cfg.early_stopping_round):
                _round_hist.observe(
                    _tracer.end_span(_round_span).seconds, mode=_round_mode)
                break
        if delegate is not None:
            delegate.after_train_iteration(it)
        _round_hist.observe(_tracer.end_span(_round_span).seconds,
                            mode=_round_mode)

    if trees:
        # trees holds [K, ...] stacks (one per iteration) and/or
        # [chunk, K, ...] stacks (one per scanned chunk). ONE batched
        # device→host pull for everything: device_get prefetches every
        # leaf asynchronously before blocking, so this costs ~one
        # round-trip rather than iterations × fields. (An eager
        # jnp.stack here would also re-enter the compiler per field —
        # and crashes on shard_map-produced leaves on CPU meshes.)
        host_stacks = jax.device_get(trees)
        flat = []
        for stack in host_stacks:
            if np.ndim(stack.num_nodes) == 1:      # [K, ...]
                flat.extend(jax.tree.map(lambda a, k=k: a[k], stack)
                            for k in range(K))
            else:                                  # [chunk, K, ...]
                for t in range(stack.num_nodes.shape[0]):
                    flat.extend(
                        jax.tree.map(lambda a, t=t, k=k: a[t, k], stack)
                        for k in range(K))
        trees = flat
    booster = build_booster(trees, boundaries, cfg, base_score,
                            feature_names, np.asarray(tree_weights,
                                                      np.float32),
                            average_output=is_rf)
    prior_iters = 0
    if init_booster is not None and init_booster.num_trees > 0:
        from .booster import merge_boosters
        booster = merge_boosters(init_booster, booster)
        prior_iters = init_booster.num_trees // max(K, 1)
    if best_iter >= 0:
        booster.best_iteration = best_iter + prior_iters
    if _debug_capture is not None:
        _debug_capture["scores"] = np.asarray(scores)
        if dart_fused:
            _debug_capture["dart_deltas"] = np.asarray(deltas_buf)
            _debug_capture["dart_weights"] = np.asarray(weights_buf)
        elif is_dart:
            _debug_capture["dart_deltas"] = np.asarray(
                jax.device_get(tree_deltas))
            _debug_capture["dart_weights"] = np.asarray(tree_weights)
    # span ends only on the success path: an exception mid-fit drops the
    # (non-current) span unemitted, which cannot corrupt ambient context
    _fit_span.set_attr("trees", len(trees))
    _fit_span.set_attr("best_iteration", best_iter)
    _tracer.end_span(_fit_span)
    return TrainResult(booster=booster, evals=evals, best_iteration=best_iter,
                       host_pulls_bulk=pulls_bulk,
                       host_pulls_scalar=pulls_scalar)


def build_booster(trees: list[Tree], boundaries: np.ndarray,
                  cfg: TrainConfig, base_score, feature_names,
                  tree_weights: np.ndarray | None = None,
                  average_output: bool = False) -> Booster:
    T = len(trees)
    NN = 2 * cfg.num_leaves - 1
    arr = {k: np.zeros((T, NN), dt) for k, dt in [
        ("feature", np.int32), ("threshold", np.float32),
        ("left", np.int32), ("right", np.int32),
        ("leaf_value", np.float32), ("is_leaf", bool),
        ("split_gain", np.float32), ("node_weight", np.float32),
        ("node_count", np.float32), ("node_value", np.float32)]}
    arr["num_nodes"] = np.zeros(T, np.int32)
    if cfg.categorical_features:
        # the engine's bin width, not cfg.max_bin: the sparse path bins
        # into sparse_max_bin-sized histograms
        B = int(trees[0].cat_left.shape[-1]) if trees else cfg.max_bin + 1
        arr["cat_flag"] = np.zeros((T, NN), bool)
        arr["cat_left"] = np.zeros((T, NN, B), bool)
    for t, tree in enumerate(trees):
        arr["feature"][t] = tree.feature
        arr["left"][t] = tree.left
        arr["right"][t] = tree.right
        arr["leaf_value"][t] = tree.leaf_value
        arr["is_leaf"][t] = tree.is_leaf
        arr["split_gain"][t] = tree.split_gain
        arr["node_weight"][t] = tree.node_weight
        arr["node_count"][t] = tree.node_count
        arr["node_value"][t] = tree.node_value
        arr["num_nodes"][t] = tree.num_nodes
        if cfg.categorical_features:
            arr["cat_flag"][t] = tree.cat_flag
            arr["cat_left"][t] = tree.cat_left
        for i in range(int(tree.num_nodes)):
            if not tree.is_leaf[i] and tree.left[i] >= 0 \
                    and not (cfg.categorical_features
                             and tree.cat_flag[i]):
                arr["threshold"][t, i] = bin_upper_value(
                    boundaries, int(tree.feature[i]),
                    int(tree.split_bin[i]))
    return Booster(arr, num_class=cfg.num_class, objective=cfg.objective,
                   sigmoid=cfg.sigmoid, init_score=base_score,
                   feature_names=feature_names,
                   max_depth_bound=cfg.num_leaves,
                   tree_weights=tree_weights, average_output=average_output)


# --------------------------------------------------------------- eval metrics
@jax.jit
def _rmse_dev(s, y, w):
    return jnp.sqrt(jnp.average((s - y) ** 2, weights=w))


@jax.jit
def _mae_dev(s, y, w):
    return jnp.average(jnp.abs(s - y), weights=w)


@jax.jit
def _auc_dev(s, y, w):
    order = jnp.argsort(s)
    y_s, w_s = y[order], w[order]
    pos = w_s * (y_s > 0)
    neg = w_s * (y_s <= 0)
    cum_neg = jnp.cumsum(neg)
    auc_sum = jnp.sum(pos * (cum_neg - 0.5 * neg))
    total = pos.sum() * neg.sum()
    return jnp.where(total > 0, auc_sum / total, 0.5)


@functools.partial(jax.jit, static_argnames=("sigmoid",))
def _binary_logloss_dev(s, y, w, *, sigmoid):
    p = jnp.clip(jax.nn.sigmoid(sigmoid * s), 1e-15, 1 - 1e-15)
    return -jnp.average(y * jnp.log(p) + (1 - y) * jnp.log1p(-p), weights=w)


@jax.jit
def _multi_logloss_dev(s, y, w):
    logp = jax.nn.log_softmax(s, axis=1)
    py = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], axis=1)[:, 0]
    return -jnp.average(py, weights=w)


@functools.partial(jax.jit, static_argnames=("sigmoid",))
def _ova_logloss_dev(s, y, w, *, sigmoid):
    """Mean per-class binary logloss with one-hot labels — the logloss
    the multiclassova objective optimizes."""
    K = s.shape[1]
    onehot = jax.nn.one_hot(y.astype(jnp.int32), K)
    p = jnp.clip(jax.nn.sigmoid(sigmoid * s), 1e-15, 1 - 1e-15)
    ll = onehot * jnp.log(p) + (1 - onehot) * jnp.log1p(-p)
    return -jnp.average(ll.sum(axis=1), weights=w)


@jax.jit
def _xentlambda_loss_dev(s, y, w):
    lam = jnp.logaddexp(0.0, s)
    p = jnp.clip(1.0 - jnp.exp(-lam), 1e-15, 1 - 1e-15)
    return -jnp.average(y * jnp.log(p) + (1 - y) * jnp.log1p(-p),
                        weights=w)


def _eval_metric_device(name: str, scores, y, w, cfg: TrainConfig):
    """Metric computed ON DEVICE where supported; only the scalar crosses
    to host (review round 1 weak #5: per-iteration np.asarray(scores) pulls).
    Returns None for metrics with no device implementation."""
    if name == "rmse":
        return _rmse_dev(scores, y, w)
    if name == "mae":
        return _mae_dev(scores, y, w)
    if name == "auc":
        return _auc_dev(scores, y, w)
    if name == "binary_logloss":
        return _binary_logloss_dev(scores, y, w, sigmoid=cfg.sigmoid)
    if name == "multi_logloss":
        return _multi_logloss_dev(scores, y, w)
    if name == "ova_logloss":
        return _ova_logloss_dev(scores, y, w, sigmoid=cfg.sigmoid)
    if name == "xentlambda_loss":
        return _xentlambda_loss_dev(scores, y, w)
    return None


def _default_metric(objective: str) -> str:
    return {"binary": "auc", "multiclass": "multi_logloss",
            "softmax": "multi_logloss",
            "multiclassova": "ova_logloss",
            "cross_entropy": "binary_logloss",
            "cross_entropy_lambda": "xentlambda_loss",
            "lambdarank": "ndcg",
            "regression_l1": "mae"}.get(objective, "rmse")


def _higher_better(metric: str) -> bool:
    return metric in ("auc", "ndcg", "map", "accuracy")


def eval_metric(name: str, raw_scores: np.ndarray, y: np.ndarray,
                w: np.ndarray | None, cfg: TrainConfig) -> float:
    w = np.ones(len(y)) if w is None else w
    if name == "rmse":
        return float(np.sqrt(np.average((raw_scores - y) ** 2, weights=w)))
    if name == "mae":
        return float(np.average(np.abs(raw_scores - y), weights=w))
    if name == "auc":
        p = raw_scores
        return roc_auc(y, p, w)
    if name == "binary_logloss":
        p = stable_sigmoid(cfg.sigmoid * raw_scores)
        p = np.clip(p, 1e-15, 1 - 1e-15)
        return float(-np.average(y * np.log(p) + (1 - y) * np.log(1 - p),
                                 weights=w))
    if name == "multi_logloss":
        e = np.exp(raw_scores - raw_scores.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        py = np.clip(p[np.arange(len(y)), y.astype(int)], 1e-15, None)
        return float(-np.average(np.log(py), weights=w))
    if name.startswith("ndcg"):
        raise ValueError(
            "ndcg requires group information; the ranker supplies a "
            "group-aware valid_eval_fn")
    raise ValueError(f"unknown metric {name!r}")


def roc_auc(y: np.ndarray, score: np.ndarray,
            w: np.ndarray | None = None) -> float:
    """Weighted ROC AUC via the rank formulation (no sklearn dependency in
    the hot path)."""
    w = np.ones(len(y)) if w is None else w
    order = np.argsort(score, kind="mergesort")
    y_s, w_s = y[order], w[order]
    pos = w_s * (y_s > 0)
    neg = w_s * (y_s <= 0)
    cum_neg = np.cumsum(neg)
    auc_sum = np.sum(pos * (cum_neg - 0.5 * neg))
    total = pos.sum() * neg.sum()
    return float(auc_sum / total) if total > 0 else 0.5
