"""Histogram-GBDT training engine: jitted leaf-wise tree growth in XLA.

This replaces the reference's native LightGBM core (histogram construction,
split finding, tree growth — reached through ``LGBM_BoosterUpdateOneIter`` at
``lightgbm/TrainUtils.scala:326-358``) with a TPU-first formulation:

- binned features are uint8 (``binning.py``), so the histogram build is one
  big scatter-add of (grad, hess, count) into a fixed [leaves, F, bins, 3]
  tensor — no sorting, no data-dependent shapes;
- split finding is a vectorized cumulative-sum + argmax over that tensor for
  ALL current leaves at once, which makes best-first (leaf-wise) growth the
  natural formulation rather than a queue of per-leaf jobs;
- the whole tree grows inside one ``lax.fori_loop`` with fixed trip count
  (num_leaves - 1) and fixed-capacity arrays; "no split found" degenerates to
  masked no-ops (the SPMD answer to the reference's empty-partition ``ignore``
  protocol);
- rows carry a compact leaf *slot* id in [0, num_leaves) so histogram memory
  stays O(num_leaves · F · bins) — the slot→node indirection mirrors
  LightGBM's data_partition, but as dense int32 arrays.

Distributed training (SURVEY §2.13): the only cross-device exchange GBDT
needs is histogram information. ``grow_tree`` takes a ``psum_axis``; when
run under ``shard_map`` with rows sharded over that axis, the histogram
reduction IS the reference's ``LGBM_NetworkInit`` + socket allreduce
(``TrainUtils.scala:609-625``), riding ICI instead of TCP. Two modes match
the reference's ``parallelism`` selector (``params/LightGBMParams.scala:16-21``,
``LightGBMConstants.scala:24-26``):

- ``data`` (data_parallel): the full [F, B, 3] histogram of each new leaf
  is ``psum``-reduced;
- ``voting`` (voting_parallel, PV-Tree): each shard nominates its local
  top-K features per new leaf, votes are ``psum``-merged, and only the
  global top-2K candidate feature columns ([2K, B, 3]) are reduced — the
  histogram state itself stays shard-local. Per split this exchanges
  ``comm_elements_per_split`` elements, a large reduction for wide
  feature spaces (the regime the reference reserves voting for).

SPMD-safety invariant: every collective (the histogram psum, the vote
psum, the candidate-column psum) executes UNCONDITIONALLY on every
``fori_loop`` iteration, outside any data-dependent ``lax.cond`` — when no
split applies the inputs are zero-masked instead of skipped. A collective
under a data-dependent branch is one refactor away from a cross-shard
deadlock; this engine keeps the lockstep property by construction.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class TreeParams(NamedTuple):
    """Static growth hyperparameters (compiled into the kernel)."""
    num_leaves: int = 31
    max_depth: int = -1          # <= 0 means unlimited (bounded by leaves)
    max_bin: int = 255
    learning_rate: float = 0.1
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    parallelism: str = "data"    # data | voting (PV-Tree top-K)
    top_k: int = 20              # voting: local nominations per shard
    cat_features: tuple = ()     # feature indices with set-based splits
    cat_smooth: float = 10.0     # hessian smoothing in the g/h cat sort
    max_cat_threshold: int = 32  # max categories in a split's left set
    max_delta_step: float = 0.0  # cap on leaf outputs (0 = off)


class Tree(NamedTuple):
    """Fixed-capacity tree arrays; node ids are append-ordered."""
    feature: jnp.ndarray      # i32 [NN] split feature (internal nodes)
    split_bin: jnp.ndarray    # i32 [NN] go left iff bin <= split_bin
                              #   (categorical: rank(bin) <= split_bin)
    cat_flag: jnp.ndarray     # bool [NN] node splits on a category set
    cat_left: jnp.ndarray     # bool [NN, B] bin ids routed left
    left: jnp.ndarray         # i32 [NN]
    right: jnp.ndarray        # i32 [NN]
    leaf_value: jnp.ndarray   # f32 [NN] (already shrunk by learning_rate)
    is_leaf: jnp.ndarray      # bool [NN]
    split_gain: jnp.ndarray   # f32 [NN]
    node_value: jnp.ndarray   # f32 [NN] unshrunk output at node (internal_value)
    node_weight: jnp.ndarray  # f32 [NN] sum of hessians under node
    node_count: jnp.ndarray   # f32 [NN] row count under node
    num_nodes: jnp.ndarray    # i32 scalar


def _thresh_l1(g, l1):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def _leaf_output(g, h, p: TreeParams):
    out = -_thresh_l1(g, p.lambda_l1) / (h + p.lambda_l2 + 1e-35)
    if p.max_delta_step > 0:
        # LightGBM max_delta_step: cap the leaf output magnitude (the
        # stabilizer for extreme-gradient objectives like poisson)
        out = jnp.clip(out, -p.max_delta_step, p.max_delta_step)
    return out


def _leaf_gain(g, h, p: TreeParams):
    t = _thresh_l1(g, p.lambda_l1)
    if p.max_delta_step > 0:
        # gain at the CLIPPED output (LightGBM's
        # GetLeafSplitGainGivenOutput) — the unconstrained t²/(h+λ)
        # would overstate splits whose outputs the cap then truncates
        o = _leaf_output(g, h, p)
        return -(2.0 * t * o + (h + p.lambda_l2) * o * o)
    return t * t / (h + p.lambda_l2 + 1e-35)


def comm_elements_per_split(num_features: int, num_bins: int,
                            top_k: int, parallelism: str) -> int:
    """Histogram elements exchanged over the mesh per split (per shard).

    data_parallel reduces the new leaf's full histogram; voting_parallel
    reduces one vote row plus 2K candidate columns for each of the two
    children (PV-Tree). This is the quantity the distributed test asserts
    shrinks under voting.
    """
    if parallelism == "voting":
        cand = min(2 * top_k, num_features)
        return 2 * (num_features + cand * num_bins * 3)
    return num_features * num_bins * 3


def _split_stats(hist, p: TreeParams):
    """[..., B, 3] histogram(s) → per-bin split stats.

    Returns (gl, hl, cl, gr, hr, cr, gain), each [..., B]: left stats are
    cumulative (split = "bin <= b goes left"), right = totals - left.
    """
    cum = jnp.cumsum(hist, axis=-2)
    gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
    tot = cum[..., -1:, :]
    gr = tot[..., 0] - gl
    hr = tot[..., 1] - hl
    cr = tot[..., 2] - cl
    gain = (_leaf_gain(gl, hl, p) + _leaf_gain(gr, hr, p)
            - _leaf_gain(tot[..., 0], tot[..., 1], p))
    return gl, hl, cl, gr, hr, cr, gain


def _split_stats_with_cat(hist, p: TreeParams, *, cat_idx=None,
                          cat_mask=None):
    """``_split_stats`` with categorical columns re-scanned in
    gradient/hessian-ratio-sorted order (LightGBM's many-vs-many
    heuristic): position b then means "the b+1 best-ratio categories go
    left". The ONE copy of the sort + merge used by split search AND
    voting nomination in both engines — a nomination path scoring
    categorical columns with the ordinal scan would systematically
    under-vote them.

    Exactly one of ``cat_idx`` (static feature columns to gather, for
    full-width [..., F, B, 3] layouts) or ``cat_mask`` (per-column bool,
    for per-leaf candidate layouts where columns vary) may be given;
    both ``None`` → plain stats. Returns ``(stats7, order)`` where
    ``order`` is the ratio argsort ([..., Fc|C, B]) or ``None``.
    """
    stats = _split_stats(hist, p)
    if cat_idx is None and cat_mask is None:
        return stats, None
    cat_hist = hist if cat_idx is None else hist[..., cat_idx, :, :]
    ratio = jnp.where(
        cat_hist[..., 2] > 0,
        cat_hist[..., 0] / (cat_hist[..., 1] + p.cat_smooth),
        jnp.inf)                          # empty bins sort last
    # the missing bin (0) must never enter a left set: predict and SHAP
    # send missing right unconditionally (LightGBM's "NaN is in no
    # bitset"), so training must match
    ratio = ratio.at[..., 0].set(jnp.inf)
    order = jnp.argsort(ratio, axis=-1)
    sorted_hist = jnp.take_along_axis(cat_hist, order[..., None],
                                      axis=-2)
    cs = _split_stats(sorted_hist, p)
    # sorted position b means "b+1 categories go left": LightGBM's
    # max_cat_threshold caps the left-set size
    B = cat_hist.shape[-2]
    cap = jnp.arange(B) < p.max_cat_threshold
    cs = cs[:6] + (jnp.where(cap, cs[6], -jnp.inf),)
    if cat_mask is not None:
        m = cat_mask[..., None]
        stats = tuple(jnp.where(m, c, s) for s, c in zip(stats, cs))
    else:
        stats = tuple(s.at[..., cat_idx, :].set(c)
                      for s, c in zip(stats, cs))
    return stats, order


def categorical_go_left(xv, missing, cat_left_rows):
    """Raw-value category routing, shared by the dense and COO
    predictors (one copy of the bitset rule): value c lives in bin c+1
    (identity binning); missing, negative, non-integer or out-of-range
    values are "in no bitset" and go right — LightGBM's NaN/unseen rule.

    cat_left_rows: bool [..., B], the cat_left row of each (row, node).
    """
    B = cat_left_rows.shape[-1]
    iv = jnp.nan_to_num(xv).astype(jnp.int32)
    in_range = (~missing) & (xv >= 0) & (iv < B - 1) \
        & (xv == iv.astype(xv.dtype))
    cat_bin = jnp.clip(iv + 1, 0, B - 1)
    picked = jnp.take_along_axis(cat_left_rows, cat_bin[..., None],
                                 axis=-1)[..., 0]
    return picked & in_range


@functools.partial(
    jax.jit,
    static_argnames=("params", "num_features", "psum_axis"))
def grow_tree(bins: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
              feature_mask: jnp.ndarray, row_mask: jnp.ndarray,
              *, params: TreeParams, num_features: int,
              psum_axis: str | None = None):
    """Grow one tree. Returns (Tree, per-row leaf node id).

    bins: uint8 [n, F]; grad/hess: f32 [n]; feature_mask: bool [F]
    (feature_fraction sampling); row_mask: f32 [n] (bagging/GOSS weights,
    0 = row excluded). All shapes static.
    """
    p = params
    n, F = bins.shape
    assert F == num_features
    L = p.num_leaves
    NN = 2 * L - 1
    B = p.max_bin + 1  # bin 0 = missing
    max_depth = p.max_depth if p.max_depth and p.max_depth > 0 else 10 ** 9
    voting = p.parallelism == "voting" and psum_axis is not None
    C = min(2 * p.top_k, F)  # global candidate features per leaf (voting)
    has_cat = len(p.cat_features) > 0
    if has_cat:
        # sorted order is load-bearing: the apply phase maps f_star back
        # to its compact column via searchsorted
        cat_features = tuple(sorted(set(p.cat_features)))
        cat_feat_mask = jnp.zeros(F, bool).at[
            jnp.asarray(cat_features, jnp.int32)].set(True)

    g = grad * row_mask
    h = hess * row_mask
    cnt_w = row_mask  # counts honour the bagging mask

    def psum(x):
        # routed through parallel.collectives so every histogram/vote
        # reduction records parallel_collective_bytes_total{op,axis}
        # (trace-time) beside the rest of the sharding engine's series
        if psum_axis is None:
            return x
        from ..parallel.collectives import allreduce
        return allreduce(x, psum_axis)

    # ---- root
    total_g, total_h, total_c = (psum(g.sum()), psum(h.sum()),
                                 psum(cnt_w.sum()))

    tree = Tree(
        feature=jnp.zeros(NN, jnp.int32),
        split_bin=jnp.full(NN, B, jnp.int32),
        cat_flag=jnp.zeros(NN, bool),
        cat_left=jnp.zeros((NN, B), bool),
        left=jnp.full(NN, -1, jnp.int32),
        right=jnp.full(NN, -1, jnp.int32),
        leaf_value=jnp.zeros(NN, jnp.float32).at[0].set(
            p.learning_rate * _leaf_output(total_g, total_h, p)),
        is_leaf=jnp.zeros(NN, bool).at[0].set(True),
        split_gain=jnp.zeros(NN, jnp.float32),
        node_value=jnp.zeros(NN, jnp.float32).at[0].set(
            _leaf_output(total_g, total_h, p)),
        node_weight=jnp.zeros(NN, jnp.float32).at[0].set(total_h),
        node_count=jnp.zeros(NN, jnp.float32).at[0].set(total_c),
        num_nodes=jnp.int32(1),
    )

    feat_offsets = (jnp.arange(F, dtype=jnp.int32) * B)[None, :]  # [1, F]
    gh1 = jnp.stack([g, h, cnt_w], axis=1)  # [n, 3]
    bin_idx = feat_offsets + bins.astype(jnp.int32)        # [n, F]

    from .pallas_hist import hist_pallas, use_pallas_hist
    pallas_ok = use_pallas_hist()

    def local_hist(row_sel, full: bool = False):
        """SHARD-LOCAL histogram of one row subset → [F, B, 3]: the
        LightGBM single-leaf ConstructHistogram. On TPU this is the Pallas
        one-hot MXU kernel (a masked full-row scan: at v5e speeds the
        kernel is DMA-bound, so row compaction via nonzero/gather costs
        ~1000x more than the scan it would save); elsewhere one
        scatter-add over [F*B] keys. Callers psum (or vote-and-gather)
        the result as the mode demands — never this function, so it can
        run under ``lax.cond`` safely."""
        masked = gh1 if full else gh1 * row_sel[:, None]
        if pallas_ok:
            return hist_pallas(bins, masked, num_bins=B)
        vals = jnp.broadcast_to(masked[:, None, :], (n, F, 3))
        hist = jnp.zeros((F * B, 3), jnp.float32)
        hist = hist.at[bin_idx.reshape(-1)].add(vals.reshape(-1, 3))
        return hist.reshape(F, B, 3)

    def local_top_features(hists):
        """[M, F, B, 3] local hists → bool votes [M, F]: each shard
        nominates its top-K features by local best-bin gain (PV-Tree local
        voting), honouring the feature_fraction mask. Categorical columns
        are scored by their sorted-scan gain — the ordinal scan would
        systematically under-vote a predictive non-contiguous set."""
        stats, _ = _split_stats_with_cat(
            hists, p,
            cat_idx=jnp.asarray(cat_features, jnp.int32)
            if has_cat else None)
        gain = stats[6]                                    # [M, F, B]
        fgain = jnp.max(gain, axis=-1)                     # [M, F]
        fgain = jnp.where(feature_mask[None, :], fgain, -jnp.inf)
        _, top_idx = jax.lax.top_k(fgain, min(p.top_k, F))  # [M, k]
        return jnp.zeros_like(fgain).at[
            jnp.arange(fgain.shape[0])[:, None], top_idx].set(1.0)

    def vote_and_gather(hists):
        """[M, F, B, 3] local hists → global candidates for M leaves:
        (cand_feat [M, C] i32, cand_hist [M, C, B, 3] globally reduced).
        Runs the two collectives of voting mode; must be called
        unconditionally."""
        votes = psum(local_top_features(hists))            # [M, F]
        _, cand = jax.lax.top_k(votes, C)                  # [M, C]
        cand = cand.astype(jnp.int32)
        cols = jnp.take_along_axis(
            hists, cand[:, :, None, None], axis=1)         # [M, C, B, 3]
        return cand, psum(cols)

    # ---- root histogram: every (unmasked) row is in slot 0. Subsequent
    # splits scatter only the smaller child and derive the larger by
    # subtraction — LightGBM's histogram-subtraction trick, which cuts
    # per-tree histogram work from O(L·n·F) to O(n·F·avg_depth).
    h_root = local_hist(jnp.ones_like(row_mask), full=True)
    if voting:
        hist0 = jnp.zeros((L, F, B, 3), jnp.float32).at[0].set(h_root)
        cand0, cand_hist0 = vote_and_gather(h_root[None])
        cand_feat = jnp.zeros((L, C), jnp.int32).at[0].set(cand0[0])
        cand_hist = jnp.zeros((L, C, B, 3), jnp.float32).at[0].set(
            cand_hist0[0])
    else:
        hist0 = jnp.zeros((L, F, B, 3), jnp.float32).at[0].set(psum(h_root))
        cand_feat = jnp.zeros((L, 0), jnp.int32)           # unused
        cand_hist = jnp.zeros((L, 0, B, 3), jnp.float32)   # unused

    state = {
        "tree": tree,
        "slot": jnp.zeros(n, jnp.int32),         # per-row leaf slot
        "slot_node": jnp.zeros(L, jnp.int32),    # slot -> node id
        "slot_depth": jnp.zeros(L, jnp.int32),
        "n_slots": jnp.int32(1),
        "done": jnp.asarray(False),
        "hist": hist0,          # data: global; voting: shard-local
        "cand_feat": cand_feat,
        "cand_hist": cand_hist,
    }

    def split_body(state):
        tree = state["tree"]
        slot_ids = jnp.arange(L)
        active = slot_ids < state["n_slots"]
        deep_ok = state["slot_depth"] < max_depth

        # ---- find the best (slot, feature, bin) from GLOBAL histogram
        # information — bitwise-identical on every shard, so every derived
        # predicate below is shard-uniform.
        if voting:
            search = state["cand_hist"]                    # [L, C, B, 3]
            n_search = C
        else:
            search = state["hist"]                         # [L, F, B, 3]
            n_search = F
        if has_cat:
            # categorical: sorted-scan stats via the shared helper. In
            # voting, candidate columns vary per (leaf, iteration) — no
            # static gather, so every (small, 2·topK) candidate column
            # pays the sort and stats select by the per-column mask; in
            # data-parallel only the categorical COLUMNS pay.
            cat_idx = jnp.asarray(cat_features, jnp.int32)
            (gl, hl, cl, gr, hr, cr, gain), cat_order_c = \
                _split_stats_with_cat(
                    search, p,
                    cat_idx=None if voting else cat_idx,
                    cat_mask=cat_feat_mask[state["cand_feat"]]
                    if voting else None)
        else:
            gl, hl, cl, gr, hr, cr, gain = _split_stats(search, p)
        if voting:
            feat_ok = feature_mask[state["cand_feat"]][:, :, None]
        else:
            feat_ok = feature_mask[None, :, None]
        valid = (
            active[:, None, None] & deep_ok[:, None, None] & feat_ok
            & (cl >= p.min_data_in_leaf) & (cr >= p.min_data_in_leaf)
            & (hl >= p.min_sum_hessian_in_leaf)
            & (hr >= p.min_sum_hessian_in_leaf)
            & (state["n_slots"] < L))
        gain = jnp.where(valid, gain, -jnp.inf)

        flat_best = jnp.argmax(gain)
        s_star = (flat_best // (n_search * B)).astype(jnp.int32)
        j_star = ((flat_best // B) % n_search).astype(jnp.int32)
        b_star = (flat_best % B).astype(jnp.int32)
        best_gain = gain.reshape(-1)[flat_best]
        f_star = state["cand_feat"][s_star, j_star] if voting else j_star
        found = (best_gain > p.min_gain_to_split) & ~state["done"]

        # global child stats of the chosen split
        lg = gl[s_star, j_star, b_star]
        lh = hl[s_star, j_star, b_star]
        lc = cl[s_star, j_star, b_star]
        tg = lg + gr[s_star, j_star, b_star]
        th = lh + hr[s_star, j_star, b_star]
        tc = lc + cr[s_star, j_star, b_star]
        rg, rh, rc = tg - lg, th - lh, tc - lc

        # ---- row routing + the UNCONDITIONAL histogram work. When no
        # split applies, sel is all-zero: the scatter/psum still executes
        # (lockstep) but the results are discarded by the cond below.
        new_slot = state["n_slots"]
        row_bin = jnp.take(bins, f_star, axis=1).astype(jnp.int32)
        in_parent = (state["slot"] == s_star) & found
        if has_cat:
            is_cat = cat_feat_mask[f_star]
            # rank of each bin in the chosen (slot, feature)'s ratio
            # sort; left = the b_star+1 best-ratio categories. In voting
            # mode the sort lives at the candidate column j_star; in
            # data-parallel f_star maps into the compact categorical
            # column via searchsorted (0 when not categorical — unused
            # then, guarded by is_cat)
            if voting:
                order_star = cat_order_c[s_star, j_star]  # [B]
            else:
                f_star_c = jnp.searchsorted(cat_idx, f_star)
                f_star_c = jnp.clip(f_star_c, 0, cat_idx.shape[0] - 1)
                order_star = cat_order_c[s_star, f_star_c]
            rank = jnp.zeros(B, jnp.int32).at[order_star].set(
                jnp.arange(B, dtype=jnp.int32))
            left_set = is_cat & (rank <= b_star)          # bool [B]
            right_rule = jnp.where(is_cat, rank[row_bin] > b_star,
                                   row_bin > b_star)
        else:
            right_rule = row_bin > b_star
        goes_right = in_parent & right_rule
        use_left = lc <= rc  # scatter the smaller child, derive sibling
        sel = jnp.where(use_left, in_parent & ~goes_right, goes_right)
        h_small = local_hist(sel.astype(jnp.float32))
        if not voting:
            h_small = psum(h_small)
        parent_h = state["hist"][s_star]
        h_other = parent_h - h_small
        h_left = jnp.where(use_left, h_small, h_other)
        h_right = jnp.where(use_left, h_other, h_small)

        if voting:
            # nominate + reduce candidate columns for both children —
            # collectives outside the cond, zero-data when not found
            child_cand, child_glob = vote_and_gather(
                jnp.stack([h_left, h_right]))

        def apply(state):
            tree = state["tree"]
            parent = state["slot_node"][s_star]
            nl = tree.num_nodes
            nr = tree.num_nodes + 1

            new_tree = Tree(
                feature=tree.feature.at[parent].set(f_star),
                split_bin=tree.split_bin.at[parent].set(b_star),
                cat_flag=(tree.cat_flag.at[parent].set(is_cat)
                          if has_cat else tree.cat_flag),
                cat_left=(tree.cat_left.at[parent].set(left_set)
                          if has_cat else tree.cat_left),
                left=tree.left.at[parent].set(nl),
                right=tree.right.at[parent].set(nr),
                leaf_value=tree.leaf_value
                    .at[nl].set(p.learning_rate * _leaf_output(lg, lh, p))
                    .at[nr].set(p.learning_rate * _leaf_output(rg, rh, p)),
                is_leaf=tree.is_leaf.at[parent].set(False)
                    .at[nl].set(True).at[nr].set(True),
                split_gain=tree.split_gain.at[parent].set(best_gain),
                node_value=tree.node_value
                    .at[nl].set(_leaf_output(lg, lh, p))
                    .at[nr].set(_leaf_output(rg, rh, p)),
                node_weight=tree.node_weight.at[nl].set(lh).at[nr].set(rh),
                node_count=tree.node_count.at[nl].set(lc).at[nr].set(rc),
                num_nodes=tree.num_nodes + 2,
            )

            slot = jnp.where(goes_right, new_slot, state["slot"])
            new_hist = state["hist"].at[s_star].set(h_left) \
                .at[new_slot].set(h_right)
            depth = state["slot_depth"][s_star] + 1
            out = {
                "tree": new_tree,
                "slot": slot,
                "slot_node": state["slot_node"]
                    .at[s_star].set(nl).at[new_slot].set(nr),
                "slot_depth": state["slot_depth"]
                    .at[s_star].set(depth).at[new_slot].set(depth),
                "n_slots": state["n_slots"] + 1,
                "done": jnp.asarray(False),
                "hist": new_hist,
                "cand_feat": state["cand_feat"],
                "cand_hist": state["cand_hist"],
            }
            if voting:
                out["cand_feat"] = state["cand_feat"] \
                    .at[s_star].set(child_cand[0]) \
                    .at[new_slot].set(child_cand[1])
                out["cand_hist"] = state["cand_hist"] \
                    .at[s_star].set(child_glob[0]) \
                    .at[new_slot].set(child_glob[1])
            return out

        def no_split(state):
            return {**state, "done": jnp.asarray(True)}

        # pure arithmetic only — every collective already ran above
        return jax.lax.cond(found, apply, no_split, state)

    if psum_axis is None:
        # single-device: no collectives exist, so the lockstep rule does
        # not apply — skip the whole body (including the O(n·F) histogram
        # scatter) once the tree stops splitting
        def split_step(_, state):
            return jax.lax.cond(state["done"], lambda s: s, split_body,
                                state)
    else:
        # distributed: the body must run on every iteration on every
        # shard so its collectives stay in lockstep
        def split_step(_, state):
            return split_body(state)

    state = jax.lax.fori_loop(0, L - 1, split_step, state)
    row_leaf = state["slot_node"][state["slot"]]
    return state["tree"], row_leaf


@functools.partial(jax.jit, static_argnames=("max_depth",))
def tree_route_bins(tree: Tree, bins: jnp.ndarray, *, max_depth: int):
    """Route binned rows through one tree → leaf node ids (for validation
    scoring during training)."""
    n = bins.shape[0]
    node = jnp.zeros(n, jnp.int32)

    def step(_, node):
        f = tree.feature[node]
        b = tree.split_bin[node]
        row_bin = jnp.take_along_axis(
            bins, f[:, None].astype(jnp.int32), axis=1)[:, 0].astype(jnp.int32)
        go_left = jnp.where(tree.cat_flag[node],
                            tree.cat_left[node, row_bin], row_bin <= b)
        nxt = jnp.where(go_left, tree.left[node], tree.right[node])
        return jnp.where(tree.is_leaf[node], node, nxt)

    return jax.lax.fori_loop(0, max_depth, step, node)
