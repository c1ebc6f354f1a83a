"""Plain reference for histogram GBDT with LightGBM's semantics: seeded
Higgs-shaped data, quantile bin bounds, a reader of the LightGBM text
model format, a tree walk over raw features, a leaf-wise grower, and the
comparison that decides ``correct`` for the fit cell. Straightforward
``numpy``/``jax.numpy`` in float32 (sums at the highest matmul
precision), no kernels; imports nothing of the program.

The comparison replays the served model tree by tree on the training
rows: scores from the reference's own walk, gradients from the binary
log-loss, histograms of every checked node by a one-hot contraction, and
then asks of each split "by how much does the gain of what the program
chose lie below the best gain the reference finds at that node" — a gap,
not an identity, because two near-equal gains may legitimately swap
under another order of summation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CHUNK = 32768          # rows per scan step of the device reductions
NODE_BATCH = 42        # nodes per histogram pass: 3 x 42 = 126 lanes


# ------------------------------------------------------------------ data
def make_data(seed: int, n_rows: int, n_features: int = 28):
    """Higgs-shaped synthetic: standard-normal float32 features, label
    from a margin with an interaction term and unit noise."""
    rng = np.random.default_rng(int(seed))
    x = rng.standard_normal((n_rows, n_features), dtype=np.float32)
    margin = x[:, :4].sum(1) + x[:, 4] * x[:, 5]
    y = (margin + rng.standard_normal(n_rows, dtype=np.float32) > 0)
    return x, y.astype(np.float32)


# --------------------------------------------------------------- binning
def bin_bounds(x: np.ndarray, cfg: dict) -> np.ndarray:
    """Per-feature upper bounds ``[F, max_bin - 1]`` (float32, +inf
    padded) by the configuration's ``bin_rule``."""
    a = cfg["assumed"]
    max_bin = int(cfg["max_bin"])
    n, n_feat = x.shape
    cnt = int(a["bin_construct_sample_cnt"])
    if n > cnt:
        rng = np.random.default_rng(int(a["bin_sample_seed"]))
        x = x[rng.choice(n, cnt, replace=False)]
    out = np.full((n_feat, max_bin - 1), np.inf, np.float64)
    probs = np.linspace(0, 1, max_bin)[1:-1]
    for f in range(n_feat):
        uniq = np.unique(x[:, f][~np.isnan(x[:, f])])
        if uniq.size == 0:
            continue
        if uniq.size <= max_bin - 1:
            cuts = (uniq[:-1] + uniq[1:]) / 2.0
        else:
            cuts = np.unique(np.quantile(uniq, probs, method="linear"))
        out[f, :cuts.size] = cuts
    return out.astype(np.float32)


def _pad_rows(a, chunk):
    pad = (-a.shape[0]) % chunk
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
    return a.reshape((-1, chunk) + a.shape[1:])


@functools.partial(jax.jit, static_argnames=("chunk",))
def bin_rows(x, bounds, *, chunk=CHUNK):
    """Bin ids ``[n, F]`` uint8: 1 + the number of bounds below the
    value (bin 0 is kept for missing values)."""
    n = x.shape[0]

    def step(_, xc):
        ids = 1 + jnp.sum(xc[:, :, None] > bounds[None], axis=-1)
        return None, ids.astype(jnp.uint8)

    _, out = lax.scan(step, None, _pad_rows(x, chunk))
    return out.reshape((-1, x.shape[1]))[:n]


# ------------------------------------------------------------ text model
class Tree(NamedTuple):
    """One tree in LightGBM's text layout: internal nodes 0..L-2, a child
    code >= 0 is an internal node, < 0 is leaf ``~code``."""
    split_feature: np.ndarray
    threshold: np.ndarray
    left: np.ndarray
    right: np.ndarray
    leaf_value: np.ndarray
    leaf_count: np.ndarray
    internal_count: np.ndarray


def parse_model(text: str) -> list:
    """Read the trees of a LightGBM text model (numerical splits)."""
    trees, cur = [], None
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("Tree="):
            cur = {}
            trees.append(cur)
        elif line == "end of trees":
            break
        elif cur is not None and "=" in line:
            key, _, val = line.partition("=")
            cur[key] = val.split()
    out = []
    for t in trees:
        if int(t.get("num_cat", ["0"])[0]) != 0:
            raise ValueError("categorical splits are not in this reference")

        def arr(key, dtype):
            return np.asarray(t.get(key, []), dtype=dtype)
        tree = Tree(arr("split_feature", np.int32),
                    arr("threshold", np.float64).astype(np.float32),
                    arr("left_child", np.int32), arr("right_child", np.int32),
                    arr("leaf_value", np.float64),
                    arr("leaf_count", np.int64),
                    arr("internal_count", np.int64))
        n_leaves = int(t["num_leaves"][0])
        if not (len(tree.leaf_value) == n_leaves
                and len(tree.split_feature) == n_leaves - 1
                == len(tree.left) == len(tree.right)):
            raise ValueError("malformed tree in the text model")
        out.append(tree)
    return out


def tree_depth(tree: Tree) -> int:
    depth = {0: 1}
    for i in range(len(tree.left)):          # parents precede children
        for c in (tree.left[i], tree.right[i]):
            if c >= 0:
                depth[int(c)] = depth[i] + 1
    return max(depth.values()) if len(tree.left) else 0


def descendants(tree: Tree) -> np.ndarray:
    """``[internal nodes, leaves]`` bool: leaf lies under the node."""
    n_int, n_leaf = len(tree.left), len(tree.leaf_value)
    under = np.zeros((n_int, n_leaf), bool)
    for i in range(n_int - 1, -1, -1):       # children follow parents
        for c in (tree.left[i], tree.right[i]):
            if c >= 0:
                under[i] |= under[c]
            else:
                under[i, ~c] = True
    return under


def _lookup(table, idx):
    """``table[idx]`` for a small table, as select-and-sum: exact, and no
    gather (a per-row gather is the slow way on a TPU)."""
    hit = idx[:, None] == jnp.arange(table.shape[0])[None, :]
    return jnp.sum(jnp.where(hit, table[None, :], 0), axis=1)


@functools.partial(jax.jit, static_argnames=("chunk",))
def route(x, feat, thr, left, right, depth, *, chunk=CHUNK):
    """Leaf index of every row: go left iff ``value <= threshold``."""
    n, n_feat = x.shape
    if feat.shape[0] == 0:
        return jnp.zeros(n, jnp.int32)

    def per_chunk(_, xc):
        def body(_, node):
            inner = node >= 0
            idx = jnp.where(inner, node, 0)
            f = _lookup(feat, idx)
            val = jnp.sum(jnp.where(
                f[:, None] == jnp.arange(n_feat)[None, :], xc, 0.0), axis=1)
            nxt = jnp.where(val <= _lookup(thr, idx), _lookup(left, idx),
                            _lookup(right, idx))
            return jnp.where(inner, nxt, node)

        node = lax.fori_loop(0, depth, body,
                             jnp.zeros(xc.shape[0], jnp.int32))
        return None, -node - 1

    _, leaf = lax.scan(per_chunk, None, _pad_rows(x, chunk))
    return leaf.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("chunk",))
def leaf_values(values, leaf, *, chunk=CHUNK):
    """``values[leaf]`` row by row, chunked."""
    n = leaf.shape[0]
    _, out = lax.scan(lambda _, lc: (None, _lookup(values, lc)), None,
                      _pad_rows(leaf, chunk))
    return out.reshape(-1)[:n]


def route_tree(x, tree: Tree, chunk: int = CHUNK):
    return route(x, jnp.asarray(tree.split_feature),
                 jnp.asarray(tree.threshold), jnp.asarray(tree.left),
                 jnp.asarray(tree.right), tree_depth(tree), chunk=chunk)


def walk_scores(x, trees: list, chunk: int = CHUNK):
    """Raw score of every row: the sum of its leaf values."""
    score = jnp.zeros(x.shape[0], jnp.float32)
    for tree in trees:
        leaf = route_tree(x, tree, chunk)
        score = score + leaf_values(
            jnp.asarray(tree.leaf_value, jnp.float32), leaf, chunk=chunk)
    return score


# ------------------------------------------------- gradients, histograms
def init_score(y: np.ndarray) -> float:
    p = float(np.mean(np.asarray(y, np.float64)))
    p = min(max(p, 1e-12), 1 - 1e-12)
    return float(np.log(p / (1 - p)))


@jax.jit
def grad_hess(score, y):
    p = jax.nn.sigmoid(score)
    return p - y, p * (1.0 - p)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk", "precision"))
def group_histograms(bins, group_of_row, member, g, h, *, num_bins: int,
                     chunk: int = CHUNK, precision: str = "highest"):
    """Histograms ``[F, num_bins, K, 3]`` (gradient, hessian, count) of K
    row sets at once. Row r belongs to set k iff
    ``member[group_of_row[r], k]``: the groups are leaves, the sets are
    nodes (a row lies in a node iff its leaf lies under it)."""
    n, n_feat = bins.shape
    k = member.shape[1]
    valid = jnp.ones(n, jnp.float32)
    vals = jnp.stack([g, h, valid], axis=1)
    prec = lax.Precision.HIGHEST if precision == "highest" \
        else lax.Precision.DEFAULT

    def step(acc, args):
        bc, gc, vc = args
        m = jnp.dot((gc[:, None] == jnp.arange(member.shape[0])[None, :])
                    .astype(jnp.float32), member,
                    precision=lax.Precision.HIGHEST)     # [C, K], 0 or 1
        v = (m[:, :, None] * vc[:, None, :]).reshape(chunk, k * 3)
        onehot = (bc[:, :, None] == jnp.arange(num_bins, dtype=bc.dtype)
                  ).astype(jnp.float32)                  # [C, F, B]
        return acc + jnp.einsum("cfb,cv->fbv", onehot, v,
                                precision=prec), None

    acc0 = jnp.zeros((n_feat, num_bins, k * 3), jnp.float32)
    acc, _ = lax.scan(step, acc0, (_pad_rows(bins, chunk),
                                   _pad_rows(group_of_row, chunk),
                                   _pad_rows(vals, chunk)))
    return acc.reshape(n_feat, num_bins, k, 3)


@functools.partial(jax.jit, static_argnames=("groups", "chunk"))
def group_sums(group_of_row, g, h, *, groups: int, chunk: int = CHUNK):
    """``[groups, 3]`` sums of gradient, hessian and count."""
    vals = jnp.stack([g, h, jnp.ones_like(g)], axis=1)

    def step(acc, args):
        gc, vc = args
        onehot = (gc[:, None] == jnp.arange(groups)).astype(jnp.float32)
        return acc + jnp.einsum("cl,cv->lv", onehot, vc,
                                precision=lax.Precision.HIGHEST), None

    acc, _ = lax.scan(step, jnp.zeros((groups, 3), jnp.float32),
                      (_pad_rows(group_of_row, chunk),
                       _pad_rows(vals, chunk)))
    return acc


def split_table(hist: np.ndarray, cfg: dict):
    """From one node's histogram ``[F, B, 3]`` (float64) the gain of
    every candidate "bin <= b goes left" and whether it is allowed."""
    a = cfg["assumed"]
    l2 = float(a["lambda_l2"])
    cum = np.cumsum(hist, axis=1)
    tot = cum[:, -1:, :]
    gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
    gr, hr, cr = tot[..., 0] - gl, tot[..., 1] - hl, tot[..., 2] - cl

    def leaf_gain(g, h):
        return g * g / (h + l2 + 1e-35)
    gain = leaf_gain(gl, hl) + leaf_gain(gr, hr) \
        - leaf_gain(tot[..., 0], tot[..., 1])
    ok = ((cl >= a["min_data_in_leaf"]) & (cr >= a["min_data_in_leaf"])
          & (hl >= cfg["min_sum_hessian_in_leaf"])
          & (hr >= cfg["min_sum_hessian_in_leaf"])
          & (gain > a["min_gain_to_split"]))
    ok[:, -1] = False                       # nothing on the right
    return gain, ok, np.minimum(hl, hr)


# ---------------------------------------------------------------- grower
def _identity(v):
    return v


def grow_tree(bins, g, h, cfg: dict, *, round_fn=_identity,
              precision: str = "highest", bounds: np.ndarray,
              shift: float = 0.0, chunk: int = CHUNK) -> Tree:
    """Leaf-wise growth of one tree: split the open leaf of largest gain
    until ``num_leaves``. ``round_fn`` rounds the gradient pair before it
    is summed: the identity for the reference, a narrower type for a
    control."""
    num_bins = int(cfg["max_bin"]) + 1
    n_leaves = int(cfg["num_leaves"])
    lr = float(cfg["learning_rate"])
    l2 = float(cfg["assumed"]["lambda_l2"])
    g, h = round_fn(g), round_fn(h)
    leaf_of_row = jnp.zeros(bins.shape[0], jnp.int32)

    def best_of(leaves):
        member = jnp.zeros((n_leaves, len(leaves)), jnp.float32)
        member = member.at[jnp.asarray(leaves), jnp.arange(len(leaves))] \
            .set(1.0)
        hist = np.asarray(group_histograms(
            bins, leaf_of_row, member, g, h, num_bins=num_bins,
            chunk=chunk, precision=precision), np.float64)
        out = []
        for k in range(len(leaves)):
            gain, ok, _ = split_table(hist[:, :, k, :], cfg)
            gain = np.where(ok, gain, -np.inf)
            f, b = np.unravel_index(np.argmax(gain), gain.shape)
            tot = hist[0, :, k, :].sum(0)
            out.append((float(gain[f, b]), int(f), int(b), tot))
        return out

    open_best = {0: best_of([0])[0]}
    # node bookkeeping in LightGBM's layout
    feat, thr, left, right, icount = [], [], [], [], []
    node_of_leaf = {}                 # leaf -> (parent internal, side)
    while len(open_best) < n_leaves:
        leaf, (gain, f, b, tot) = max(open_best.items(),
                                      key=lambda kv: kv[1][0])
        if not np.isfinite(gain):
            break
        new_leaf = len(open_best)
        node = len(feat)
        feat.append(f)
        thr.append(float(bounds[f, b - 1]) if b >= 1 else -np.inf)
        icount.append(int(round(tot[2])))
        left.append(~leaf)
        right.append(~new_leaf)
        if leaf in node_of_leaf:
            parent, side = node_of_leaf[leaf]
            (left if side == 0 else right)[parent] = node
        node_of_leaf[leaf] = (node, 0)
        node_of_leaf[new_leaf] = (node, 1)
        goes_right = (leaf_of_row == leaf) & (bins[:, f] > b)
        leaf_of_row = jnp.where(goes_right, new_leaf, leaf_of_row)
        open_best[leaf], open_best[new_leaf] = best_of([leaf, new_leaf])
    n_out = len(open_best)
    tots = np.stack([open_best[i][3] for i in range(n_out)])
    value = shift - lr * tots[:, 0] / (tots[:, 1] + l2 + 1e-35)
    return Tree(np.asarray(feat, np.int32), np.asarray(thr, np.float32),
                np.asarray(left, np.int32), np.asarray(right, np.int32),
                value, np.round(tots[:, 2]).astype(np.int64),
                np.asarray(icount, np.int64))


def _prepare(x, y, cfg: dict, chunk: int):
    """What a fit and a replay both start from: the bounds, the rows and
    labels on the device, their bins, the base score and the scores."""
    bounds = bin_bounds(np.asarray(x), cfg)
    xd, yd = jnp.asarray(x), jnp.asarray(y)
    bins = bin_rows(xd, jnp.asarray(bounds), chunk=chunk)
    base = init_score(np.asarray(y))
    return bounds, xd, yd, bins, base, jnp.full(xd.shape[0], base,
                                                jnp.float32)


def fit(x, y, cfg: dict, num_trees: int, *, round_fn=_identity,
        precision: str = "highest", chunk: int = CHUNK) -> list:
    """The reference put in the program's place: boost ``num_trees``
    trees on device-resident ``x``/``y``."""
    bounds, xd, yd, bins, base, score = _prepare(x, y, cfg, chunk)
    trees = []
    for t in range(num_trees):
        g, h = grad_hess(score, yd)
        tree = grow_tree(bins, g, h, cfg, round_fn=round_fn,
                         precision=precision, bounds=bounds,
                         shift=base if t == 0 else 0.0, chunk=chunk)
        trees.append(tree)
        score = walk_scores(xd, trees, chunk)
    return trees


# ------------------------------------------------------------ comparison
NUMBERS = ("split_gain_gap", "leaf_value_gap", "leaf_count_gap",
           "hessian_floor_gap", "threshold_gap", "prob_gap")


def compare_model(trees: list, x, y, cfg: dict, *, num_trees: int,
                  check_nodes: int, seed: int, held_out=None,
                  served_prob=None, chunk: int = CHUNK) -> dict:
    """Replay ``trees`` (the served model, parsed) on the training rows
    and return the numbers compared, by the names in ``NUMBERS``."""
    inf = float("inf")
    n_leaves = int(cfg["num_leaves"])
    num_bins = int(cfg["max_bin"]) + 1
    lr = float(cfg["learning_rate"])
    l2 = float(cfg["assumed"]["lambda_l2"])
    floor = float(cfg["min_sum_hessian_in_leaf"])
    out = dict.fromkeys(NUMBERS, 0.0)
    if len(trees) != num_trees or any(
            len(t.leaf_value) != n_leaves for t in trees):
        # a model of another size is another answer
        return dict.fromkeys(NUMBERS, inf)
    bounds, xd, yd, bins, base, score = _prepare(x, y, cfg, chunk)
    rng = np.random.default_rng(int(seed))
    for t, tree in enumerate(trees):
        g, h = grad_hess(score, yd)
        leaf = route_tree(xd, tree, chunk)
        sums = np.asarray(group_sums(leaf, g, h, groups=n_leaves,
                                     chunk=chunk),
                          np.float64)
        # leaves: rows routed, and the value the sums give
        out["leaf_count_gap"] = max(out["leaf_count_gap"], float(np.abs(
            np.round(sums[:, 2]) - tree.leaf_count).max()))
        # the text model folds the base score into the first tree
        shift = base if t == 0 else 0.0
        step = -lr * sums[:, 0] / (sums[:, 1] + l2 + 1e-35)
        scale = np.maximum(np.abs(step), np.median(np.abs(step)))
        out["leaf_value_gap"] = max(out["leaf_value_gap"], float(
            (np.abs(tree.leaf_value - (shift + step)) / scale).max()))
        # thresholds: each must be one of the reference's bounds
        fb = bounds[tree.split_feature]                   # [nodes, B-2]
        j = np.abs(fb - tree.threshold[:, None]).argmin(axis=1)
        near = fb[np.arange(len(j)), j]
        out["threshold_gap"] = max(out["threshold_gap"], float(
            (np.abs(near - tree.threshold)
             / np.maximum(np.abs(near), 1e-3)).max()))
        # internal nodes: a seeded sample with the root in it
        n_int = len(tree.left)
        picked = np.arange(n_int) if check_nodes >= n_int else np.unique(
            np.concatenate([[0], rng.choice(n_int, check_nodes - 1,
                                            replace=False)]))
        under = descendants(tree)
        for s in range(0, len(picked), NODE_BATCH):
            batch = picked[s:s + NODE_BATCH]
            member = np.zeros((n_leaves, NODE_BATCH), np.float32)
            member[:, :len(batch)] = under[batch].T
            hist = np.asarray(group_histograms(
                bins, leaf, jnp.asarray(member), g, h, num_bins=num_bins,
                chunk=chunk), np.float64)
            for k, node in enumerate(batch):
                hk = hist[:, :, k, :]
                gain, ok, hmin = split_table(hk, cfg)
                f, b = int(tree.split_feature[node]), int(j[node]) + 1
                best = float(np.where(ok, gain, -np.inf).max())
                if not np.isfinite(best) or best <= 0:
                    out["split_gain_gap"] = inf   # split where none is allowed
                    continue
                out["split_gain_gap"] = max(
                    out["split_gain_gap"], (best - float(gain[f, b])) / best)
                out["hessian_floor_gap"] = max(
                    out["hessian_floor_gap"],
                    (floor - float(hmin[f, b])) / floor)
                count = int(round(hk[0, :, 2].sum()))
                out["leaf_count_gap"] = max(
                    out["leaf_count_gap"],
                    float(abs(count - tree.internal_count[node])))
        score = (0.0 if t == 0 else score) + leaf_values(
            jnp.asarray(tree.leaf_value, jnp.float32), leaf, chunk=chunk)
    if held_out is not None:
        prob = np.asarray(jax.nn.sigmoid(
            walk_scores(jnp.asarray(held_out), trees, chunk)), np.float64)
        got = np.asarray(served_prob, np.float64)
        out["prob_gap"] = float(np.abs(got - prob).max()) \
            if got.shape == prob.shape and np.isfinite(got).all() else inf
    return out
