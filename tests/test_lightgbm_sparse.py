"""Sparse (padded-COO) GBDT path — the CSR-equivalent of reference
``TrainUtils.scala:33-92`` (review round 1 missing #4): high-dimensional hashed
features train end-to-end without densification, single-device and sharded.
"""

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.lightgbm.sparse import (SparseData, bin_sparse,
                                          compute_sparse_bin_boundaries)
from mmlspark_tpu.lightgbm.trainer import roc_auc


def dense_to_coo(x: np.ndarray, width: int | None = None):
    """Dense [n, F] → padded-COO (indices, values) with -1/-0 padding."""
    n, F = x.shape
    nnz = (x != 0)
    W = width or max(int(nnz.sum(1).max()), 1)
    indices = np.full((n, W), -1, np.int32)
    values = np.zeros((n, W), np.float32)
    for r in range(n):
        cols = np.flatnonzero(nnz[r])[:W]
        indices[r, :cols.size] = cols
        values[r, :cols.size] = x[r, cols]
    return indices, values


def sparse_binary_df(n=400, f=10, density=0.4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    x[rng.random((n, f)) > density] = 0.0
    logits = x[:, 0] * 2 - x[:, 1] + x[:, 2]
    y = (logits + rng.normal(scale=0.3, size=n) > 0).astype(np.float32)
    idx, val = dense_to_coo(x)
    return DataFrame({"features_indices": idx, "features_values": val,
                      "label": y}), x, y


class TestSparseBinning:
    def test_zero_gets_own_bin(self):
        # features with positive, negative, and mixed values: implicit
        # zeros must never share a bin with a nonzero value (LightGBM's
        # ZeroAsOneBin semantics)
        idx = np.array([[0, 1, 2], [0, 1, 2], [0, 1, -1]], np.int32)
        val = np.array([[1.0, -2.0, 3.0], [2.0, -1.0, -3.0],
                        [4.0, -4.0, 0.0]], np.float32)
        sd = SparseData(idx, val, 4)
        bounds = compute_sparse_bin_boundaries(sd, max_bin=8)
        binned = bin_sparse(sd, bounds)
        zb = np.asarray(binned.zero_bin)
        eb = np.asarray(binned.ebins)
        for (r, w), f in np.ndenumerate(idx):
            if f >= 0 and val[r, w] != 0.0:
                assert eb[r, w] != zb[f], (
                    f"value {val[r, w]} of feature {f} shares the zero bin")
        # ordering: negative < zero < positive in bin space
        for (r, w), f in np.ndenumerate(idx):
            if f >= 0 and val[r, w] > 0:
                assert eb[r, w] > zb[f]
            if f >= 0 and val[r, w] < 0:
                assert eb[r, w] < zb[f]

    def test_binning_is_monotone_per_feature(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(100, 5)).astype(np.float32)
        x[rng.random((100, 5)) > 0.5] = 0.0
        idx, val = dense_to_coo(x)
        sd = SparseData(idx, val, 5)
        bounds = compute_sparse_bin_boundaries(sd, max_bin=16)
        binned = bin_sparse(sd, bounds)
        eb = np.asarray(binned.ebins)
        for f in range(5):
            sel = idx == f
            order = np.argsort(val[sel])
            assert (np.diff(eb[sel][order]) >= 0).all()


def test_coalesce_coo_merges_duplicates():
    from mmlspark_tpu.lightgbm.sparse import coalesce_coo
    idx = np.array([[3, 1, 3, -1], [2, 2, 2, 2], [5, 6, -1, -1]], np.int32)
    val = np.array([[1., 2., 4., 0.], [1., 1., 1., 1.], [7., 8., 0., 0.]],
                   np.float32)
    ci, cv = coalesce_coo(idx, val)
    # row 0: 3 appears twice -> summed; row 1: all four merge; row 2 intact
    got = [dict(zip(ci[r][ci[r] >= 0].tolist(),
                    cv[r][ci[r] >= 0].tolist())) for r in range(3)]
    assert got[0] == {1: 2.0, 3: 5.0}
    assert got[1] == {2: 4.0}
    assert got[2] == {5: 7.0, 6: 8.0}
    # no duplicates: returns inputs unchanged (no copy)
    i2 = np.array([[0, 1, -1]], np.int32)
    v2 = np.ones((1, 3), np.float32)
    ri, rv = coalesce_coo(i2, v2)
    assert ri is i2 and rv is v2


class TestSparseTraining:
    def test_sparse_matches_dense_auc(self):
        df, x, y = sparse_binary_df()
        dense_df = DataFrame({"features": x, "label": y})
        common = dict(numIterations=20, numLeaves=7, minDataInLeaf=5,
                      learningRate=0.2)
        dense_m = LightGBMClassifier(**common).fit(dense_df)
        sparse_m = LightGBMClassifier(**common).fit(df)
        auc_d = roc_auc(y, dense_m.transform(dense_df)["probability"][:, 1])
        auc_s = roc_auc(y, sparse_m.transform(df)["probability"][:, 1])
        assert auc_d > 0.9
        assert auc_s > 0.9
        assert abs(auc_d - auc_s) < 0.05

    def test_sparse_regression(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(300, 8)).astype(np.float32)
        x[rng.random((300, 8)) > 0.5] = 0.0
        y = (x[:, 0] * 3 + x[:, 1] ** 2).astype(np.float32)
        idx, val = dense_to_coo(x)
        df = DataFrame({"features_indices": idx, "features_values": val,
                        "label": y})
        m = LightGBMRegressor(numIterations=30, numLeaves=15,
                              minDataInLeaf=3, learningRate=0.2).fit(df)
        pred = m.transform(df)["prediction"]
        resid = np.sqrt(np.mean((pred - y) ** 2))
        assert resid < 0.8 * y.std(), (resid, y.std())

    def test_sparse_native_roundtrip(self):
        df, x, y = sparse_binary_df(seed=5)
        m = LightGBMClassifier(numIterations=10, numLeaves=7,
                               minDataInLeaf=5).fit(df)
        sd = SparseData(np.asarray(df["features_indices"]),
                        np.asarray(df["features_values"]), x.shape[1])
        expected = m.booster.raw_scores(sd)
        from mmlspark_tpu.lightgbm import Booster
        re = Booster.load_native(m.get_native_model_string())
        np.testing.assert_allclose(re.raw_scores(sd), expected,
                                   rtol=1e-4, atol=1e-5)
        # sparse-trained thresholds are raw-value thresholds: dense scoring
        # of the densified matrix must agree with COO scoring
        np.testing.assert_allclose(m.booster.raw_scores(x), expected,
                                   rtol=1e-4, atol=1e-5)

    def test_empty_and_all_padding_input(self):
        df, x, y = sparse_binary_df(seed=21)
        m = LightGBMClassifier(numIterations=5, numLeaves=7,
                               minDataInLeaf=5).fit(df)
        empty = DataFrame({
            "features_indices": np.zeros((0, 4), np.int32),
            "features_values": np.zeros((0, 4), np.float32)})
        out = m.transform(empty)
        assert out["prediction"].shape == (0,)
        allpad = DataFrame({
            "features_indices": np.full((3, 4), -1, np.int32),
            "features_values": np.zeros((3, 4), np.float32)})
        out2 = m.transform(allpad)
        assert out2["prediction"].shape == (3,)

    def test_validation_early_stopping_sparse(self):
        df, x, y = sparse_binary_df(n=500, seed=7)
        flag = np.zeros(500, bool)
        flag[400:] = True
        df = df.with_column("isVal", flag)
        m = LightGBMClassifier(numIterations=40, numLeaves=7,
                               minDataInLeaf=5,
                               validationIndicatorCol="isVal",
                               earlyStoppingRound=5).fit(df)
        assert m.booster.num_trees <= 40


class TestHighDimHashed:
    """The north-star scenario: 2^18-dim hashed features (the VW
    featurizer's own output) feed the GBDT directly (review round 1 item 4)."""

    def test_featurize_to_gbdt_end_to_end(self):
        rng = np.random.default_rng(11)
        n = 300
        words = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta",
                 "eta", "theta"]
        texts, labels = [], []
        for i in range(n):
            k = rng.integers(2, 6)
            chosen = rng.choice(len(words), size=k, replace=False)
            texts.append(" ".join(words[c] for c in chosen))
            labels.append(1.0 if 0 in chosen or 1 in chosen else 0.0)
        df = DataFrame({"text": np.asarray(texts, object),
                        "label": np.asarray(labels, np.float32)})

        from mmlspark_tpu.vw import VowpalWabbitFeaturizer
        feat = VowpalWabbitFeaturizer(inputCols=["text"],
                                      stringSplitInputCols=["text"],
                                      numBits=18, outputCol="features")
        fdf = feat.transform(df)
        assert fdf["features_indices"].max() > 2 ** 12  # truly high-dim

        m = LightGBMClassifier(numIterations=15, numLeaves=7,
                               minDataInLeaf=5, learningRate=0.3,
                               sparseFeatureCount=2 ** 18).fit(fdf)
        out = m.transform(fdf)
        auc = roc_auc(np.asarray(labels), out["probability"][:, 1])
        assert auc > 0.9, auc

    def test_memory_proportional_to_nnz(self):
        # the training path must never allocate a dense [n, F] matrix at
        # F = 2^18: 2000 rows × 2^18 × 4B would be 2 GB. Assert the
        # process high-water mark grows far less than that during fit.
        import resource
        rng = np.random.default_rng(13)
        n, W, F = 2000, 8, 2 ** 18
        idx = rng.integers(0, F, size=(n, W)).astype(np.int32)
        val = np.ones((n, W), np.float32)
        y = (idx[:, 0] % 2).astype(np.float32)
        df = DataFrame({"features_indices": idx, "features_values": val,
                        "label": y})
        rss_before = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        m = LightGBMClassifier(numIterations=3, numLeaves=7,
                               minDataInLeaf=5,
                               sparseFeatureCount=F).fit(df)
        rss_after = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        assert m.booster.num_trees == 3
        grown_mb = (rss_after - rss_before) / 1024  # ru_maxrss is KiB
        assert grown_mb < 1000, (
            f"fit grew peak RSS by {grown_mb:.0f} MB — a dense [n, F] "
            "materialization at 2^18 features would cost ~2000 MB")


@pytest.mark.slow
class TestSparseDistributed:
    def test_sharded_sparse_matches_single(self):
        df, x, y = sparse_binary_df(n=1200, seed=9)
        common = dict(numIterations=15, numLeaves=7, minDataInLeaf=5)
        single = LightGBMClassifier(numShards=1, **common).fit(df)
        sharded = LightGBMClassifier(numShards=8, **common).fit(df)
        p1 = single.transform(df)["probability"][:, 1]
        p8 = sharded.transform(df)["probability"][:, 1]
        auc_1, auc_8 = roc_auc(y, p1), roc_auc(y, p8)
        assert auc_1 > 0.9
        assert abs(auc_1 - auc_8) < 0.02
        np.testing.assert_allclose(p1, p8, atol=5e-3)

    def test_voting_parallel_sparse(self):
        df, x, y = sparse_binary_df(n=1200, seed=15)
        m = LightGBMClassifier(numIterations=15, numLeaves=7,
                               minDataInLeaf=5, numShards=8,
                               parallelism="voting_parallel",
                               topK=5).fit(df)
        auc = roc_auc(y, m.transform(df)["probability"][:, 1])
        assert auc > 0.88, auc
