"""Distributed GBDT: sharded histogram training over the 8-device virtual
mesh must match single-device training (the psum reassociates float adds, so
comparisons are statistical, not bitwise).

Mirrors the reference's distributed test strategy: multi-partition local[*]
runs exercising the full rendezvous + allreduce path
(``lightgbm/split1/VerifyLightGBMClassifier.scala:595`` — including
not getting stuck on empty partitions / unbalanced shards).
"""

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.lightgbm.trainer import roc_auc


def make_binary(n=1200, f=12, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    logits = x[:, 0] * 2 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return DataFrame({"features": x, "label": y})


class TestDistributedTraining:
    @pytest.mark.slow
    def test_sharded_matches_single_device(self):
        df = make_binary()
        single = (LightGBMClassifier(numIterations=30, numLeaves=15,
                                     numShards=1)
                  .fit(df).transform(df))
        sharded = (LightGBMClassifier(numIterations=30, numLeaves=15,
                                      numShards=8)
                   .fit(df).transform(df))
        y = df["label"]
        auc_1 = roc_auc(y, single["probability"][:, 1])
        auc_8 = roc_auc(y, sharded["probability"][:, 1])
        assert auc_1 > 0.9
        assert abs(auc_1 - auc_8) < 0.02
        # trees see identical global histograms → predictions nearly equal
        np.testing.assert_allclose(single["probability"][:, 1],
                                   sharded["probability"][:, 1], atol=5e-3)

    @pytest.mark.slow
    def test_unbalanced_padding(self):
        # 1203 rows over 8 shards → 5 pad rows; the SPMD 'ignore' path
        df = make_binary(n=1203)
        m = LightGBMClassifier(numIterations=15, numShards=8).fit(df)
        out = m.transform(df)
        assert out["prediction"].shape == (1203,)
        assert roc_auc(df["label"], out["probability"][:, 1]) > 0.85

    @pytest.mark.slow
    def test_regressor_sharded(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(900, 8)).astype(np.float32)
        y = (x[:, 0] * 3 + np.sin(x[:, 1] * 2)).astype(np.float32)
        df = DataFrame({"features": x, "label": y})
        m1 = LightGBMRegressor(numIterations=25, numShards=1).fit(df)
        m8 = LightGBMRegressor(numIterations=25, numShards=8).fit(df)
        p1 = m1.transform(df)["prediction"]
        p8 = m8.transform(df)["prediction"]
        rmse1 = float(np.sqrt(np.mean((p1 - y) ** 2)))
        rmse8 = float(np.sqrt(np.mean((p8 - y) ** 2)))
        assert rmse1 < 1.0 and abs(rmse1 - rmse8) < 0.1

    def test_auto_shard_threshold(self):
        clf = LightGBMClassifier()
        assert clf._training_mesh(100) is None        # tiny data stays local
        mesh = clf._training_mesh(10_000)             # big data auto-shards
        assert mesh is not None and mesh.shape["dp"] == 8

    @pytest.mark.slow
    def test_hierarchical_two_level_psum_matches_flat(self):
        """shardAxisName="slice,dp" shards rows over a two-level
        (DCN x ICI) mesh; the histogram psum composes over the axis
        TUPLE and must train the same model as the flat 8-way psum
        (pure collective algebra over identical global histograms)."""
        import jax
        from jax.sharding import Mesh

        df = make_binary(n=960)
        flat = (LightGBMClassifier(numIterations=15, numLeaves=15,
                                   numShards=8)
                .fit(df).transform(df))
        h = LightGBMClassifier(numIterations=15, numLeaves=15,
                               numShards=8, shardAxisName="slice,dp")
        # single-slice CPU host: the built-in grouping would fall back
        # to slice=1; force the genuinely two-level 2x4 shape
        h._training_mesh = lambda n: Mesh(
            np.asarray(jax.devices()[:8]).reshape(2, 4), ("slice", "dp"))
        hier = h.fit(df).transform(df)
        np.testing.assert_allclose(flat["probability"][:, 1],
                                   hier["probability"][:, 1], atol=5e-3)

    def test_hierarchical_mesh_shape_fallback(self):
        """Without platform slice info the two-level request still
        builds a (1, n) mesh — the composed psum compiles identically
        to what a real multi-slice pod would run."""
        clf = LightGBMClassifier(shardAxisName="slice,dp")
        mesh = clf._training_mesh(10_000)
        assert mesh is not None
        assert mesh.shape["slice"] == 1 and mesh.shape["dp"] == 8
        assert clf._shard_axes() == ("slice", "dp")


class TestVotingParallel:
    """PV-Tree voting mode (reference ``parallelism`` selector,
    ``params/LightGBMParams.scala:16-21``, ``LightGBMConstants.scala:24-26``
    — previously accepted and silently ignored, review round 1 missing #3)."""

    @pytest.mark.slow
    def test_voting_matches_data_parallel_auc(self):
        # wide feature space is voting's regime; top-2K candidates must
        # recover (nearly) the data_parallel splits
        df = make_binary(n=1600, f=40, seed=5)
        y = df["label"]
        data_par = LightGBMClassifier(
            numIterations=25, numLeaves=15, numShards=8,
            parallelism="data_parallel").fit(df).transform(df)
        voting = LightGBMClassifier(
            numIterations=25, numLeaves=15, numShards=8,
            parallelism="voting_parallel", topK=8).fit(df).transform(df)
        auc_d = roc_auc(y, data_par["probability"][:, 1])
        auc_v = roc_auc(y, voting["probability"][:, 1])
        assert auc_d > 0.9
        assert abs(auc_d - auc_v) < 0.02, (auc_d, auc_v)

    def test_voting_single_device_equals_data(self):
        # without a mesh there is nothing to vote over; the param is a
        # no-op by construction (not silently dropped: same code path)
        df = make_binary(n=600)
        a = LightGBMClassifier(numIterations=10, numShards=1,
                               parallelism="voting_parallel").fit(df)
        b = LightGBMClassifier(numIterations=10, numShards=1,
                               parallelism="data_parallel").fit(df)
        np.testing.assert_allclose(a.transform(df)["prediction"],
                                   b.transform(df)["prediction"])

    def test_voting_communicates_less(self):
        # histogram elements exchanged per split: voting must beat the
        # full-histogram reduce in the wide-feature regime
        from mmlspark_tpu.lightgbm.engine import comm_elements_per_split
        F, B = 2000, 256
        data = comm_elements_per_split(F, B, 20, "data")
        voting = comm_elements_per_split(F, B, 20, "voting")
        assert voting < data / 10, (voting, data)
        # and the crossover is where theory says: 2*(F + C·B·3) vs F·B·3
        assert comm_elements_per_split(28, B, 20, "voting") > \
            comm_elements_per_split(28, B, 20, "data")


class TestMulticlassDistributed:
    """K-class growth runs as one vmapped jitted call (review round 1 item 8
    tail) — verify the batched path on the sharded mesh, dense and COO."""

    def _multi(self, n=2000, seed=3):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, 8)).astype(np.float32)
        y = np.digitize(x[:, 0], [-0.5, 0.5]).astype(np.float32)
        return x, y

    @pytest.mark.slow
    def test_dense_sharded_matches_single(self):
        x, y = self._multi()
        df = DataFrame({"features": x, "label": y})
        m1 = LightGBMClassifier(objective="multiclass", numIterations=10,
                                numShards=1).fit(df)
        m8 = LightGBMClassifier(objective="multiclass", numIterations=10,
                                numShards=8).fit(df)
        np.testing.assert_allclose(m1.transform(df)["probability"],
                                   m8.transform(df)["probability"],
                                   atol=6e-3)
        assert (m8.transform(df)["prediction"] == y).mean() > 0.95

    @pytest.mark.slow
    def test_sparse_sharded_multiclass(self):
        from test_lightgbm_sparse import dense_to_coo
        x, _ = self._multi(seed=5)
        rng = np.random.default_rng(7)
        x[rng.random(x.shape) > 0.5] = 0.0
        y = np.digitize(x[:, 0], [-0.3, 0.3]).astype(np.float32)
        idx, val = dense_to_coo(x)
        df = DataFrame({"features_indices": idx, "features_values": val,
                        "label": y})
        m = LightGBMClassifier(objective="multiclass", numIterations=10,
                               numShards=8, minDataInLeaf=5).fit(df)
        assert (m.transform(df)["prediction"] == y).mean() > 0.9


class TestDistributedRanker:
    """Sharded lambdarank training: the reference repartitions by the
    grouping column so no query straddles a worker
    (``LightGBMRanker.scala:92-101``); here gradients are computed on the
    global (replicated) margin so straddling cannot corrupt pairs — the
    test asserts the sharded histogram path still reproduces single-device
    ranking quality, under group sizes that do NOT align with the shard
    count."""

    @pytest.mark.slow
    def test_ranker_sharded_matches_single(self):
        from test_benchmarks import TestRankerBenchmarks
        from mmlspark_tpu.lightgbm import LightGBMRanker
        from mmlspark_tpu.lightgbm.ranker_objective import ndcg_at_k
        x, rel, qid = TestRankerBenchmarks.msl_shaped(n_queries=60, seed=3)
        df = DataFrame({"features": x, "label": rel, "query": qid})
        kw = dict(groupCol="query", numIterations=25, numLeaves=15,
                  minDataInLeaf=5, seed=0)
        m1 = LightGBMRanker(numShards=1, **kw).fit(df)
        m8 = LightGBMRanker(numShards=8, **kw).fit(df)
        n1 = m1.evaluate_ndcg(df, k=10)
        n8 = m8.evaluate_ndcg(df, k=10)
        assert n1 > 0.8
        assert abs(n1 - n8) < 0.02, (n1, n8)
        # same global histograms → near-identical scores
        s1 = np.asarray(m1.transform(df)["prediction"])
        s8 = np.asarray(m8.transform(df)["prediction"])
        np.testing.assert_allclose(s1, s8, atol=5e-3)


class TestDistributedDart:
    @pytest.mark.slow
    def test_dart_sharded_matches_single_device(self):
        """Fused DART under the sharded histogram path: the drop-set /
        rescale machinery operates on globally-replicated score and
        delta buffers, so sharding must only change histogram summation
        order (statistical, not structural, differences)."""
        df = make_binary(n=1100)
        kw = dict(boostingType="dart", numIterations=20, numLeaves=15,
                  dropRate=0.25, skipDrop=0.3, seed=0)
        single = LightGBMClassifier(numShards=1, **kw).fit(df)
        sharded = LightGBMClassifier(numShards=8, **kw).fit(df)
        y = df["label"]
        auc_1 = roc_auc(y, single.transform(df)["probability"][:, 1])
        auc_8 = roc_auc(y, sharded.transform(df)["probability"][:, 1])
        assert auc_1 > 0.9
        assert abs(auc_1 - auc_8) < 0.02
        np.testing.assert_allclose(
            single.transform(df)["probability"][:, 1],
            sharded.transform(df)["probability"][:, 1], atol=5e-3)
