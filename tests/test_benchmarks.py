"""Accuracy-parity benchmark harness (reference
``core/test/benchmarks/Benchmarks.scala`` + the
``benchmarks_VerifyLightGBMClassifier.csv`` pattern): metric values are
regression-checked against committed CSVs with explicit tolerances.

Synthetic datasets are deterministic (seeded), so metric drift signals a
behavioral change in the engine — the same role the reference's blob
datasets play in its CI.
"""

import os

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.lightgbm import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.lightgbm.trainer import roc_auc
from mmlspark_tpu.testing import Benchmarks
from mmlspark_tpu.vw import VowpalWabbitClassifier, VowpalWabbitFeaturizer

RESOURCE_DIR = os.path.join(os.path.dirname(__file__), "resources",
                            "benchmarks")
REGEN = os.environ.get("MMLSPARK_TPU_REGEN_BENCHMARKS") == "1"


def tabular(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 12)).astype(np.float32)
    logits = x[:, 0] * 2 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3] + \
        np.sin(x[:, 4])
    y_cls = (logits + rng.normal(scale=0.4, size=n) > 0).astype(np.float32)
    y_reg = (logits + rng.normal(scale=0.2, size=n)).astype(np.float32)
    return x, y_cls, y_reg


class TestLightGBMBenchmarks:
    def test_classifier_auc(self):
        b = Benchmarks(os.path.join(RESOURCE_DIR,
                                    "benchmarks_LightGBMClassifier.csv"))
        x, y, _ = tabular()
        df = DataFrame({"features": x, "label": y})
        for boosting in ("gbdt", "goss", "dart", "rf"):
            kw = {"boostingType": boosting, "numIterations": 40,
                  "numShards": 1, "seed": 0}
            if boosting == "rf":
                kw.update(baggingFraction=0.8, baggingFreq=1)
            model = LightGBMClassifier(**kw).fit(df)
            auc = roc_auc(y, model.transform(df)["probability"][:, 1])
            b.add(f"synthetic.{boosting}", auc, 0.015)
        b.verify(regenerate=REGEN)

    def test_categorical_auc(self):
        b = Benchmarks(os.path.join(
            RESOURCE_DIR, "benchmarks_LightGBMCategorical.csv"))
        rng = np.random.default_rng(5)
        n = 2500
        cats = rng.integers(0, 16, size=n).astype(np.float32)
        num = rng.normal(size=(n, 3)).astype(np.float32)
        margin = (np.isin(cats, [1, 4, 7, 12]) * 2.0 - 1.0
                  + num[:, 0] + 0.3 * rng.normal(size=n))
        y = (margin > 0).astype(np.float32)
        x = np.concatenate([cats[:, None], num], axis=1)
        df = DataFrame({"features": x, "label": y})
        for mode, kw in (("set_split", {"categoricalSlotIndexes": [0]}),
                         ("ordinal", {})):
            model = LightGBMClassifier(numIterations=40, numLeaves=15,
                                       numShards=1, seed=0, **kw).fit(df)
            auc = roc_auc(y, model.transform(df)["probability"][:, 1])
            b.add(f"categorical.{mode}", auc, 0.015)
        b.verify(regenerate=REGEN)

    def test_regressor_rmse(self):
        b = Benchmarks(os.path.join(RESOURCE_DIR,
                                    "benchmarks_LightGBMRegressor.csv"))
        x, _, y = tabular(seed=1)
        df = DataFrame({"features": x, "label": y})
        for objective in ("regression", "regression_l1", "huber"):
            model = LightGBMRegressor(
                objective=objective, numIterations=40, numShards=1,
                seed=0).fit(df)
            pred = model.transform(df)["prediction"]
            rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
            b.add(f"synthetic.{objective}", rmse, 0.1)
        b.verify(regenerate=REGEN)


class TestTrainBenchmarks:
    """Reference benchmarks_VerifyTrainClassifier /
    benchmarks_VerifyTuneHyperparameters analogs: the auto-featurizing
    trainer across inner learners, and the random-search tuner."""

    def test_train_classifier_learners(self):
        from mmlspark_tpu.train import LogisticRegression, TrainClassifier
        b = Benchmarks(os.path.join(RESOURCE_DIR,
                                    "benchmarks_TrainClassifier.csv"))
        rng = np.random.default_rng(9)
        n = 1200
        age = rng.normal(40, 12, n).astype(np.float32)
        city = rng.choice(["a", "b", "c"], size=n).astype(object)
        score = rng.normal(size=n).astype(np.float32)
        y = ((age > 40) ^ (city == "b") ^ (score > 0.8)).astype(np.float32)
        df = DataFrame({"age": age, "city": city, "score": score,
                        "label": y})
        learners = {
            "lightgbm": LightGBMClassifier(
                numIterations=30, numLeaves=15, minDataInLeaf=5, seed=0),
            "lightgbm_rf": LightGBMClassifier(
                boostingType="rf", baggingFraction=0.8, baggingFreq=1,
                numIterations=30, numLeaves=15, minDataInLeaf=5, seed=0),
            "logistic": LogisticRegression(maxIter=60),
        }
        for name, est in learners.items():
            model = TrainClassifier(model=est, labelCol="label").fit(df)
            pred = np.asarray(model.transform(df)["scored_labels"])
            acc = float((pred == y).mean())
            b.add(f"mixed.{name}", acc, 0.02)
        b.verify(regenerate=REGEN)

    @staticmethod
    def _split(x, y):
        """Deterministic 75/25 split shared by every real-data
        benchmark (one convention, one place)."""
        rng = np.random.default_rng(13)
        order = rng.permutation(len(y))
        cut = int(len(y) * 0.75)
        tr, te = order[:cut], order[cut:]
        return x[tr], y[tr], x[te], y[te]

    @classmethod
    def _real_datasets(cls):
        """sklearn's bundled REAL datasets (review round 3 Weak #4: the
        matrix was synthetic outside the parity file; the reference
        verifies 12 real datasets in
        ``benchmarks_VerifyTrainClassifier.csv``). Deterministic 75/25
        split; held-out accuracy is the recorded metric."""
        from sklearn.datasets import load_breast_cancer, load_digits, \
            load_wine
        out = {}
        for name, loader in (("breast_cancer", load_breast_cancer),
                             ("digits", load_digits),
                             ("wine", load_wine)):
            d = loader()
            x = d.data.astype(np.float32)
            y = d.target.astype(np.float32)
            if len(y) > 800:
                # cap CI cost: the XLA:CPU scatter histogram makes the
                # 10-class digits fit ~10x a binary one (the TPU path
                # runs the Pallas kernel instead); 800 real rows keep
                # the regression signal at a fraction of the time
                keep = np.random.default_rng(29).permutation(len(y))[:800]
                x, y = x[keep], y[keep]
            out[name] = cls._split(x, y)
        return out

    @pytest.mark.slow
    def test_train_classifier_real_datasets(self):
        from mmlspark_tpu.train import LogisticRegression, TrainClassifier
        b = Benchmarks(os.path.join(
            RESOURCE_DIR, "benchmarks_TrainClassifierRealData.csv"))
        for ds, (xtr, ytr, xte, yte) in self._real_datasets().items():
            train = DataFrame({"features": xtr, "label": ytr})
            test = DataFrame({"features": xte, "label": yte})
            learners = {
                "lightgbm": LightGBMClassifier(
                    numIterations=40, numLeaves=15, minDataInLeaf=5,
                    seed=0),
                "logistic": LogisticRegression(maxIter=150),
            }
            for lname, est in learners.items():
                model = TrainClassifier(model=est,
                                        labelCol="label").fit(train)
                pred = np.asarray(model.transform(test)["scored_labels"])
                b.add(f"{ds}.{lname}", float((pred == yte).mean()), 0.02)
        b.verify(regenerate=REGEN)

    def test_train_regressor_real_dataset(self):
        from sklearn.datasets import load_diabetes

        from mmlspark_tpu.train import TrainRegressor
        b = Benchmarks(os.path.join(
            RESOURCE_DIR, "benchmarks_TrainRegressorRealData.csv"))
        d = load_diabetes()
        xtr, ytr, xte, yte = self._split(d.data.astype(np.float32),
                                         d.target.astype(np.float32))
        model = TrainRegressor(
            model=LightGBMRegressor(numIterations=60, numLeaves=7,
                                    minDataInLeaf=10, seed=0),
            labelCol="label").fit(
            DataFrame({"features": xtr, "label": ytr}))
        pred = np.asarray(model.transform(
            DataFrame({"features": xte, "label": yte}))["scores"])
        rmse = float(np.sqrt(np.mean((pred - yte) ** 2)))
        b.add("diabetes.lightgbm_rmse", rmse, 2.0)
        b.verify(regenerate=REGEN)

    def test_tune_hyperparameters_real_datasets(self):
        from mmlspark_tpu.automl import (HyperparamBuilder,
                                         IntRangeHyperParam,
                                         TuneHyperparameters)
        b = Benchmarks(os.path.join(
            RESOURCE_DIR, "benchmarks_TuneHyperparametersRealData.csv"))
        for ds, (xtr, ytr, _, _) in self._real_datasets().items():
            df = DataFrame({"features": xtr, "label": ytr})
            est = LightGBMClassifier(numIterations=15, minDataInLeaf=5,
                                     seed=0)
            space = HyperparamBuilder().addHyperparam(
                est, "numLeaves", IntRangeHyperParam(4, 32)).build()
            tuned = TuneHyperparameters(
                models=[est], paramSpace=space, numFolds=3, numRuns=4,
                evaluationMetric="accuracy", labelCol="label").fit(df)
            b.add(f"{ds}.best_accuracy",
                  float(tuned.get("bestMetric")), 0.02)
        b.verify(regenerate=REGEN)

    def test_tune_hyperparameters_accuracy(self):
        from mmlspark_tpu.automl import (HyperparamBuilder,
                                         IntRangeHyperParam,
                                         TuneHyperparameters)
        b = Benchmarks(os.path.join(
            RESOURCE_DIR, "benchmarks_TuneHyperparameters.csv"))
        x, y, _ = tabular(n=800, seed=3)
        df = DataFrame({"features": x, "label": y})
        est = LightGBMClassifier(numIterations=15, minDataInLeaf=5,
                                 seed=0)
        space = HyperparamBuilder().addHyperparam(
            est, "numLeaves", IntRangeHyperParam(4, 32)).build()
        tuned = TuneHyperparameters(
            models=[est], paramSpace=space, numFolds=3, numRuns=4,
            evaluationMetric="accuracy", labelCol="label").fit(df)
        b.add("synthetic.best_accuracy",
              float(tuned.get("bestMetric")), 0.02)
        b.verify(regenerate=REGEN)


class TestVWBenchmarks:
    def test_classifier_auc(self):
        b = Benchmarks(os.path.join(
            RESOURCE_DIR, "benchmarks_VowpalWabbitClassifier.csv"))
        rng = np.random.default_rng(2)
        n = 2000
        x = rng.normal(size=(n, 10)).astype(np.float32)
        y = ((x[:, 0] - x[:, 1] + 0.5 * x[:, 2]
              + rng.normal(scale=0.3, size=n)) > 0).astype(np.float32)
        df = DataFrame({"features": x, "label": y})
        for args, tag in [("", "default"), ("--l1 1e-7", "l1"),
                          ("-l 0.2 --passes 4", "lr_passes")]:
            model = VowpalWabbitClassifier(
                args=args, numPasses=4, batchSize=128,
                numShards=1).fit(df)
            auc = roc_auc(y, model.transform(df)["probability"][:, 1])
            b.add(f"synthetic.{tag}", auc, 0.02)
        b.verify(regenerate=REGEN)


class TestSparseGBDTBenchmarks:
    @pytest.mark.slow
    def test_sparse_classifier_auc(self):
        from test_lightgbm_sparse import dense_to_coo
        b = Benchmarks(os.path.join(
            RESOURCE_DIR, "benchmarks_LightGBMSparse.csv"))
        rng = np.random.default_rng(5)
        x = rng.normal(size=(1500, 16)).astype(np.float32)
        x[rng.random(x.shape) > 0.4] = 0.0
        y = ((x[:, 0] * 2 - x[:, 1] + x[:, 2]
              + rng.normal(scale=0.3, size=1500)) > 0).astype(np.float32)
        idx, val = dense_to_coo(x)
        df = DataFrame({"features_indices": idx, "features_values": val,
                        "label": y})
        for shards, tag in [(1, "single"), (8, "data_parallel")]:
            m = LightGBMClassifier(numIterations=30, numLeaves=15,
                                   minDataInLeaf=5, numShards=shards,
                                   seed=0).fit(df)
            auc = roc_auc(y, m.transform(df)["probability"][:, 1])
            b.add(f"sparse.{tag}", auc, 0.015)
        m = LightGBMClassifier(numIterations=30, numLeaves=15,
                               minDataInLeaf=5, numShards=8,
                               parallelism="voting_parallel", topK=6,
                               seed=0).fit(df)
        auc = roc_auc(y, m.transform(df)["probability"][:, 1])
        b.add("sparse.voting_parallel", auc, 0.02)
        b.verify(regenerate=REGEN)


class TestLinearBenchmarks:
    def test_linear_family(self):
        from mmlspark_tpu.train import LinearRegression, LogisticRegression
        b = Benchmarks(os.path.join(RESOURCE_DIR,
                                    "benchmarks_LinearLearners.csv"))
        x, y_cls, y_reg = tabular(seed=3)
        df_c = DataFrame({"features": x, "label": y_cls})
        auc = roc_auc(y_cls, LogisticRegression(maxIter=40).fit(df_c)
                      .transform(df_c)["probability"][:, 1])
        b.add("logistic.auc", auc, 0.01)
        df_r = DataFrame({"features": x, "label": y_reg})
        pred = LinearRegression().fit(df_r).transform(df_r)["prediction"]
        b.add("ridge.rmse", float(np.sqrt(np.mean((pred - y_reg) ** 2))),
              0.05)
        rng = np.random.default_rng(4)
        y3 = np.digitize(x[:, 0] + 0.3 * x[:, 1],
                         [-0.6, 0.6]).astype(np.float32)
        df_m = DataFrame({"features": x, "label": y3})
        m = LogisticRegression(maxIter=300).fit(df_m)
        acc = float((m.transform(df_m)["prediction"] == y3).mean())
        b.add("softmax.accuracy", acc, 0.01)
        b.verify(regenerate=REGEN)


class TestRankerBenchmarks:
    """MSLR-shaped ranking benchmark (BASELINE configs[2] names
    LightGBMRanker on MSLR-WEB30K, which cannot be fetched zero-egress):
    variable-size query groups with graded 0-4 relevance driven by a
    latent linear utility — the ndcg@k values regression-check the whole
    lambdarank + NDCG chain."""

    @staticmethod
    def msl_shaped(n_queries=80, f=32, seed=12):
        rng = np.random.default_rng(seed)
        w_true = rng.normal(size=f).astype(np.float32)
        feats, rels, qids = [], [], []
        for q in range(n_queries):
            sz = int(rng.integers(8, 40))
            xq = rng.normal(size=(sz, f)).astype(np.float32)
            util = xq @ w_true + rng.normal(scale=2.0, size=sz)
            cuts = np.quantile(util, [0.5, 0.75, 0.9, 0.97])
            rels.append(np.digitize(util, cuts).astype(np.float32))
            feats.append(xq)
            qids.append(np.full(sz, q, np.int64))
        return (np.concatenate(feats), np.concatenate(rels),
                np.concatenate(qids))

    def test_ranker_ndcg(self):
        from mmlspark_tpu.lightgbm import LightGBMRanker
        b = Benchmarks(os.path.join(RESOURCE_DIR,
                                    "benchmarks_LightGBMRanker.csv"))
        x, rel, qid = self.msl_shaped()
        df = DataFrame({"features": x, "label": rel, "query": qid})
        m = LightGBMRanker(groupCol="query", numIterations=40,
                           numLeaves=15, minDataInLeaf=5, numShards=1,
                           seed=0).fit(df)
        for k in (1, 3, 5, 10):
            b.add(f"mslr_shaped.ndcg@{k}", m.evaluate_ndcg(df, k=k), 0.02)
        b.verify(regenerate=REGEN)


def test_diff_timed_discards_noise():
    """A non-positive long-minus-short delta must come back None —
    clamping it once published absurd MFU numbers."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    seq = iter([0.5, 0.5, 0.4, 0.4])   # long runs FASTER than short

    def run_loop(n):
        return next(seq)

    assert bench._diff_timed(run_loop, 10, 2) is None

    # and a sane sequence divides over iters
    seq2 = iter([0.1, 0.1, 1.1, 1.1])
    per = bench._diff_timed(lambda n: next(seq2), 10, 2)
    assert per is not None and abs(per - 0.1) < 1e-9
