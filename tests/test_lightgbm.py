import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame, load_stage
from mmlspark_tpu.lightgbm import (Booster, LightGBMClassificationModel,
                                   LightGBMClassifier, LightGBMRanker,
                                   LightGBMRegressor, roc_auc)


def classification_df(n=400, seed=0):
    from sklearn.datasets import make_classification
    X, y = make_classification(n_samples=n, n_features=10, n_informative=5,
                               random_state=seed)
    return DataFrame({"features": X.astype(np.float32),
                      "label": y.astype(np.float32)})


def small_params():
    return dict(numIterations=20, numLeaves=7, minDataInLeaf=5,
                learningRate=0.2)


@pytest.fixture(scope="module")
def binary_model_and_df():
    df = classification_df()
    model = LightGBMClassifier(**small_params()).fit(df)
    return model, df


def test_binary_classification_auc(binary_model_and_df):
    model, df = binary_model_and_df
    out = model.transform(df)
    assert out["probability"].shape == (400, 2)
    assert out["rawPrediction"].shape == (400, 2)
    auc = roc_auc(np.asarray(df["label"]), out["probability"][:, 1])
    assert auc > 0.95, auc
    acc = (out["prediction"] == df["label"]).mean()
    assert acc > 0.85


def test_save_load_roundtrip(binary_model_and_df, tmp_path):
    model, df = binary_model_and_df
    expected = model.transform(df)["probability"]
    model.save(str(tmp_path / "m"))
    loaded = load_stage(str(tmp_path / "m"))
    np.testing.assert_allclose(loaded.transform(df)["probability"], expected,
                               rtol=1e-5)


def test_native_model_string_roundtrip(binary_model_and_df, tmp_path):
    model, df = binary_model_and_df
    x = df["features"]
    expected = model.booster.raw_scores(x)
    text = model.get_native_model_string()
    assert "tree" in text and "split_feature=" in text
    re = Booster.load_native(text)
    got = re.raw_scores(x)
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_feature_importances(binary_model_and_df):
    model, _ = binary_model_and_df
    imp_split = np.asarray(model.get_feature_importances("split"))
    imp_gain = np.asarray(model.get_feature_importances("gain"))
    assert imp_split.sum() > 0 and imp_gain.sum() > 0
    with pytest.raises(ValueError):
        model.get_feature_importances("banana")


def test_leaf_prediction_and_shap(binary_model_and_df):
    model, df = binary_model_and_df
    small = df.limit(10)
    m = model.copy({"leafPredictionCol": "leaves",
                    "featuresShapCol": "shap"})
    out = m.transform(small)
    assert out["leaves"].shape == (10, model.booster.num_trees)
    shap = out["shap"]
    assert shap.shape == (10, 11)
    raw = model.booster.raw_scores(small["features"])
    np.testing.assert_allclose(shap.sum(axis=1), raw, rtol=1e-3, atol=1e-3)


def test_multiclass():
    from sklearn.datasets import load_iris
    X, y = load_iris(return_X_y=True)
    df = DataFrame({"features": X.astype(np.float32),
                    "label": y.astype(np.float32)})
    model = LightGBMClassifier(objective="multiclass",
                               **small_params()).fit(df)
    out = model.transform(df)
    assert out["probability"].shape == (150, 3)
    np.testing.assert_allclose(out["probability"].sum(axis=1), 1.0,
                               rtol=1e-5)
    assert (out["prediction"] == y).mean() > 0.9


def test_regression_modes():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 5)).astype(np.float32)
    y = (X[:, 0] * 3 + X[:, 1] ** 2 + rng.normal(0, 0.1, 300)).astype(
        np.float32)
    df = DataFrame({"features": X, "label": y})
    for objective in ["regression", "regression_l1", "huber", "quantile"]:
        model = LightGBMRegressor(objective=objective,
                                  **small_params()).fit(df)
        pred = model.transform(df)["prediction"]
        assert np.isfinite(pred).all()
    model = LightGBMRegressor(objective="regression",
                              **small_params()).fit(df)
    rmse = float(np.sqrt(np.mean((model.transform(df)["prediction"] - y) ** 2)))
    assert rmse < np.std(y), (rmse, np.std(y))


def test_poisson_positive_output():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 3)).astype(np.float32)
    y = rng.poisson(np.exp(0.5 * X[:, 0] + 1)).astype(np.float32)
    df = DataFrame({"features": X, "label": y})
    model = LightGBMRegressor(objective="poisson", **small_params()).fit(df)
    assert (model.transform(df)["prediction"] > 0).all()


def test_boosting_modes():
    df = classification_df(300)
    y = np.asarray(df["label"])
    for mode in ["gbdt", "goss", "dart", "rf"]:
        params = small_params()
        if mode == "rf":
            params.update(baggingFraction=0.8, baggingFreq=1)
        model = LightGBMClassifier(boostingType=mode, **params).fit(df)
        out = model.transform(df)
        auc = roc_auc(y, out["probability"][:, 1])
        assert auc > 0.8, (mode, auc)


def test_dart_multiclass_and_roundtrip(tmp_path):
    from sklearn.datasets import load_iris
    X, y = load_iris(return_X_y=True)
    df = DataFrame({"features": X.astype(np.float32),
                    "label": y.astype(np.float32)})
    model = LightGBMClassifier(objective="multiclass", boostingType="dart",
                               skipDrop=0.0, dropRate=0.3,
                               **small_params()).fit(df)
    out = model.transform(df)
    assert (out["prediction"] == y).mean() > 0.85
    # dart tree weights must survive save/load (baked into text model)
    expected = out["probability"]
    model.save(str(tmp_path / "m"))
    loaded = load_stage(str(tmp_path / "m"))
    np.testing.assert_allclose(loaded.transform(df)["probability"], expected,
                               rtol=1e-4, atol=1e-5)


def test_rf_native_roundtrip():
    df = classification_df(300)
    model = LightGBMClassifier(boostingType="rf", baggingFraction=0.8,
                               baggingFreq=1, **small_params()).fit(df)
    expected = model.transform(df)["probability"]
    text = model.get_native_model_string()
    assert "average_output" in text
    re = Booster.load_native(text)
    got = np.asarray(re.transform_scores(re.raw_scores(df["features"])))
    np.testing.assert_allclose(got, expected[:, 1], rtol=1e-4, atol=1e-5)


def test_rf_trees_are_not_shrunk():
    """LightGBM rf semantics (rf.hpp): averaged trees carry NO
    learning-rate shrinkage. A shrunk average cannot move the init
    log-odds, so predicted probabilities collapse toward the class
    prior — which AUC-based checks cannot see (ranking is
    scale-invariant). Guard the margin scale directly."""
    df = classification_df(400)
    y = np.asarray(df["label"])
    model = LightGBMClassifier(boostingType="rf", baggingFraction=0.8,
                               baggingFreq=1, learningRate=0.1,
                               numIterations=20, numLeaves=15,
                               minDataInLeaf=5).fit(df)
    prob = np.asarray(model.transform(df)["probability"])[:, 1]
    # separable-ish data: confident probabilities on both sides, and
    # accuracy well above the class prior
    assert prob.max() > 0.8 and prob.min() < 0.2, (prob.min(), prob.max())
    acc = float(((prob > 0.5) == (y > 0)).mean())
    assert acc > 0.9, acc


def test_early_stopping_and_validation():
    df = classification_df(500)
    rng = np.random.default_rng(0)
    flag = rng.random(500) < 0.25
    df = df.with_column("isVal", flag)
    model = LightGBMClassifier(validationIndicatorCol="isVal",
                               earlyStoppingRound=5,
                               numIterations=200, numLeaves=31,
                               minDataInLeaf=5, learningRate=0.3).fit(df)
    assert model.booster.best_iteration >= 0
    # stopped before all 200 iterations
    assert model.booster.num_trees < 200


def test_weight_column():
    df = classification_df(300)
    w = np.where(np.asarray(df["label"]) > 0, 10.0, 1.0).astype(np.float32)
    df = df.with_column("w", w)
    model = LightGBMClassifier(weightCol="w", **small_params()).fit(df)
    out = model.transform(df)
    # heavily weighting positives should push mean probability up
    base = LightGBMClassifier(**small_params()).fit(df).transform(df)
    assert out["probability"][:, 1].mean() > base["probability"][:, 1].mean()


def test_batch_training_continuation():
    df = classification_df(400)
    model = LightGBMClassifier(numBatches=2, **small_params()).fit(df)
    # 2 batches x 20 iterations
    assert model.booster.num_trees == 40
    out = model.transform(df)
    assert roc_auc(np.asarray(df["label"]), out["probability"][:, 1]) > 0.9


def test_custom_fobj():
    df = classification_df(300)

    def fobj(scores, y, w):
        import jax
        p = jax.nn.sigmoid(scores)
        return (p - y) * w, p * (1 - p) * w

    model = LightGBMClassifier(fobj=fobj, boostFromAverage=False,
                               **small_params()).fit(df)
    out = model.transform(df)
    assert roc_auc(np.asarray(df["label"]), out["probability"][:, 1]) > 0.9


def test_ranker_ndcg():
    rng = np.random.default_rng(0)
    n_queries, docs = 40, 12
    rows = n_queries * docs
    X = rng.normal(size=(rows, 6)).astype(np.float32)
    rel = np.clip((X[:, 0] * 2 + rng.normal(0, 0.5, rows)).round(), 0,
                  3).astype(np.float32)
    qid = np.repeat(np.arange(n_queries), docs)
    df = DataFrame({"features": X, "label": rel, "query": qid})
    model = LightGBMRanker(groupCol="query", numIterations=30, numLeaves=7,
                           minDataInLeaf=3, learningRate=0.2).fit(df)
    ndcg = model.evaluate_ndcg(df, k=5)
    assert ndcg > 0.75, ndcg


def test_missing_values_handled():
    df = classification_df(300)
    X = np.asarray(df["features"]).copy()
    X[::7, 0] = np.nan
    df = DataFrame({"features": X, "label": df["label"]})
    model = LightGBMClassifier(**small_params()).fit(df)
    out = model.transform(df)
    assert np.isfinite(out["probability"]).all()


def test_hot_loop_no_bulk_host_pulls():
    """De-synced boosting loop (review round 1 weak #5): GOSS sampling and the
    auc/rmse eval metrics run on device, so no O(n) device->host copy
    happens inside the iteration loop, and eval_freq thins the scalar
    reads."""
    from mmlspark_tpu.lightgbm.trainer import TrainConfig, train
    rng = np.random.default_rng(0)
    x = rng.normal(size=(600, 8)).astype(np.float32)
    y = (x[:, 0] - x[:, 1] + rng.normal(scale=0.3, size=600) > 0).astype(
        np.float32)
    xv = rng.normal(size=(200, 8)).astype(np.float32)
    yv = (xv[:, 0] - xv[:, 1] > 0).astype(np.float32)
    cfg = TrainConfig(objective="binary", num_iterations=12,
                      boosting_type="goss", num_leaves=7,
                      min_data_in_leaf=5, eval_freq=4)
    res = train(x, y, None, cfg, valid=(xv, yv, None))
    assert res.host_pulls_bulk == 0
    # evals at iterations 3, 7, 11 only (cadence 4 over 12 iterations)
    assert res.host_pulls_scalar == 3
    assert [e["iteration"] for e in res.evals] == [3, 7, 11]


def test_goss_on_device_learns():
    df = classification_df(500, seed=3)
    model = LightGBMClassifier(boostingType="goss", **small_params()).fit(df)
    out = model.transform(df)
    assert roc_auc(df["label"], out["probability"][:, 1]) > 0.9


def test_multiclassova_objective():
    """One-vs-all multiclass (LightGBM multiclassova): per-class sigmoid
    models; accuracy comparable to softmax on separable data and
    probabilities are per-class sigmoids (not a normalized softmax)."""
    rng = np.random.default_rng(4)
    n = 900
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (np.argmax(x[:, :3], axis=1)).astype(np.float32)
    df = DataFrame({"features": x, "label": y})
    m = LightGBMClassifier(objective="multiclassova", numIterations=25,
                           numLeaves=15, minDataInLeaf=5).fit(df)
    out = m.transform(df)
    acc = float((np.asarray(out["prediction"]) == y).mean())
    assert acc > 0.9, acc
    probs = np.asarray(out["probability"])
    # unnormalized per-class sigmoids: rows need not sum to 1
    assert probs.shape == (n, 3)
    assert (probs > 0).all() and (probs < 1).all()


def test_cross_entropy_objectives():
    """Probabilistic labels in [0,1] (LightGBM xentropy/xentlambda):
    predictions calibrate to the label probabilities."""
    from mmlspark_tpu.lightgbm import LightGBMRegressor
    rng = np.random.default_rng(5)
    n = 1500
    x = rng.normal(size=(n, 3)).astype(np.float32)
    p_true = 1.0 / (1.0 + np.exp(-(1.5 * x[:, 0] - x[:, 1])))
    y = p_true.astype(np.float32)  # soft labels
    df = DataFrame({"features": x, "label": y})
    for obj in ("cross_entropy", "cross_entropy_lambda"):
        m = LightGBMRegressor(objective=obj, numIterations=60,
                              numLeaves=15, minDataInLeaf=5).fit(df)
        pred = np.asarray(m.transform(df)["prediction"])
        assert (pred > 0).all()
        if obj == "cross_entropy_lambda":
            # native ConvertOutput parity: prediction is the intensity
            # lambda; the probability is 1 - exp(-lambda)
            pred = 1.0 - np.exp(-pred)
        assert (pred < 1).all()
        mae = float(np.mean(np.abs(pred - p_true)))
        assert mae < 0.06, (obj, mae)


def test_multiclassova_native_roundtrip():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(400, 4)).astype(np.float32)
    y = np.argmax(x[:, :3], axis=1).astype(np.float32)
    df = DataFrame({"features": x, "label": y})
    m = LightGBMClassifier(objective="multiclassova", numIterations=10,
                           numLeaves=7, minDataInLeaf=5).fit(df)
    text = m.get_native_model_string()
    assert "multiclassova num_class:3" in text
    re = Booster.load_native(text)
    np.testing.assert_allclose(re.raw_scores(x), m.booster.raw_scores(x),
                               rtol=1e-4, atol=1e-5)


def test_multiclassova_validation_early_stopping():
    """ova + validation used to crash (no default metric, K-column
    scores fed to rmse); ova_logloss now drives early stopping."""
    rng = np.random.default_rng(8)
    n = 600
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.argmax(x[:, :3], axis=1).astype(np.float32)
    isval = (np.arange(n) % 4 == 0)
    df = DataFrame({"features": x, "label": y, "isVal": isval})
    m = LightGBMClassifier(objective="multiclassova", numIterations=40,
                           numLeaves=7, minDataInLeaf=5,
                           validationIndicatorCol="isVal",
                           earlyStoppingRound=3).fit(df)
    out = m.transform(df)
    assert float((np.asarray(out["prediction"]) == y).mean()) > 0.85
    # alias canonicalization: 'ova' saves a loadable header
    m2 = LightGBMClassifier(objective="ova", numIterations=5,
                            numLeaves=7, minDataInLeaf=5).fit(
        DataFrame({"features": x, "label": y}))
    text = m2.get_native_model_string()
    assert "multiclassova num_class:3" in text


def test_scan_chunking_is_equivalent():
    """scanChunk fuses k iterations into one dispatch; results must be
    IDENTICAL to per-iteration dispatch (same host RNG order, same
    fold_in keys) for gbdt, goss, and rf."""
    df = classification_df(300, seed=3)
    for mode, extra in (("gbdt", {}), ("goss", {}),
                        ("rf", {"baggingFraction": 0.8, "baggingFreq": 1}),
                        ("gbdt", {"featureFraction": 0.6})):
        kw = dict(numIterations=11, numLeaves=7, minDataInLeaf=5,
                  boostingType=mode, seed=7, **extra)
        p1 = LightGBMClassifier(scanChunk=1, **kw).fit(df) \
            .transform(df)["probability"]
        p4 = LightGBMClassifier(scanChunk=4, **kw).fit(df) \
            .transform(df)["probability"]
        np.testing.assert_allclose(np.asarray(p4), np.asarray(p1),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"{mode} {extra}")


class TestDeviceSideDart:
    """Fused DART (one dispatch per iteration, device delta buffers) must
    reproduce the stepwise semantics oracle bit-for-bit: both paths draw
    the same host RNG sequence and apply the same float32 ops in the same
    order."""

    @staticmethod
    def _data(n=400, f=8, seed=7, classes=2):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, f)).astype(np.float32)
        margin = x[:, 0] * 2 - x[:, 1] + 0.4 * rng.normal(size=n)
        if classes == 2:
            y = (margin > 0).astype(np.float32)
        else:
            y = np.digitize(margin, [-0.7, 0.7]).astype(np.float32)
        return x, y

    def _train(self, x, y, mode, **kw):
        from mmlspark_tpu.lightgbm.trainer import TrainConfig, train
        cfg = TrainConfig(objective=kw.pop("objective", "binary"),
                          boosting_type="dart", dart_mode=mode,
                          num_iterations=30, num_leaves=7,
                          min_data_in_leaf=5, drop_rate=0.3, skip_drop=0.3,
                          max_drop=5, seed=11, **kw)
        return train(x, y, None, cfg)

    def _assert_same(self, a, b, x):
        for fld in ("leaf_value", "feature", "left", "right", "num_nodes"):
            np.testing.assert_array_equal(a.booster.arrays[fld],
                                          b.booster.arrays[fld],
                                          err_msg=fld)
        np.testing.assert_array_equal(a.booster.tree_weights,
                                      b.booster.tree_weights)
        np.testing.assert_array_equal(np.asarray(a.booster.raw_scores(x)),
                                      np.asarray(b.booster.raw_scores(x)))

    def test_bit_match_binary(self):
        x, y = self._data()
        fused = self._train(x, y, "fused", scan_chunk=1)
        stepwise = self._train(x, y, "stepwise")
        self._assert_same(fused, stepwise, x)

    def test_bit_match_multiclass(self):
        x, y = self._data(classes=3)
        fused = self._train(x, y, "fused", scan_chunk=1,
                            objective="multiclass", num_class=3)
        stepwise = self._train(x, y, "stepwise", objective="multiclass",
                               num_class=3)
        self._assert_same(fused, stepwise, x)

    def test_bit_match_chunked(self):
        """Scan-chunked dart (k iterations per dispatch) equals both the
        per-iteration fused path and the stepwise oracle."""
        x, y = self._data()
        chunked = self._train(x, y, "fused", scan_chunk=8)
        stepwise = self._train(x, y, "stepwise")
        self._assert_same(chunked, stepwise, x)

    def test_bit_match_with_bagging_and_feature_fraction(self):
        x, y = self._data()
        kw = dict(bagging_fraction=0.7, bagging_freq=2,
                  feature_fraction=0.6)
        fused = self._train(x, y, "fused", scan_chunk=4, **kw)
        stepwise = self._train(x, y, "stepwise", **kw)
        self._assert_same(fused, stepwise, x)

    def test_no_bulk_host_pulls_and_eval(self):
        """Fused dart joins gbdt's dispatch discipline: zero O(n) pulls
        in-loop even with a validation set observed per iteration."""
        x, y = self._data()
        xv, yv = self._data(seed=9)
        from mmlspark_tpu.lightgbm.trainer import TrainConfig, train
        cfg = TrainConfig(objective="binary", boosting_type="dart",
                          num_iterations=12, num_leaves=7,
                          min_data_in_leaf=5, drop_rate=0.3,
                          skip_drop=0.3, seed=11, eval_freq=4)
        res = train(x, y, None, cfg, valid=(xv, yv, None))
        assert res.host_pulls_bulk == 0
        assert [e["iteration"] for e in res.evals] == [3, 7, 11]


class TestLongTailParams:
    """Reference param-surface long tail (LightGBMParams.scala):
    improvementTolerance, maxDeltaStep, pos/negBaggingFraction,
    startIteration, maxBinByFeature."""

    def test_max_delta_step_caps_leaf_values(self):
        df = classification_df(500)
        kw = dict(numIterations=10, numLeaves=15, minDataInLeaf=5,
                  numShards=1, seed=0)
        m = LightGBMClassifier(maxDeltaStep=0.01, **kw).fit(df)
        leaves = np.asarray(m.booster.arrays["leaf_value"])
        # leaf values carry learning_rate (0.1) shrinkage on top
        assert np.abs(leaves).max() <= 0.01 * 0.1 + 1e-6
        m2 = LightGBMClassifier(**kw).fit(df)
        assert np.abs(np.asarray(
            m2.booster.arrays["leaf_value"])).max() > 0.001 + 1e-6

    def test_improvement_tolerance_stops_earlier(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(800, 8)).astype(np.float32)
        y = (x[:, 0] + rng.normal(scale=1.5, size=800) > 0).astype(
            np.float32)
        flag = np.zeros(800, bool)
        flag[::4] = True
        df = DataFrame({"features": x, "label": y, "valid": flag})
        kw = dict(numIterations=60, numLeaves=7, minDataInLeaf=5,
                  numShards=1, seed=0, validationIndicatorCol="valid",
                  earlyStoppingRound=5)
        m_tol = LightGBMClassifier(improvementTolerance=0.05, **kw).fit(df)
        m_no = LightGBMClassifier(**kw).fit(df)
        it_tol = m_tol.booster.best_iteration
        it_no = m_no.booster.best_iteration
        assert it_tol >= 0
        assert it_tol <= it_no or it_no < 0

    def test_stratified_bagging(self):
        rng = np.random.default_rng(5)
        x = rng.normal(size=(1500, 6)).astype(np.float32)
        y = (rng.random(1500) < 0.1).astype(np.float32)  # rare positives
        df = DataFrame({"features": x, "label": y})
        m = LightGBMClassifier(numIterations=5, numLeaves=7,
                               minDataInLeaf=2, numShards=1, seed=0,
                               baggingFreq=1, posBaggingFraction=1.0,
                               negBaggingFraction=0.2).fit(df)
        # root node_count reflects the stratified sample: ~all positives
        # + ~20% negatives
        counts = np.asarray(m.booster.arrays["node_count"])[:, 0]
        expect = y.sum() + 0.2 * (1500 - y.sum())
        assert abs(counts.mean() - expect) < 0.15 * expect, (
            counts.mean(), expect)

    def test_start_iteration_prediction(self):
        df = classification_df(500)
        m = LightGBMClassifier(numIterations=12, numLeaves=7,
                               minDataInLeaf=5, numShards=1,
                               seed=0).fit(df)
        x = np.asarray(df["features"])
        full = np.asarray(m.booster.raw_scores(x))
        head = np.asarray(m.booster.raw_scores(x, num_iteration=4))
        tail = np.asarray(m.booster.raw_scores(x, start_iteration=4))
        init = float(m.booster.init_score)
        np.testing.assert_allclose(head + tail - init, full, atol=1e-5)
        # the model param routes through transform
        m.set("startIteration", 4)
        p_tail = np.asarray(m.transform(df)["probability"][:, 1])
        np.testing.assert_allclose(
            p_tail, np.asarray(m.booster.transform_scores(tail)),
            atol=1e-6)

    def test_max_bin_by_feature(self):
        rng = np.random.default_rng(7)
        x = rng.normal(size=(800, 3)).astype(np.float32)
        y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
        df = DataFrame({"features": x, "label": y})
        m = LightGBMClassifier(numIterations=10, numLeaves=15,
                               minDataInLeaf=5, numShards=1, seed=0,
                               maxBinByFeature=[2, 0, 0]).fit(df)
        # feature 0 has a 2-bin budget → only one distinct threshold
        arr = m.booster.arrays
        f0_splits = arr["threshold"][(arr["feature"] == 0)
                                     & ~arr["is_leaf"]
                                     & (arr["left"] >= 0)]
        assert len(set(np.round(f0_splits, 5).tolist())) <= 1
        with pytest.raises(ValueError, match="maxBinByFeature"):
            LightGBMClassifier(maxBinByFeature=[2],
                               numIterations=2).fit(df)

    def test_xgboost_dart_mode_raises(self):
        df = classification_df(300)
        with pytest.raises(NotImplementedError, match="xgboostDartMode"):
            LightGBMClassifier(boostingType="dart",
                               xgboostDartMode=True,
                               numIterations=2).fit(df)

    def test_stratified_bagging_requires_binary(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(300, 4)).astype(np.float32)
        y = rng.normal(size=300).astype(np.float32)
        df = DataFrame({"features": x, "label": y})
        from mmlspark_tpu.lightgbm import LightGBMRegressor
        with pytest.raises(ValueError, match="binary"):
            LightGBMRegressor(numIterations=2, baggingFreq=1,
                              negBaggingFraction=0.5).fit(df)

    def test_start_iteration_leaf_and_shap_consistent(self):
        """Leaf and SHAP outputs honour startIteration: leaf columns for
        skipped iterations drop, and the SHAP sum equals the SAME
        tail-model margin the score columns carry."""
        df = classification_df(300)
        m = LightGBMClassifier(numIterations=6, numLeaves=7,
                               minDataInLeaf=5, numShards=1,
                               seed=0).fit(df)
        m.set("startIteration", 2)
        m.set("leafPredictionCol", "leaves")
        m.set("featuresShapCol", "shap")
        out = m.transform(df)
        assert np.asarray(out["leaves"]).shape[1] == 4
        x = np.asarray(df["features"])
        raw_tail = np.asarray(m.booster.raw_scores(x, start_iteration=2))
        np.testing.assert_allclose(
            np.asarray(out["shap"]).sum(axis=-1), raw_tail,
            rtol=1e-3, atol=1e-3)

    def test_max_bin_by_feature_rejects_categorical_and_one(self):
        rng = np.random.default_rng(2)
        x = np.stack([rng.integers(0, 5, 300), rng.normal(size=300)],
                     axis=1).astype(np.float32)
        y = (x[:, 1] > 0).astype(np.float32)
        df = DataFrame({"features": x, "label": y})
        with pytest.raises(ValueError, match="categorical"):
            LightGBMClassifier(numIterations=2, maxBinByFeature=[4, 0],
                               categoricalSlotIndexes=[0]).fit(df)
        with pytest.raises(ValueError, match="unsplittable"):
            LightGBMClassifier(numIterations=2,
                               maxBinByFeature=[0, 1]).fit(df)

    def test_xgboost_dart_mode_inert_outside_dart(self):
        df = classification_df(300)
        m = LightGBMClassifier(numIterations=3, numLeaves=7,
                               minDataInLeaf=5, numShards=1, seed=0,
                               xgboostDartMode=True).fit(df)
        assert m.booster.num_trees == 3

    def test_shap_honours_prediction_window_and_rf_average(self):
        """SHAP must track the same margin as scores for BOTH window
        params and for rf's averaged output."""
        from mmlspark_tpu.lightgbm.shap import booster_shap_values
        df = classification_df(400)
        x = np.asarray(df["features"])
        m = LightGBMClassifier(numIterations=6, numLeaves=7,
                               minDataInLeaf=5, numShards=1,
                               seed=0).fit(df)
        shap = booster_shap_values(m.booster, x[:40], x.shape[1],
                                   start_iteration=1, num_iteration=4)
        raw = np.asarray(m.booster.raw_scores(
            x[:40], num_iteration=4, start_iteration=1))
        np.testing.assert_allclose(shap.sum(-1), raw, rtol=1e-3,
                                   atol=1e-3)
        rf = LightGBMClassifier(boostingType="rf", baggingFraction=0.8,
                                baggingFreq=1, numIterations=6,
                                numLeaves=7, minDataInLeaf=5,
                                numShards=1, seed=0).fit(df)
        shap_rf = booster_shap_values(rf.booster, x[:40], x.shape[1])
        raw_rf = np.asarray(rf.booster.raw_scores(x[:40]))
        np.testing.assert_allclose(shap_rf.sum(-1), raw_rf, rtol=1e-3,
                                   atol=1e-3)


class TestFusedTraceCache:
    """The cross-fit trace cache must reuse compiled steps across
    same-shape fits WITHOUT baking the previous fit's data in as
    constants (the classic stale-capture bug of cached jitted
    closures)."""

    def _mkdata(self, seed, signal_col):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(1500, 10)).astype(np.float32)
        y = (x[:, signal_col] > 0).astype(np.float32)
        return DataFrame({"features": x, "label": y}), x, y

    def test_refit_hits_cache_and_sees_new_data(self):
        from mmlspark_tpu.lightgbm import trainer as trainer_mod
        trainer_mod._FUSED_CACHE.clear()
        df_a, _, _ = self._mkdata(0, signal_col=0)
        df_b, xb, yb = self._mkdata(1, signal_col=7)
        kw = dict(numIterations=15, numLeaves=15, learningRate=0.3)
        LightGBMClassifier(**kw).fit(df_a)
        assert len(trainer_mod._FUSED_CACHE) == 1
        model_b = LightGBMClassifier(**kw).fit(df_b)
        # same statics -> same entry reused, not a second compile
        assert len(trainer_mod._FUSED_CACHE) == 1
        # had fit B reused fit A's baked labels/features, accuracy on
        # B's signal (feature 7, unrelated to A's feature 0) would be
        # near chance
        pred = model_b.transform(df_b)["prediction"]
        acc = float((np.asarray(pred) == yb).mean())
        assert acc > 0.9, acc

    def test_different_objective_gets_its_own_entry(self):
        from mmlspark_tpu.lightgbm import trainer as trainer_mod
        from mmlspark_tpu.lightgbm import LightGBMRegressor
        trainer_mod._FUSED_CACHE.clear()
        df, _, _ = self._mkdata(2, signal_col=3)
        LightGBMClassifier(numIterations=5).fit(df)
        LightGBMRegressor(numIterations=5).fit(df)
        assert len(trainer_mod._FUSED_CACHE) == 2

    def test_learning_rate_sweep_shares_one_trace(self):
        """lr is a traced scalar in the cached path: sweeping it must
        reuse ONE compiled step and still produce exactly the model the
        closure (delegate) path produces. Both paths now shrink via the
        same isolated post-hoc multiply — this oracle guards that the
        two builders stay bit-identical (traced-scalar vs baked-constant
        lr), incl. under max_delta_step>0; accuracy-level correctness of
        the shrinkage itself is covered by the reference-parity CSVs."""
        from mmlspark_tpu.lightgbm import trainer as trainer_mod

        class _NoOpDelegate:
            """Forces the closure (make_fused_step) path; changes no
            semantics: lr unchanged, hooks empty."""
            def get_learning_rate(self, it):
                return None

            def before_train_iteration(self, it):
                pass

            def after_train_iteration(self, it):
                pass

        rng = np.random.default_rng(4)
        x = rng.normal(size=(1500, 10)).astype(np.float32)
        y = (x[:, 1] > 0).astype(np.float32)
        for mds in (0.0, 0.02):
            cfgkw = dict(objective="binary", num_iterations=12,
                         num_leaves=15, max_delta_step=mds)
            trainer_mod._FUSED_CACHE.clear()
            trainer_mod.train(x, y, None, trainer_mod.TrainConfig(
                learning_rate=0.1, **cfgkw))
            r_cached = trainer_mod.train(x, y, None,
                                         trainer_mod.TrainConfig(
                                             learning_rate=0.05, **cfgkw))
            assert len(trainer_mod._FUSED_CACHE) == 1
            r_closure = trainer_mod.train(
                x, y, None,
                trainer_mod.TrainConfig(learning_rate=0.05, **cfgkw),
                delegate=_NoOpDelegate())
            for fld in ("leaf_value", "feature", "left", "right"):
                np.testing.assert_array_equal(
                    r_cached.booster.arrays[fld],
                    r_closure.booster.arrays[fld], err_msg=fld)
            np.testing.assert_array_equal(
                np.asarray(r_cached.booster.raw_scores(x)),
                np.asarray(r_closure.booster.raw_scores(x)))


def test_delegate_learning_rate_schedule():
    """A delegate LR schedule (reference delegate hooks,
    ``LightGBMDelegate.scala``) applies mid-fit: trees before the switch
    bit-match a constant-lr run, trees after reflect the new rate —
    growers are lr-free so only the step closures rebuild."""
    from mmlspark_tpu.lightgbm.trainer import TrainConfig, train

    class _Halver:
        def __init__(self, switch_at):
            self.switch_at = switch_at

        def get_learning_rate(self, it):
            return 0.1 if it < self.switch_at else 0.05

        def before_train_iteration(self, it):
            pass

        def after_train_iteration(self, it):
            pass

    rng = np.random.default_rng(6)
    x = rng.normal(size=(800, 6)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 3] > 0).astype(np.float32)
    cfgkw = dict(objective="binary", num_iterations=8, num_leaves=7,
                 learning_rate=0.1)
    r_const = train(x, y, None, TrainConfig(**cfgkw))
    r_sched = train(x, y, None, TrainConfig(**cfgkw),
                    delegate=_Halver(switch_at=4))
    lv_c = r_const.booster.arrays["leaf_value"]
    lv_s = r_sched.booster.arrays["leaf_value"]
    np.testing.assert_array_equal(lv_s[:4], lv_c[:4])
    assert not np.array_equal(lv_s[4], lv_c[4]), \
        "the LR switch at iteration 4 must change the 5th tree"
