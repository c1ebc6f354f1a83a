"""That ``correct`` can come out false: each cell's lower-precision
control, and the timed path broken underneath a run of the harness."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.references import gbdt as gbdt_ref  # noqa: E402
from benchmark.references import resnet50 as resnet_ref  # noqa: E402
from test_harness import run_tiny  # noqa: E402


def _cfg(name, **over):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return {**json.load(f), **over}


# ---------------------------------------------------------- featurize
def test_featurize_control_int8_path_is_not_correct():
    """The program's own int8 path (``quantize=True``) in the program's
    place: the comparison has to refuse it."""
    rc, result, _, err = run_tiny("resnet50.featurize", quantize=True)
    assert rc == 0
    assert result["correct"] is False, err
    chk = result["checks"]["feature_row_rel_l2_max"]
    assert chk["value"] > chk["limit"]
    assert "NOT CORRECT" in err


def test_featurize_reference_in_fp8_reads_far_from_float32():
    cfg = _cfg("resnet50-imagenet", stage_sizes=[1, 1, 1, 1], stem_width=8,
               image_size=32, num_classes=10)
    weights = resnet_ref.make_weights(cfg, 5)
    images = np.random.default_rng(5).integers(
        0, 256, size=(4, 32, 32, 3), dtype=np.uint8)
    ref = np.asarray(resnet_ref.forward(weights, images, cfg))
    bf16 = np.asarray(resnet_ref.forward(
        weights, images, cfg, round_fn=resnet_ref.round_to("bfloat16")))
    fp8 = np.asarray(resnet_ref.forward(
        weights, images, cfg,
        round_fn=resnet_ref.round_to("float8_e4m3fn", scaled=True)))
    assert resnet_ref.row_gaps(fp8, ref).max() \
        > 3 * resnet_ref.row_gaps(bf16, ref).max()


@pytest.mark.parametrize("fault", ["answer_altered", "rows_swapped"])
def test_featurize_fault_under_the_harness(monkeypatch, fault):
    from mmlspark_tpu.dl.model import TPUModel
    real = TPUModel._transform

    def broken(self, df):
        out = real(self, df)
        col = self.getOutputCol()
        val = np.array(out[col])
        if fault == "answer_altered":
            val[-1] *= 1.25                 # the padded tail's last row
        else:
            val = val[::-1].copy()          # answers handed to other rows
        return out.with_column(col, val)

    monkeypatch.setattr(TPUModel, "_transform", broken)
    rc, result, _, err = run_tiny("resnet50.featurize")
    assert rc == 0 and result["correct"] is False, err


# --------------------------------------------------------------- gbdt
GBDT_TINY = dict(num_leaves=15, max_bin=63, min_sum_hessian_in_leaf=5.0)


@pytest.fixture(scope="module")
def gbdt_case():
    cfg = _cfg("lightgbm-higgs", **GBDT_TINY)
    x, y = gbdt_ref.make_data(11, 20_000, 28)
    return cfg, x, y


def _numbers(trees, cfg, x, y):
    return gbdt_ref.compare_model(trees, x, y, cfg, num_trees=len(trees),
                                  check_nodes=14, seed=3, chunk=4096)


def test_gbdt_reference_fit_passes_and_fp8_control_fails(gbdt_case):
    cfg, x, y = gbdt_case
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "higgs.fit.json")) as f:
        limits = json.load(f)["params"]["limits"]
    exact = _numbers(gbdt_ref.fit(x, y, cfg, 2, chunk=4096), cfg, x, y)
    assert all(exact[k] <= limits[k] for k in exact), exact
    control = _numbers(gbdt_ref.fit(
        x, y, cfg, 2, round_fn=resnet_ref.round_to("float8_e4m3fn"),
        precision="default", chunk=4096), cfg, x, y)
    assert any(control[k] > limits[k] for k in control), control
    assert control["leaf_value_gap"] > 3 * max(exact["leaf_value_gap"], 1e-4)


def test_text_model_reader_round_trip(gbdt_case):
    cfg, x, y = gbdt_case
    text = "\n".join([
        "tree", "version=v3", "", "Tree=0", "num_leaves=3", "num_cat=0",
        "split_feature=1 0", "split_gain=2 1", "threshold=0.5 -0.25",
        "decision_type=2 2", "left_child=1 -1", "right_child=-2 -3",
        "leaf_value=0.1 -0.2 0.3", "leaf_count=5 6 7",
        "internal_count=18 12", "shrinkage=1", "", "end of trees"])
    (tree,) = gbdt_ref.parse_model(text)
    assert gbdt_ref.tree_depth(tree) == 2
    assert gbdt_ref.descendants(tree).tolist() == [[True, True, True],
                                                   [True, False, True]]
    rows = np.zeros((3, 28), np.float32)
    rows[0, 1], rows[1, 1], rows[1, 0], rows[2, 1], rows[2, 0] = \
        1.0, 0.0, -1.0, 0.0, 0.0
    assert np.asarray(gbdt_ref.route_tree(rows, tree)).tolist() == [1, 0, 2]


@pytest.mark.parametrize("fault", ["leaf_altered", "half_the_rows",
                                   "score_altered"])
def test_gbdt_fault_under_the_harness(monkeypatch, fault):
    from mmlspark_tpu.lightgbm import estimators

    if fault == "half_the_rows":
        real_fit = estimators.LightGBMClassifier._fit

        def broken_fit(self, df):
            n = len(df["label"]) // 2
            half = type(df)({k: np.asarray(df[k])[:n] for k in
                             ("features", "label")})
            return real_fit(self, half)
        monkeypatch.setattr(estimators.LightGBMClassifier, "_fit",
                            broken_fit)
    elif fault == "leaf_altered":
        from mmlspark_tpu.lightgbm.booster import Booster
        real_init = Booster.__init__

        def broken_init(self, arrays, **kw):
            real_init(self, arrays, **kw)
            if "leaf_value" in self.arrays:
                lv = np.array(self.arrays["leaf_value"])
                lv[0, np.flatnonzero(self.arrays["is_leaf"][0])[0]] *= 1.5
                self.arrays["leaf_value"] = lv
        monkeypatch.setattr(Booster, "__init__", broken_init)
    else:
        cls = estimators.LightGBMClassificationModel
        real_transform = cls._transform

        def broken_transform(self, df):
            out = real_transform(self, df)
            col = self.getProbabilityCol()
            prob = np.array(out[col])
            prob[0] = prob[0][::-1]
            return out.with_column(col, prob)
        monkeypatch.setattr(cls, "_transform", broken_transform)
    rc, result, _, err = run_tiny("higgs.fit")
    assert rc == 0 and result["correct"] is False, err


# ----------------------------------------------------------- fine-tune
def _finetune_tiny():
    from benchmark import run
    _, wl, cfg, params = run.load_cell("resnet50.finetune",
                                       run.load_bench(), tiny=True)
    return run._load_module("drivers", wl["driver"]), cfg, params


def test_finetune_controls_fail_and_stated_precision_passes():
    driver, cfg, params = _finetune_tiny()
    got = {}
    for name, value, limit in driver.control_checks(cfg, params, 5):
        label, number = name.split(".", 1)
        got.setdefault(label, {})[number] = (value, limit)
    assert any(v > lim for v, lim in got["fp8"].values()), got["fp8"]
    assert any(v > lim for v, lim in got["half_batch"].values())
    assert all(v <= lim for v, lim in got["bf16_bwd"].values())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_finetune_fault_under_the_harness(monkeypatch, fault):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.dl import train
    real = train.train_epoch

    def broken(step, state, batches, placement=None):
        if fault == "state_unchanged":
            keep = jax.tree.map(jnp.copy, state)
            _, losses = real(step, state, batches, placement)
            return keep, losses
        return real(step, state, [(x[:len(x) // 2], y[:len(y) // 2])
                                  for x, y in batches], placement)

    monkeypatch.setattr(train, "train_epoch", broken)
    rc, result, _, err = run_tiny("resnet50.finetune")
    assert rc == 0 and result["correct"] is False, err
