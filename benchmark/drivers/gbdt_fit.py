"""Driver of the GBDT fit cells: back-to-back warm
``LightGBMClassifier.fit`` on one host-resident DataFrame, each followed
by ``model.transform`` on held-out rows (body lifted from
``chip_smoke.phase_gbdt``, not imported)."""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic
from benchmark.references import gbdt as ref


def _classifier(cfg: dict, iterations: int):
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    a = cfg["assumed"]
    return LightGBMClassifier(
        numIterations=int(iterations), numLeaves=int(cfg["num_leaves"]),
        learningRate=float(cfg["learning_rate"]),
        maxBin=int(cfg["max_bin"]),
        minSumHessianInLeaf=float(cfg["min_sum_hessian_in_leaf"]),
        minDataInLeaf=int(a["min_data_in_leaf"]),
        binSampleCount=int(a["bin_construct_sample_cnt"]),
        seed=int(a["bin_sample_seed"]), numShards=1)


def setup(cfg: dict, params: dict, seed: int) -> dict:
    from mmlspark_tpu.core import DataFrame

    data = traffic.make_inputs(params["inputs"], seed)
    return {"cfg": cfg, "data": data,
            "clf": _classifier(cfg, params["iterations"]),
            "train": DataFrame({"features": data["x"], "label": data["y"]}),
            "test": DataFrame({"features": data["held_out"]}),
            "iterations": int(params["iterations"]),
            "rows": data["x"].shape[0], "served": [], "stats": []}


def step(ctx: dict) -> int:
    """One fit and one scoring of the held-out rows; returns the
    row-iterations fitted."""
    t0 = time.perf_counter()
    model = ctx["clf"].fit(ctx["train"])
    t1 = time.perf_counter()
    prob = np.asarray(model.transform(ctx["test"])[
        model.getProbabilityCol()])[:, 1]
    ctx["served"].append((model, prob))
    ctx["stats"].append({"fit_s": t1 - t0,
                         "score_s": time.perf_counter() - t1})
    return ctx["rows"] * ctx["iterations"]


def warm(ctx: dict) -> None:
    step(ctx)
    ctx["served"].clear()
    ctx["stats"].clear()


def after_window(ctx: dict, trace: bool) -> None:
    """A traced run also times one 1-iteration fit on the same rows: the
    fixed cost of a fit (binning, upload, one tree)."""
    if not trace:
        return
    one = _classifier(ctx["cfg"], 1)
    one.fit(ctx["train"])                       # its shapes, compiled
    t0 = time.perf_counter()
    one.fit(ctx["train"])
    ctx["first_iter_s"] = time.perf_counter() - t0


def outputs_for_check(ctx: dict) -> dict:
    """Every model of the window as its LightGBM text, the held-out
    probabilities, and the rows; drops the program's state."""
    served = [(model.booster.save_native(), prob)
              for model, prob in ctx["served"]]
    out = {"served": served, "data": ctx["data"],
           "iterations": ctx["iterations"]}
    for key in ("clf", "train", "test", "served", "data"):
        ctx.pop(key, None)
    return out


def check(outputs: dict, cfg: dict, params: dict, seed: int) -> list:
    """The last model of the window is replayed, and the first too where
    it differs (fits of the same rows are expected to repeat)."""
    data, limits = outputs["data"], params["limits"]
    served = outputs["served"]
    todo = [served[-1]]
    if served[0][0] != served[-1][0] or not np.array_equal(
            served[0][1], served[-1][1]):
        todo.append(served[0])
    worst: dict = {}
    for text, prob in todo:
        got = ref.compare_model(
            ref.parse_model(text), data["x"], data["y"], cfg,
            num_trees=outputs["iterations"],
            check_nodes=int(params["check_nodes"]), seed=seed,
            held_out=data["held_out"], served_prob=prob)
        for name, value in got.items():
            worst[name] = max(worst.get(name, 0.0), value)
    return [(name, worst[name], limits[name]) for name in ref.NUMBERS]


def control_checks(cfg: dict, params: dict, seed: int) -> list:
    """The control: the reference grower in the program's place with the
    gradient pair rounded to fp8 (e4m3), one step below the bfloat16
    operands the configuration states; the same comparison."""
    from benchmark.references.resnet50 import round_to
    data = traffic.make_inputs(params["inputs"], seed)
    trees = ref.fit(data["x"], data["y"], cfg, int(params["iterations"]),
                    round_fn=round_to("float8_e4m3fn"), precision="default")
    got = ref.compare_model(
        trees, data["x"], data["y"], cfg,
        num_trees=int(params["iterations"]),
        check_nodes=int(params["check_nodes"]), seed=seed)
    return [(name, got[name], params["limits"][name])
            for name in ref.NUMBERS]
