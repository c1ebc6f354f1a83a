"""Driver of the featurize cells: back-to-back
``ImageFeaturizer.transform`` over one host-resident DataFrame (body
lifted from ``chip_smoke.phase_featurizer``, not imported: later PRs may
change the smoke, not the yardstick)."""

from __future__ import annotations

import numpy as np

from benchmark import traffic
from benchmark.drivers_common import resnet_variables
from benchmark.references import resnet50 as ref


def setup(cfg: dict, params: dict, seed: int) -> dict:
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.image import ImageFeaturizer
    from mmlspark_tpu.models.resnet import BottleneckBlock, ResNet
    from mmlspark_tpu.models.zoo import LoadedModel, get_model

    weights = ref.make_weights(cfg, seed)
    schema = get_model(cfg["model"])
    # the zoo's module, built from the configuration's own sizes
    module = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                    block=BottleneckBlock, width=int(cfg["stem_width"]),
                    num_classes=int(cfg["num_classes"]))
    loaded = LoadedModel(schema=schema, module=module,
                         variables=resnet_variables(weights))
    images = traffic.make_inputs(params["inputs"], seed)["images"]
    feat = ImageFeaturizer(
        model=loaded, cutOutputLayers=1, inputCol="image",
        outputCol="features", autoResize=False,
        miniBatchSize=int(params["minibatch"]),
        pipelineDepth=int(params["pipeline_depth"]),
        quantize=bool(params.get("quantize", False)))
    n = images.shape[0]
    # first row, last row (the padded tail minibatch) and a seeded rest
    sample = traffic.sample_rows(seed, n, int(params["check_rows"]),
                                 always=(0, n - 1))
    return {"cfg": cfg, "weights": weights, "feat": feat,
            "df": DataFrame({"image": images}), "images": images,
            "sample": sample, "served": [], "stats": [], "n": n,
            "warm_ops": int(params["warm_ops"])}


def step(ctx: dict) -> int:
    """One transform; returns the images it featurized."""
    out = np.asarray(ctx["feat"].transform(ctx["df"])["features"])
    ctx["served"].append(out[ctx["sample"]])
    ctx["stats"].append(dict(ctx["feat"].last_transform_stats))
    return ctx["n"]


def warm(ctx: dict) -> None:
    """Several transforms, not one: the first ones after start-up ran up
    to 5 % slow on the chip (fresh host pages under the 154 MB copies)."""
    for _ in range(ctx["warm_ops"]):
        step(ctx)
    ctx["served"].clear()
    ctx["stats"].clear()


def outputs_for_check(ctx: dict) -> dict:
    """What the window produced, and the inputs it was produced from;
    drops the program's state so the reference has the device."""
    out = {"images": ctx["images"][ctx["sample"]],
           "served": list(ctx["served"]), "weights": ctx["weights"]}
    for key in ("feat", "df", "images"):
        ctx.pop(key, None)
    return out


def check(outputs: dict, cfg: dict, params: dict, seed: int) -> list:
    return ref.compare_features(outputs["weights"], cfg, outputs["images"],
                                outputs["served"], params["limits"])


def control_checks(cfg: dict, params: dict, seed: int) -> list:
    """The control: the program's own int8 path (``quantize=True``) in
    the program's place, one transform, the same comparison."""
    ctx = setup(cfg, {**params, "quantize": True}, seed)
    step(ctx)
    return check(outputs_for_check(ctx), cfg, params, seed)
