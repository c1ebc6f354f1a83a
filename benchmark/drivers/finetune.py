"""Driver of the fine-tune cells: ``dl.train_epoch`` over
``make_train_step(module, optax.sgd(lr, momentum))`` on host batches
(body lifted from ``chip_smoke.phase_train``, not imported). Set-up
builds ONE compiled step with its state, drives it through its first
three steps by the window's own call and feed, keeps what the comparison
needs, and hands the same state to the window."""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import traffic
from benchmark.drivers_common import resnet_variables
from benchmark.references import resnet50 as ref
from benchmark.references import resnet50_train as ref_train


def _flat(tree, prefix=()) -> dict:
    out = {}
    for key, value in tree.items():
        if isinstance(value, dict):
            out.update(_flat(value, prefix + (key,)))
        else:
            out["/".join(prefix + (key,))] = value
    return out


def _norms(tree) -> dict:
    return ref_train.norms(_flat(jax.device_get(tree)))


def _batches(cfg: dict, params: dict, seed: int) -> list:
    return traffic.make_inputs(
        {**params["inputs"], "classes": cfg["num_classes"],
         "size": cfg["image_size"], "channels": cfg["in_channels"]},
        seed)["batches"]


def setup(cfg: dict, params: dict, seed: int) -> dict:
    import optax
    from mmlspark_tpu.dl.train import TrainState, make_train_step
    from mmlspark_tpu.models.resnet import BottleneckBlock, ResNet

    variables = resnet_variables(ref.make_weights(cfg, seed))
    module = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                    block=BottleneckBlock, width=int(cfg["stem_width"]),
                    num_classes=int(cfg["num_classes"]))
    tx = optax.sgd(float(params["learning_rate"]),
                   momentum=float(params["momentum"]))
    state = TrainState(params=variables["params"],
                       batch_stats=variables["batch_stats"],
                       opt_state=tx.init(variables["params"]),
                       step=jnp.zeros((), jnp.int32))
    return {"cfg": cfg, "step_fn": make_train_step(module, tx),
            "state": state, "batches": _batches(cfg, params, seed),
            "start": {k: np.asarray(v) for k, v in
                      _flat(variables["params"]).items()},
            "steps": int(params["steps_per_epoch"]),
            "batch": int(params["inputs"]["batch"]), "stats": []}


def _epoch(ctx: dict, batches: list) -> list:
    from mmlspark_tpu.dl.train import train_epoch
    ctx["state"], losses = train_epoch(ctx["step_fn"], ctx["state"],
                                       batches)
    return losses


def warm(ctx: dict) -> None:
    """The first three steps, on three different batches: the first
    alone, because its gradient is read from the optimizer's state."""
    b = ctx["batches"]
    losses = _epoch(ctx, [b[0]])
    trace = ctx["state"].opt_state[0].trace        # momentum buffer = g1
    grad_norms = _norms(trace)
    losses += _epoch(ctx, [b[1], b[2]])
    params = _flat(jax.device_get(ctx["state"].params))
    ctx["first_steps"] = {
        "losses": losses, "grad_norms": grad_norms,
        "change_norms": {k: float(np.linalg.norm(
            np.asarray(v, np.float64) - ctx["start"][k]))
            for k, v in params.items()},
        "stat_norms": _norms(ctx["state"].batch_stats)}
    del ctx["start"]


def step(ctx: dict) -> int:
    """One epoch: ``steps`` batches from the ring, losses fetched at its
    end; returns the images trained on."""
    ring = ctx["batches"]
    losses = _epoch(ctx, [ring[i % len(ring)] for i in range(ctx["steps"])])
    ctx["stats"].append({"last_loss": losses[-1]})
    return ctx["steps"] * ctx["batch"]


def outputs_for_check(ctx: dict) -> dict:
    out = {"first_steps": ctx["first_steps"], "batches": ctx["batches"][:3],
           "finite": all(np.isfinite(s["last_loss"]) for s in ctx["stats"])}
    for key in ("state", "step_fn", "batches"):
        ctx.pop(key, None)
    return out


def check(outputs: dict, cfg: dict, params: dict, seed: int) -> list:
    want = ref_train.train_steps(
        cfg, seed, outputs["batches"], lr=float(params["learning_rate"]),
        momentum=float(params["momentum"]))
    details = {}
    got = ref_train.compare(outputs["first_steps"], want, details)
    limits = params["limits"]
    # numbers without a limit have no upper reading or swing with one
    # small leaf (PERF.md section 2): read in every run, never compared
    print(json.dumps({"not_compared": {k: v for k, v in got.items()
                                       if k not in limits},
                      "worst_leaves": details}), file=sys.stderr)
    if not outputs["finite"]:
        got = dict.fromkeys(got, float("inf"))
    return [(name, got[name], limits[name])
            for name in ref_train.NUMBERS if name in limits]


def control_checks(cfg: dict, params: dict, seed: int) -> list:
    """The reference put in the program's place three ways, each against
    the float32 reference: forward operands rounded to fp8 (one step
    below the configuration's bfloat16), half of the batch left out, and
    the bfloat16 the configuration states in both passes (the look at the
    worst leaves)."""
    batches = _batches(cfg, params, seed)[:3]
    kw = dict(lr=float(params["learning_rate"]),
              momentum=float(params["momentum"]))
    want = ref_train.train_steps(cfg, seed, batches, **kw)
    half = slice(0, int(params["inputs"]["batch"]) // 2)
    variants = {
        "fp8": dict(round_fn=ref_train.straight_through(
            ref.round_to("float8_e4m3fn", scaled=True))),
        "half_batch": dict(rows=half),
        # the look at the worst leaves: bfloat16 in the backward pass too
        # (the cast's transpose rounds the cotangent), as the program runs
        "bf16_bwd": dict(round_fn=ref.round_to("bfloat16")),
    }
    out = []
    for label, extra in variants.items():
        details = {}
        got = ref_train.compare(
            ref_train.train_steps(cfg, seed, batches, **kw, **extra), want,
            details)
        print(json.dumps({"variant": label, "worst_leaves": details}),
              file=sys.stderr)
        out += [(f"{label}.{name}", got[name],
                 params["limits"].get(name, float("inf")))
                for name in ref_train.NUMBERS]
    return out
