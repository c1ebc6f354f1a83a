#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one import of JAX, one chip held throughout. Drives the main
paths through the entry points a user calls — ``ImageFeaturizer``, the
ResNet-50 train step, ``LightGBMClassifier``, ``TextEncoderFeaturizer`` with
the fused flash kernel, ``serving.llm.LLMEngine`` and ``serving_query`` over
both HTTP fronts — at the sizes ``bench.py`` uses for these models, on data
and weights made from a fixed seed, and checks each against the repo's own
reference. There is no CPU mode: without a TPU the script exits non-zero at
once and prints no result. ``tests/test_chip_smoke.py`` rehearses the same
phase functions at tiny sizes on the CPU.

    python chip_smoke.py                 # all six phases, one chip
    python chip_smoke.py --phase llm     # a subset (repeatable)
    python chip_smoke.py --chips 4       # only the two sharded comparisons

Each phase prints one JSON line (``phase``, ``ok``, ``compile_s``,
``run_s`` and what it checked); the first phase that fails ends the run
non-zero after its line. The last line of a run that passed is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import http.client
import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("featurizer", "train", "gbdt", "encoder", "llm", "serving")
KERNEL = "tpu_custom_call"      # how a Mosaic (Pallas TPU) kernel shows in IR


# ---------------------------------------------------------------- helpers

def _require(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def _same_device(arr) -> bool:
    """Whether ``arr`` lives on the process's first device (the chip)."""
    import jax
    return set(arr.devices()) == {jax.devices()[0]}


def _rel(a, b) -> float:
    """Relative L2 distance of ``a`` from the reference ``b``."""
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _host_cpu():
    """The host CPU backend's first device when it is addressable beside
    the default backend, else None."""
    import jax
    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return None
    return None if cpu.platform == jax.devices()[0].platform else cpu


def _hbm() -> dict:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


class _IrDump:
    """Collect the StableHLO of everything jitted inside the block
    (``jax_dump_ir_to``): how a phase sees the program a library entry
    point compiled without rebuilding that program's arguments."""

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
        jax.config.update("jax_dump_ir_to", self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.config.update("jax_dump_ir_to", None)
        self.modules = {}
        for name in sorted(os.listdir(self.dir)):
            with open(os.path.join(self.dir, name), errors="replace") as f:
                self.modules[name] = f.read().count(KERNEL)
        shutil.rmtree(self.dir, ignore_errors=True)
        return False

    def with_kernel(self) -> dict:
        return {k: v for k, v in self.modules.items() if v}


class _CompileClock:
    """Seconds JAX spent lowering to StableHLO and in the backend's
    compiler (or fetching from the persistent cache in its place),
    summed from its own monitoring events — what splits a phase into
    compile and run. Tracing nests, so it stays on the run side."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                     "/jax/core/compile/backend_compile_duration"):
            self.seconds += float(duration)


def final_line(devices) -> str:
    """The contract's last line: exactly the three ``device`` keys, as
    JAX reports them."""
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


# ----------------------------------------------------------------- phases
# Each takes ``rec`` (the phase's JSON line, filled as it goes so that a
# failing check still reports what was seen) and its sizes as keywords —
# the defaults are the chip sizes, tests pass tiny ones.

def phase_featurizer(rec: dict, *, model: str = "ResNet50",
                     n_images: int = 256, size: int = 224,
                     minibatch: int = 64, feature_dim: int = 2048,
                     ref_rows: int = 8, tol: float = 0.05) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.image import ImageFeaturizer
    from mmlspark_tpu.models import ModelDownloader

    loaded = ModelDownloader().download_by_name(model,
                                                allow_random_init=True)
    leaf = jax.tree.leaves(loaded.variables)[0]
    rec["weights_init_on"] = next(iter(leaf.devices())).platform
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, size=(n_images, size, size, 3),
                        dtype=np.uint8)
    feat = ImageFeaturizer(model=loaded, cutOutputLayers=1,
                           inputCol="image", outputCol="features",
                           autoResize=False, miniBatchSize=minibatch)
    out = np.asarray(feat.transform(DataFrame({"image": imgs}))["features"])
    rec.update(images=n_images, minibatch=minibatch,
               out_shape=list(out.shape),
               transform_stats_ms=feat.last_transform_stats)
    _require(out.shape == (n_images, feature_dim),
             f"features {out.shape} != {(n_images, feature_dim)}")
    _require(np.isfinite(out).all(), "non-finite features")

    # float32 reference apply of the SAME variables on ref_rows images
    ref_module = loaded.module.clone(dtype=jnp.float32)
    x_ref = imgs[:ref_rows]

    def ref_apply(v, x):
        return ref_module.apply(v, x, False)["pooled"]

    cpu = _host_cpu()
    if cpu is not None:
        rec["reference"] = "float32 on the host CPU backend"
        with jax.default_device(cpu):
            ref = jax.jit(ref_apply)(jax.device_put(loaded.variables, cpu),
                                     jax.device_put(x_ref, cpu))
    else:
        rec["reference"] = ("float32 at highest matmul precision on "
                            "the default device")
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(ref_apply)(loaded.variables, jnp.asarray(x_ref))
    rec["rel_l2_vs_float32"] = _rel(out[:ref_rows], ref)
    rec["tolerance"] = tol
    _require(rec["rel_l2_vs_float32"] < tol,
             "features disagree with the float32 reference beyond bf16 "
             f"tolerance: {rec['rel_l2_vs_float32']:.4g} >= {tol}")

    # the featurizer's own jitted apply, on the shape it already compiled
    run = feat._tpu_model[1]._apply_fn()
    dev_out = run(jnp.asarray(imgs[:minibatch]))["pooled"]
    rec["output_device"] = str(next(iter(dev_out.devices())))
    _require(_same_device(dev_out),
             f"jitted apply output lives on {rec['output_device']}, not "
             f"on {jax.devices()[0]}")


def phase_train(rec: dict, *, model: str = "ResNet50", batch: int = 64,
                size: int = 224, steps: int = 3,
                num_classes: int = 100) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mmlspark_tpu.dl.train import init_train_state, make_train_step
    from mmlspark_tpu.models import ModelDownloader

    loaded = ModelDownloader().download_by_name(
        model, num_classes=num_classes, allow_random_init=True)
    tx = optax.sgd(1e-2, momentum=0.9)
    device = jax.devices()[0]
    cpu = _host_cpu()
    with jax.default_device(cpu) if cpu is not None else contextlib.nullcontext():
        state0 = init_train_state(loaded.module, jax.random.PRNGKey(0),
                                  np.zeros((1, size, size, 3), np.float32),
                                  tx)
    before = jax.device_get(state0.params)      # the step donates its state
    state = jax.device_put(state0, device)
    del state0
    rng = np.random.default_rng(3)
    x = jax.device_put(jnp.asarray(
        rng.normal(size=(batch, size, size, 3)), jnp.float32), device)
    y = jax.device_put(jnp.asarray(
        rng.integers(0, num_classes, size=batch), jnp.int32), device)
    step = make_train_step(loaded.module, tx)
    losses = []
    loss = None
    for _ in range(steps):
        state, loss = step(state, x, y)
        losses.append(float(loss))
    rec.update(batch=batch, steps=steps, losses=losses,
               loss_device=str(next(iter(loss.devices()))))
    _require(all(np.isfinite(v) for v in losses),
             f"non-finite loss: {losses}")
    _require(_same_device(loss) and all(
        _same_device(leaf) for leaf in jax.tree.leaves(state.params)),
        "train step output is not on the first device")
    moved = [float(np.abs(np.asarray(a, np.float32)
                          - np.asarray(b, np.float32)).max())
             for a, b in zip(jax.tree.leaves(jax.device_get(state.params)),
                             jax.tree.leaves(before))]
    rec["param_leaves_changed"] = f"{sum(m > 0 for m in moved)}/{len(moved)}"
    _require(all(np.isfinite(m) for m in moved), "non-finite parameters")
    # not every leaf: the zero-initialised last BatchNorm scale of each
    # residual block leaves the two scales before it a gradient too
    # small to move a float32 1.0 in three steps
    _require(sum(m > 0 for m in moved) >= 0.75 * len(moved),
             "parameters did not change: "
             f"{rec['param_leaves_changed']} leaves moved")


def gbdt_data(n_rows: int, n_features: int = 28, seed: int = 7):
    """``bench_gbdt``'s Higgs-shaped synthetic."""
    import numpy as np
    rng = np.random.default_rng(seed)
    feats = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    margin = feats[:, :4].sum(1) + feats[:, 4] * feats[:, 5]
    labels = (margin + rng.normal(size=n_rows) > 0).astype(np.float32)
    return feats, labels


def phase_gbdt(rec: dict, *, n_rows: int = 500_000, n_features: int = 28,
               n_test: int = 50_000, iters: int = 20,
               leaves: int = 31, min_auc: float = 0.8):
    """Returns ``(model, held-out features)`` for the serving phase."""
    import numpy as np

    from mmlspark_tpu.core import DataFrame, load_stage
    from mmlspark_tpu.lightgbm import LightGBMClassifier
    from mmlspark_tpu.lightgbm.pallas_hist import use_pallas_hist
    from mmlspark_tpu.lightgbm.trainer import roc_auc

    feats, labels = gbdt_data(n_rows + n_test, n_features)
    train = DataFrame({"features": feats[:n_rows],
                       "label": labels[:n_rows]})
    test_x, test_y = feats[n_rows:], labels[n_rows:]
    clf = LightGBMClassifier(numIterations=iters, numLeaves=leaves,
                             learningRate=0.1, numShards=1)
    pallas_before = use_pallas_hist()
    with _IrDump() as ir:
        model = clf.fit(train)
    rec.update(rows=n_rows, features=n_features, iterations=iters,
               trees=int(model.booster.num_trees),
               use_pallas_hist=[pallas_before, use_pallas_hist()],
               programs_with_kernel=ir.with_kernel())
    _require(model.booster.num_trees == iters,
             f"{model.booster.num_trees} trees != {iters} iterations")
    if _on_tpu():
        _require(pallas_before and use_pallas_hist(),
                 "use_pallas_hist() is not true on the TPU")
        steps = {k: v for k, v in ir.modules.items() if "step" in k}
        _require(steps and all(steps.values()),
                 "a compiled boosting step has no Pallas histogram "
                 f"({KERNEL}): {steps}")

    col = model.getProbabilityCol()
    test = DataFrame({"features": test_x})
    prob = np.asarray(model.transform(test)[col])[:, 1]
    auc = roc_auc(test_y, prob)
    path = tempfile.mkdtemp(prefix="chip_smoke_gbdt_")
    try:
        model.save(path)
        prob2 = np.asarray(load_stage(path).transform(test)[col])[:, 1]
    finally:
        shutil.rmtree(path, ignore_errors=True)
    auc2 = roc_auc(test_y, prob2)
    rec.update(held_out_rows=n_test, auc=auc, auc_after_load=auc2,
               max_prob_diff_after_load=float(np.abs(prob - prob2).max()))
    _require(np.isfinite(prob).all(), "non-finite probabilities")
    _require(auc > min_auc, f"held-out AUC {auc:.4f} is not clearly "
             f"above chance (> {min_auc})")
    _require(abs(auc - auc2) <= 1e-6 and
             rec["max_prob_diff_after_load"] <= 1e-6,
             "save/load changed the model's answers")
    return model, test_x


def phase_encoder(rec: dict, *, vocab: int = 32768, width: int = 512,
                  depth: int = 8, heads: int = 8, mlp: int = 2048,
                  seq: int = 2048, batch: int = 8,
                  tol: float = 0.03, grad_tol: float = 0.1) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.dl.text_encoder import (TextEncoder,
                                              TextEncoderFeaturizer,
                                              make_attention_fn)
    from mmlspark_tpu.models import ModelDownloader
    from mmlspark_tpu.models.zoo import register_text_encoder

    name = f"ChipSmokeEncoder-{vocab}x{width}x{depth}"
    register_text_encoder(name, vocab=vocab, width=width, depth=depth,
                          heads=heads, mlp_dim=mlp, seq_len=128)
    loaded = ModelDownloader().download_by_name(name,
                                                allow_random_init=True)
    rng = np.random.default_rng(2)
    ids = rng.integers(1, vocab, size=(batch, seq)).astype(np.int32)
    rows = np.empty(batch, object)
    rows[:] = list(ids)
    df = DataFrame({"tokens": rows})

    feats = {}
    for impl in ("pallas", "dense"):
        stage = TextEncoderFeaturizer(model=loaded, attentionImpl=impl,
                                      seqChunk=128)
        feats[impl] = np.asarray(stage.transform(df)["features"])
        if impl == "pallas":
            apply, variables = stage._encoder()
            fwd_text = apply.lower(variables, jnp.asarray(ids)).as_text()
            rec["forward_kernel_calls"] = fwd_text.count(KERNEL)
    rec.update(batch=batch, seq=seq, width=width, depth=depth,
               out_shape=list(feats["pallas"].shape),
               rel_l2_pallas_vs_dense=_rel(feats["pallas"],
                                           feats["dense"]),
               tolerance=tol)
    _require(feats["pallas"].shape == (batch, width),
             f"embeddings {feats['pallas'].shape}")
    _require(np.isfinite(feats["pallas"]).all(), "non-finite embeddings")
    _require(rec["rel_l2_pallas_vs_dense"] < tol,
             "flash embeddings disagree with the dense impl beyond bf16 "
             f"tolerance: {rec['rel_l2_pallas_vs_dense']:.4g} >= {tol}")

    # one jax.grad step of the same TextEncoder, kernel vs dense
    y = jnp.asarray(rng.integers(0, 2, size=batch), jnp.float32)
    # a fixed read-out: the final LayerNorm centres ``pooled``, so its
    # plain mean would carry no gradient
    w_out = jnp.asarray(rng.normal(size=width) / np.sqrt(width),
                        jnp.float32)
    params = jax.device_put(loaded.variables["params"], jax.devices()[0])
    arch = dict(vocab=vocab, width=width, depth=depth, heads=heads,
                mlp_dim=mlp)
    got = {}
    for impl in ("pallas", "dense"):
        module = TextEncoder(attention_fn=make_attention_fn(impl), **arch)

        def loss_of(p, x, t, module=module):
            pooled = module.apply({"params": p}, x, True)["pooled"]
            return jnp.mean((pooled @ w_out - t) ** 2)

        step = jax.jit(jax.value_and_grad(loss_of))
        if impl == "pallas":
            bwd_text = step.lower(params, jnp.asarray(ids), y).as_text()
            rec["grad_kernel_calls"] = bwd_text.count(KERNEL)
        loss, grads = step(params, jnp.asarray(ids), y)
        got[impl] = (float(loss), jax.device_get(grads))
    flat = {k: np.concatenate([np.asarray(g, np.float32).ravel()
                               for g in jax.tree.leaves(v[1])])
            for k, v in got.items()}
    rec.update(loss_pallas=got["pallas"][0], loss_dense=got["dense"][0],
               rel_l2_grad_pallas_vs_dense=_rel(flat["pallas"],
                                                flat["dense"]),
               grad_tolerance=grad_tol)
    _require(np.isfinite(flat["pallas"]).all()
             and np.isfinite(got["pallas"][0]), "non-finite gradients")
    _require(float(np.abs(flat["pallas"]).max()) > 0, "all-zero gradients")
    _require(rec["rel_l2_grad_pallas_vs_dense"] < grad_tol,
             "flash gradients disagree with the dense impl: "
             f"{rec['rel_l2_grad_pallas_vs_dense']:.4g} >= {grad_tol}")
    if _on_tpu():
        # StableHLO shares one function among the blocks, so the count
        # is of distinct kernels: the forward one, and in the grad
        # program the fused dq and dkv kernels beside it
        _require(rec["forward_kernel_calls"] >= 1,
                 f"forward program holds no {KERNEL}")
        _require(rec["grad_kernel_calls"] > rec["forward_kernel_calls"],
                 "grad program holds no backward kernel: "
                 f"{rec['grad_kernel_calls']} {KERNEL}")


def llm_prompts(vocab: int, *, n: int = 8, shared: int = 4,
                prefix_len: int = 64, lo: int = 64, hi: int = 256,
                seed: int = 5) -> list:
    """``n`` prompts of ``lo``..``hi`` tokens; the first ``shared`` of
    them start with one common ``prefix_len``-token prefix."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prefix = rng.integers(2, vocab, size=prefix_len)
    lens = np.linspace(lo, hi, n).astype(int)
    rng.shuffle(lens)
    out = []
    for i, length in enumerate(int(v) for v in lens):
        if i < shared:
            length = max(length, prefix_len + 1)
            tail = rng.integers(2, vocab, size=length - prefix_len)
            out.append(np.concatenate([prefix, tail]).astype(np.int32))
        else:
            out.append(rng.integers(2, vocab, size=length).astype(np.int32))
    return out


def _greedy_margins(module, variables, seqs: dict, prompt_lens: dict,
                    pad_to: int) -> dict:
    """For each generated token of each sequence: how far below the
    float32 reference's best logit the chosen token's logit lies, given
    the sequence's OWN prefix (0 = the reference's argmax). Also the
    largest distance between the model's own-dtype logits and the
    float32 ones at those positions — the rounding a greedy choice has
    to survive. The reference is the same weights in float32 at highest
    matmul precision through the plain dense causal forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.dl import MaskedLMModel, TextEncoder
    from mmlspark_tpu.dl.text_encoder import make_attention_fn

    enc = module.encoder
    ref = MaskedLMModel(TextEncoder(
        vocab=enc.vocab, width=enc.width, depth=enc.depth, heads=enc.heads,
        mlp_dim=enc.mlp_dim, dtype=jnp.float32,
        attention_fn=make_attention_fn("dense", causal=True)))
    keys = sorted(seqs)
    ids = np.zeros((len(keys), pad_to), np.int32)
    for r, k in enumerate(keys):
        ids[r, :len(seqs[k])] = seqs[k]
    n_new = max(len(seqs[k]) - prompt_lens[k] for k in keys)
    # logits at position t choose the token at t+1
    at = np.stack([np.minimum(prompt_lens[k] - 1 + np.arange(n_new),
                              len(seqs[k]) - 2) for k in keys])
    chosen = np.stack([ids[r, at[r] + 1] for r in range(len(keys))])

    @jax.jit
    def gaps(params, ids, at, chosen):
        with jax.default_matmul_precision("highest"):
            l32 = ref.apply({"params": params}, ids, False)["logits"]
        l32 = jnp.take_along_axis(l32, at[:, :, None], axis=1)
        own = module.apply({"params": params}, ids, False)["logits"]
        own = jnp.take_along_axis(own.astype(jnp.float32),
                                  at[:, :, None], axis=1)
        picked = jnp.take_along_axis(l32, chosen[:, :, None], axis=2)[..., 0]
        return l32.max(-1) - picked, jnp.abs(own - l32).max(-1)

    margin, noise = gaps(variables["params"], jnp.asarray(ids),
                         jnp.asarray(at), jnp.asarray(chosen))
    return {"keys": keys, "margin": np.asarray(margin),
            "noise": np.asarray(noise)}


def explain_divergence(module, variables, prompts: list, got: dict,
                       ref: dict, diverged: dict, pad_to: int) -> dict:
    """Rounding or logic? ``got``/``ref``: the engine's and
    ``dl.generate``'s sequences by prompt index; ``diverged``: index of
    the first differing token of each sequence that differs.

    Identity was pinned in float32 on the CPU. In bf16 on the chip the
    paged kernel and the dense cached decode round differently, and a
    random-weight model's best logits lie closer together than that
    rounding (first chip run, PR 23: gaps of 0.0015-0.006 under a logit
    noise of 0.03). So a divergence is not passed in silence and not
    failed blindly: at the first divergent step of each sequence both
    choices are held against float32 logits of the same prefix.
    ``rounding`` — each choice within the model's own-dtype logit noise
    of the float32 best, and every later token of the engine too, given
    its own prefix; anything further off is ``logic``."""
    # one batch for both decoders' sequences: one compile
    both = {(who, i): seq for who, seqs in (("engine", got),
                                            ("generate", ref))
            for i, seq in seqs.items()}
    m = _greedy_margins(module, variables, both,
                        {k: len(prompts[k[1]]) for k in both}, pad_to)
    row = {k: r for r, k in enumerate(m["keys"])}
    report = []
    for i, t in sorted(diverged.items()):
        j = t - len(prompts[i])
        report.append({
            "seq": i, "step": j,
            "engine_token": int(got[i][t]),
            "generate_token": int(ref[i][t]),
            "engine_gap_to_f32_top": float(
                m["margin"][row["engine", i], j]),
            "generate_gap_to_f32_top": float(
                m["margin"][row["generate", i], j]),
            "own_dtype_logit_noise": float(
                m["noise"][row["engine", i], j])})
    out = {
        "first_divergences": report,
        "engine_max_gap": float(max(
            m["margin"][row["engine", i]].max() for i in got)),
        "generate_max_gap": float(max(
            m["margin"][row["generate", i]].max() for i in ref)),
        "logit_noise_max": float(m["noise"].max())}
    rounding = out["engine_max_gap"] <= out["logit_noise_max"] and all(
        max(r["engine_gap_to_f32_top"], r["generate_gap_to_f32_top"])
        <= r["own_dtype_logit_noise"] for r in report)
    out["divergence_verdict"] = "rounding" if rounding else "logic"
    return out


def phase_llm(rec: dict, *, vocab: int = 32768, width: int = 512,
              depth: int = 8, heads: int = 8, mlp: int = 2048,
              slots: int = 8, block_len: int = 16, max_seq_len: int = 512,
              prefill_batch: int = 4, n_prompts: int = 8, shared: int = 4,
              prefix_len: int = 64, prompt_lo: int = 64,
              prompt_hi: int = 256, new_tokens: int = 32,
              dtype=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.dl import MaskedLMModel, TextEncoder
    from mmlspark_tpu.dl.generate import generate
    from mmlspark_tpu.dl.paged_kv import pool_block_bytes
    from mmlspark_tpu.dl.text_encoder import make_attention_fn
    from mmlspark_tpu.obs.metrics import MetricsRegistry
    from mmlspark_tpu.obs.profile import compile_tracker
    from mmlspark_tpu.serving.llm import LLMEngine, _bucket_window

    kw = {} if dtype is None else {"dtype": dtype}
    module = MaskedLMModel(TextEncoder(
        vocab=vocab, width=width, depth=depth, heads=heads, mlp_dim=mlp,
        attention_fn=make_attention_fn("dense", causal=True), **kw))
    cpu = _host_cpu()
    with jax.default_device(cpu) if cpu is not None else contextlib.nullcontext():
        variables = {"params": module.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]}
    variables = jax.device_put(variables, jax.devices()[0])
    prompts = llm_prompts(vocab, n=n_prompts, shared=shared,
                          prefix_len=prefix_len, lo=prompt_lo,
                          hi=prompt_hi)
    rec.update(prompt_lens=[len(p) for p in prompts],
               new_tokens=new_tokens, hbm_before_engine=_hbm())

    service = "chip-smoke-llm"
    reg = MetricsRegistry()
    engine = LLMEngine(module, variables, slots=slots,
                       block_len=block_len, max_seq_len=max_seq_len,
                       prefill_batch=prefill_batch, service=service,
                       registry=reg)        # num_blocks: the allocator's
    pools = jax.tree.leaves(engine.pools.target)
    rec.update(num_blocks=int(engine.kv.num_blocks),
               block_bytes_priced=pool_block_bytes(module.cache_spec(),
                                                   block_len),
               pool_bytes=int(sum(p.nbytes for p in pools)),
               pool_shape=list(pools[0].shape),
               hbm_after_pools=_hbm())
    _require(all(_same_device(p) for p in pools),
             "KV pools are not on the first device")

    # every prefill window this traffic can ask for: whole prompts, and
    # the suffixes left once the shared prefix is reused
    lens = {len(p) for p in prompts} | \
        {len(p) - prefix_len for p in prompts[:shared]}
    windows = sorted({_bucket_window(n) for n in lens if n > 0} | {1})
    engine.warm(prefill_windows=tuple(windows), mark_steady=True)
    try:
        # the first shared-prefix prompt arrives one step ahead, so that
        # its blocks are published when the others are allocated
        engine.submit(0, prompts[0], new_tokens)
        done = dict(engine.step())
        for i, p in enumerate(prompts[1:], start=1):
            engine.submit(i, p, new_tokens)
        done.update(engine.run_until_drained())
        compile_tracker.assert_steady_state()
        rec["compiles_after_warm"] = 0
    finally:
        compile_tracker.unmark_steady()

    snap = reg.snapshot()

    def total(prefix: str) -> float:
        return sum(v for k, v in snap.items() if k.startswith(prefix)
                   and f'service="{service}"' in k)

    decode_args = (
        variables["params"], None, engine.pools.target, engine.pools.draft,
        *engine.programs.blank(True, None))
    decode_text = engine.programs.get(True, None).lower(
        *decode_args).as_text()
    rec.update(prefill_windows=windows,
               decode_kernel_calls=decode_text.count(KERNEL),
               decode_steps=int(total("gen_decode_steps_total")),
               prefix_hits=int(total("kv_prefix_hits_total")),
               tokens_reused=int(total("kv_prefix_tokens_reused_total")))
    _require(set(done) == set(range(n_prompts)),
             f"engine finished {sorted(done)} of {n_prompts} sequences")
    _require(rec["prefix_hits"] > 0, "no prefix-cache hit")
    if _on_tpu():
        _require(rec["decode_kernel_calls"] >= 1,
                 f"decode program holds no {KERNEL}")

    # the engine's own contract: greedy tokens identical to dl.generate
    ref = {}
    for i, p in enumerate(prompts):
        ref[i] = np.asarray(generate(
            module, variables, p[None, :], max_new_tokens=new_tokens,
            temperature=0.0)[0])[:len(p) + new_tokens]
    got = {i: np.asarray(done[i]) for i in done}
    diverged = {}
    for i, p in enumerate(prompts):
        _require(len(got[i]) == len(p) + new_tokens and
                 np.array_equal(got[i][:len(p)], p),
                 f"sequence {i}: wrong length or prompt not echoed")
        neq = np.flatnonzero(got[i] != ref[i])
        if neq.size:
            diverged[i] = int(neq[0])
    rec["identical_to_generate"] = f"{n_prompts - len(diverged)}/{n_prompts}"
    if diverged:
        rec.update(explain_divergence(module, variables, prompts, got, ref,
                                      diverged, max_seq_len))
        _require(rec["divergence_verdict"] == "rounding",
                 "greedy tokens differ from dl.generate in sequences "
                 f"{sorted(diverged)} by more than rounding explains "
                 f"(first divergent index each: {diverged})")


def _post(addr, body: bytes, timeout: float = 60.0):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("POST", "/", body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def phase_serving(rec: dict, model, rows, *, n_requests: int = 50,
                  clients: int = 4) -> None:
    """``model``/``rows``: the classifier ``phase_gbdt`` fitted and its
    held-out feature rows."""
    import jax
    import numpy as np

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.io.http.schema import HTTPResponseData
    from mmlspark_tpu.native.loader import get_httpfront
    from mmlspark_tpu.serving import serving_query

    col = model.getProbabilityCol()
    rows = np.ascontiguousarray(rows[:n_requests], np.float32)
    expected = np.asarray(
        model.transform(DataFrame({"features": rows}))[col])[:, 1]
    scored_on: set = set()
    leaf_nodes = model.booster._leaf_nodes

    def leaf_nodes_seen(x, t_end):
        # the jitted tree walk inside every served model.transform:
        # its output's device is where the request was scored
        out = leaf_nodes(x, t_end)
        scored_on.update(d.platform for d in out.devices())
        return out

    def transform(df):
        x = np.stack([np.frombuffer(r.entity, np.float32)
                      for r in df["request"]])
        prob = model.transform(DataFrame({"features": x}))[col]
        replies = np.empty(len(df), object)
        replies[:] = [HTTPResponseData(
            status_code=200, entity=np.float32(p[1]).tobytes())
            for p in prob]
        return df.with_column("reply", replies)

    have_gxx = shutil.which("g++") is not None
    rec.update(requests_per_front=n_requests, gxx=have_gxx, fronts={})
    backends = ["python"] + (["native"] if have_gxx else [])
    if have_gxx:
        # with a toolchain present, a source that no longer builds is a
        # failure here, not a quiet fall back to the Python front
        _require(get_httpfront() is not None,
                 "g++ is installed but the native front failed to build "
                 "(see the mmlspark_tpu.native warning above)")
    model.booster._leaf_nodes = leaf_nodes_seen
    for backend in backends:
        query = serving_query(f"chip-smoke-{backend}", transform,
                              reply_timeout=60.0, backend=backend)
        front = type(query.server).__name__
        got = np.full(n_requests, np.nan, np.float32)
        errors: list = []

        def client(k: int):   # a thread that never touches JAX
            for i in range(k, n_requests, clients):
                try:
                    status, body = _post(query.server.address,
                                         rows[i].tobytes())
                    if status != 200:
                        raise RuntimeError(f"HTTP {status}")
                    got[i] = np.frombuffer(body, np.float32)[0]
                except Exception as e:      # reported below, never lost
                    errors.append(f"request {i}: {e!r}")

        try:
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            query.stop()
        diff = float(np.nanmax(np.abs(got - expected))) \
            if not np.isnan(got).all() else float("nan")
        rec["fronts"][backend] = {"answered_by": front,
                                  "errors": errors[:3],
                                  "max_abs_diff_vs_transform": diff}
        _require(not errors, f"{front}: {errors[:3]}")
        _require(np.isfinite(got).all() and diff <= 1e-6,
                 f"{front}: replies differ from model.transform by {diff}")
    del model.booster._leaf_nodes       # the class's method again
    rec["scored_on"] = sorted(scored_on)
    _require(scored_on == {jax.devices()[0].platform},
             f"scoring ran on {sorted(scored_on)}")
    if have_gxx:
        _require(rec["fronts"]["native"]["answered_by"]
                 != rec["fronts"]["python"]["answered_by"],
                 "the native front did not answer")


# ------------------------------------------------------- four chips only

def phase_gbdt_sharded(rec: dict, *, chips: int = 4, n_rows: int = 500_000,
                       n_features: int = 28, n_test: int = 50_000,
                       iters: int = 20, leaves: int = 31) -> None:
    """``numShards=chips`` (histogram psum over the mesh) against
    ``numShards=1`` on the same data."""
    import numpy as np

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.lightgbm import LightGBMClassifier, trainer

    feats, labels = gbdt_data(n_rows + n_test, n_features)
    train = DataFrame({"features": feats[:n_rows],
                       "label": labels[:n_rows]})
    test = DataFrame({"features": feats[n_rows:]})
    out = {}
    for shards in (1, chips):
        clf = LightGBMClassifier(numIterations=iters, numLeaves=leaves,
                                 learningRate=0.1, numShards=shards)
        # the trainer's own instrument: where it put the binned rows
        trainer._debug_capture = seen = {}
        try:
            model = clf.fit(train)
        finally:
            trainer._debug_capture = None
        col = model.getProbabilityCol()
        out[shards] = (int(model.booster.num_trees),
                       np.asarray(model.transform(test)[col])[:, 1],
                       seen["rows_placement"])
    diff = float(np.abs(out[1][1] - out[chips][1]).max())
    rec.update(rows=n_rows, trees={str(k): v[0] for k, v in out.items()},
               max_prob_diff=diff, placement=out[chips][2],
               placement_single=out[1][2])
    _require(out[1][0] == out[chips][0] == iters, "tree counts differ")
    _require(diff <= 1e-5,
             f"sharded predictions differ from single-device by {diff}")
    spread = out[chips][2]
    _require(spread.get("devices") == chips and
             spread["shard_shape"][0] < spread["global_shape"][0],
             f"training rows were not spread over {chips} devices: {spread}")


def phase_train_sharded(rec: dict, *, chips: int = 4, vocab: int = 30522,
                        width: int = 768, depth: int = 2, heads: int = 12,
                        mlp: int = 3072, seq: int = 128, batch: int = 16,
                        steps: int = 2, tol: float = 0.02) -> None:
    """The partition-rule-sharded ``BertEncoder`` train step on a
    dp=2,tp=2 mesh against the single-device step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mmlspark_tpu.dl.bert import BertEncoder
    from mmlspark_tpu.dl.train import (init_train_state,
                                       make_partitioned_train_step,
                                       make_train_step,
                                       partition_train_state)
    from mmlspark_tpu.parallel import MeshSpec, build_mesh
    from mmlspark_tpu.parallel.partition import partition_rules_for

    devices = jax.devices()[:chips]
    mesh = build_mesh(MeshSpec(dp=chips // 2, tp=2),
                      devices=np.asarray(devices))
    module = BertEncoder(vocab=vocab, width=width, depth=depth,
                         heads=heads, mlp_dim=mlp, max_len=seq,
                         pooler=False, dtype=jnp.bfloat16)
    tx = optax.sgd(1e-2)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, vocab, size=(batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, width, size=batch), jnp.int32)

    def fresh():
        return init_train_state(module, jax.random.PRNGKey(0), ids[:1], tx)

    single = make_train_step(module, tx, fetch="pooled")
    s1 = jax.device_put(fresh(), devices[0])
    ref_losses = []
    for _ in range(steps):
        s1, loss = single(s1, ids, labels)
        ref_losses.append(float(loss))

    state, shardings = partition_train_state(
        fresh(), mesh, partition_rules_for("BertEncoder"))
    from jax.sharding import NamedSharding, PartitionSpec as P
    bsh = NamedSharding(mesh, P("dp"))
    ids_s, labels_s = jax.device_put(ids, bsh), jax.device_put(labels, bsh)
    step = make_partitioned_train_step(module, tx, mesh, shardings,
                                       fetch="pooled")
    losses = []
    for _ in range(steps):
        state, loss = step(state, ids_s, labels_s)
        losses.append(float(loss))

    def spread(a):
        return (len(a.sharding.device_set),
                tuple(a.sharding.shard_shape(a.shape)), tuple(a.shape))

    leaves = jax.tree.leaves(state.params)
    split = [spread(a) for a in leaves
             if spread(a)[1] != spread(a)[2]]
    rec.update(mesh={k: int(v) for k, v in mesh.shape.items() if v > 1},
               losses=losses, losses_single=ref_losses,
               input_placement=spread(ids_s),
               param_leaves_split=f"{len(split)}/{len(leaves)}",
               largest_split_param=max(split, key=lambda s: np.prod(s[2]))
               if split else None, tolerance=tol)
    _require(all(np.isfinite(v) for v in losses), f"loss {losses}")
    _require(all(abs(a - b) <= tol * max(abs(b), 1.0)
                 for a, b in zip(losses, ref_losses)),
             f"sharded losses {losses} != single-device {ref_losses}")
    _require(spread(ids_s)[0] == chips and
             spread(ids_s)[1][0] < spread(ids_s)[2][0],
             f"inputs are not spread over {chips} devices")
    _require(all(len(a.sharding.device_set) == chips for a in leaves),
             "a parameter does not span every device")
    _require(split, "no parameter is actually split: every per-device "
             "shard has the global shape")


# ------------------------------------------------------------------- main

def run_phase(name: str, fn, clock: _CompileClock, *args, **kw):
    """Run one phase, print its line, re-raise what it raised: nothing
    is caught and carried on from."""
    # ok and the seconds first, then what was checked
    rec: dict = {"phase": name, "ok": False, "compile_s": None,
                 "run_s": None}
    c0, t0 = clock.seconds, time.perf_counter()
    try:
        result = fn(rec, *args, **kw)
        rec["ok"] = True
        return result
    except BaseException as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        raise
    finally:
        total = time.perf_counter() - t0
        compile_s = min(clock.seconds - c0, total)
        rec.update(compile_s=round(compile_s, 3),
                   run_s=round(total - compile_s, 3))
        print(json.dumps(rec, default=str), flush=True)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", action="append", choices=PHASES,
                    help="run only this phase (repeatable)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the two sharded comparisons")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "mmlspark_tpu")):
        print("chip_smoke.py runs from the root of a checkout: no "
              "mmlspark_tpu/ beside it", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found "
              f"{devices[0].platform} ({devices[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but JAX found {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2

    from mmlspark_tpu.core.aot import place_jax_cache
    cache_dir = place_jax_cache()
    print(json.dumps({"jax": jax.__version__,
                      "device_kind": devices[0].device_kind,
                      "device_count": len(devices),
                      "compile_cache_dir": cache_dir}), flush=True)
    clock = _CompileClock()

    if args.chips == 4:
        run_phase("gbdt_sharded", phase_gbdt_sharded, clock, chips=4)
        run_phase("train_sharded", phase_train_sharded, clock, chips=4)
        print(final_line(devices), flush=True)
        return 0

    want = [p for p in PHASES if not args.phase or p in args.phase]
    if "serving" in want and "gbdt" not in want:
        want.insert(want.index("serving"), "gbdt")   # it serves that model
    plain = {"featurizer": phase_featurizer, "train": phase_train,
             "encoder": phase_encoder, "llm": phase_llm}
    served = None
    for name in want:
        if name == "gbdt":
            served = run_phase(name, phase_gbdt, clock)
        elif name == "serving":
            run_phase(name, phase_serving, clock, *served)
        else:
            run_phase(name, plain[name], clock)
    print(final_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
