"""Headline benchmark suite. Prints ONE JSON line:
``{"metric", "value", "unit", "vs_baseline", "extras": {...}}``.

Primary metric: ImageFeaturizer ResNet-50 inference throughput
(BASELINE.json config 2; reference path = CNTKModel JNI evaluation,
``cntk/CNTKModel.scala:499-541``). ``vs_baseline`` is against an A100
bf16 ResNet-50 inference figure (~2500 img/s) per the BASELINE.json
"≥3× A100 on a v5e-64 pod" target — 1.0 is chip-for-chip A100 parity.

``extras`` carries the rest of the suite (review round 1 item 2):
- ``resnet50_mfu`` — achieved FLOP/s ÷ chip peak (XLA cost analysis),
  best over a batch-size sweep with bf16-cast weights.
- ``vit_mfu`` / ``encoder_mfu`` — ViT-B/16 and the long-context
  TextEncoder under the same sweep harness.
- ``train_images_per_sec`` / ``train_mfu_est`` — ResNet-50 SGD training
  step throughput (the transfer north star is a training workload).
- ``gbdt_rows_per_sec`` — LightGBMClassifier training row-scans/sec
  (rows × iterations ÷ fit seconds) on a Higgs-shaped synthetic
  (28 features; ``docs/lightgbm.md:17-21`` is the speed claim being
  chased). vs_baseline inside extras uses ~20M row-iter/s, upstream
  LightGBM's published Higgs pace on a 16-core CPU box.
- ``ranker_rows_per_sec`` / ``ranker_ndcg10`` — LightGBMRanker
  lambdarank training pace + quality on an MSLR-WEB30K-shaped synthetic
  (~100 docs/query, graded 0-4 relevance; BASELINE.json configs[2]).
- ``serving_p50_ms`` / ``serving_p99_ms`` — end-to-end HTTP latency of
  a live ServingServer with a jitted pipeline, against the reference's
  ~1 ms continuous-mode claim (``docs/mmlspark-serving.md:9-12``).

Every sub-bench is individually fault-isolated: a failure records an
``error`` string in extras and the line still prints (round-1 failure
mode was rc=1 with no line at all; VERDICT "What's weak" #1).
"""

from __future__ import annotations

import functools
import json
import os
import time
import traceback

A100_IMAGES_PER_SEC = 2500.0    # bf16 ResNet-50 inference, batch ~128
# per-chip bf16 peak, read by key from the shared PeakSpec table
# (obs.attribution)
from mmlspark_tpu.obs.attribution import peak_spec as _peak_spec
V5E_PEAK_BF16_FLOPS = _peak_spec("tpu-v5e").peak_flops
RESNET50_FLOPS_PER_IMAGE = 4.09e9   # fallback if XLA cost analysis absent
GBDT_BASELINE_ROW_ITERS = 20e6  # upstream LightGBM Higgs rows×iters/sec
SERVING_TARGET_MS = 1.0
_PLATFORM: str | None = None   # set by main() once the TPU is acquired


def _timeout_scale() -> float:
    try:
        scale = float(os.environ.get("MMLSPARK_TPU_BENCH_TIMEOUT_SCALE",
                                     "1"))
    except ValueError:
        return 1.0  # a bad knob must never cost the output line
    # 0/negative would zero every deadline and fake-timeout healthy runs
    return scale if scale > 0 else 1.0


def _watchdog(fn, extras: dict, key: str, timeout_s: float):
    """Run one sub-bench with a deadline, so that a compile that hangs
    costs one ``error_*`` key and not the whole line. The sub-bench
    runs in a daemon thread; on timeout its error is recorded, the suite
    moves on, and the final os._exit abandons the stuck thread. The
    sub-bench writes into a PRIVATE dict merged only after a successful
    join — an abandoned thread that later finishes must not race the
    shared extras (or the final json.dumps)."""
    import threading
    box: dict = {}
    scratch: dict = {}

    def run():
        try:
            box["result"] = fn(scratch)
        except Exception:
            box["error"] = traceback.format_exc()[-1500:]

    t = threading.Thread(target=run, daemon=True)
    t.start()
    deadline = timeout_s * _timeout_scale()
    t.join(deadline)
    if t.is_alive():
        extras[f"error_{key}"] = f"timed out after {deadline:.0f}s"
        return None
    extras.update(scratch)
    if "error" in box:
        extras[f"error_{key}"] = box["error"]
        return None
    return box.get("result")


@functools.lru_cache(maxsize=None)
def _phase_hist():
    """The obs registry's bench histogram — lazy so importing bench.py
    (harness smoke, --help) stays free of mmlspark_tpu imports."""
    from mmlspark_tpu.obs import registry
    return registry.histogram(
        "bench_phase_seconds",
        "bench timed-region wall seconds, by phase")


def _timed(phase: str):
    """THE bench stopwatch: ``with _timed("x") as t: ...`` then read
    ``t.seconds``. Every timed region lands in the process-wide obs
    registry (``bench_phase_seconds{phase=...}``) so bench timings sit
    on the same scrape surface as serving/training series instead of
    dying in paired ``perf_counter`` reads."""
    return _phase_hist().time(phase=phase)


def _t_block(f, x):
    """Wall seconds of one blocking call — the null-dispatch floor."""
    import jax
    with _timed("block") as t:
        jax.block_until_ready(f(x))
    return t.seconds


def _diff_timed(run_loop, iters, short, reps=2):
    """Difference two loop lengths: ``run_loop(n)`` -> blocking wall
    seconds for n chained iterations. Returns per-iteration seconds
    with the constant per-call overhead (the blocking call's dispatch
    and sync) cancelled, or None when noise swamps the delta — callers must
    DISCARD such points (clamping a non-positive delta would publish
    absurd throughput)."""
    t_short = min(run_loop(short) for _ in range(reps))
    t_long = min(run_loop(short + iters) for _ in range(reps))
    dt = (t_long - t_short) / iters
    return dt if dt > 0 else None


def _mfu_sweep(module, variables, make_input, batches, *, iters=20,
               fallback_flops_per_item=0.0, output_key=None,
               force_fallback_flops=False):
    """Best-of-batch-sweep inference throughput + MFU for one model.

    Weights are cast to bf16 (inference-only: halves the HBM weight
    traffic that bounds the small-batch regime) and live on device; the
    timed loop re-dispatches a resident input, so the number is the
    compute path, not host→device transfer. Returns
    (items/sec, mfu, best_batch, flops_per_item)."""
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    variables = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if hasattr(a, "dtype") and a.dtype == jnp.float32 else a,
        variables)
    variables = jax.device_put(variables, device)

    @jax.jit
    def forward(x):
        out = module.apply(variables, x, False)
        return out[output_key] if output_key else out

    best = (0.0, 0.0, 0, 0.0)
    per_batch: dict[int, float] = {}
    for batch in batches:
        # one failing point (e.g. the largest batch OOMing HBM) must not
        # discard the measurements already taken
        try:
            x = jax.device_put(make_input(batch), device)
            # ONE compile per point: the AOT executable serves cost
            # analysis, warmup and the timed loop (re-jitting the same
            # computation doubles the remote-compiler round trips)
            compiled = forward.lower(x).compile()
            if force_fallback_flops:
                # cross-impl MFU comparability: XLA's cost analysis
                # does not see inside a Pallas custom call, so impls
                # sharing one model must share one analytic yardstick
                # (round-5: pallas beat dense on seqs/sec yet lost on
                # cost-analysis MFU by ~40% uncounted kernel flops)
                flops_per_batch = fallback_flops_per_item * batch
            else:
                from mmlspark_tpu.parallel.compat import cost_analysis
                cost = cost_analysis(compiled)
                flops_per_batch = (cost["flops"] if cost else 0.0) or \
                    fallback_flops_per_item * batch
            compiled(x).block_until_ready()
            for _ in range(3):
                compiled(x).block_until_ready()

            # an async dispatch loop pays one dispatch-and-sync per
            # BLOCKING call, which at iters=10-20 inflates per-iter
            # time — difference it out
            def loop(n):
                with _timed("mfu_loop") as t:
                    for _ in range(n):
                        out = compiled(x)
                    out.block_until_ready()
                return t.seconds

            per_iter = _diff_timed(loop, iters, max(iters // 5, 2))
            if per_iter is None:
                continue                  # noise swamped the delta
        except Exception:
            continue
        ips = batch / per_iter
        per_batch[batch] = round(ips, 1)
        mfu = ips / batch * flops_per_batch / V5E_PEAK_BF16_FLOPS
        if ips > best[0]:
            best = (ips, mfu, batch, flops_per_batch / batch)
    if not per_batch:
        raise RuntimeError(f"every batch size in {batches} failed")
    return best, per_batch


def bench_resnet(extras: dict) -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models import ModelDownloader

    loaded = ModelDownloader().download_by_name(
        "ResNet50", allow_random_init=True)  # weights init on host CPU

    rng = np.random.default_rng(0)

    def make_input(batch):
        return jnp.asarray(rng.normal(size=(batch, 224, 224, 3)),
                           jnp.bfloat16)

    raw = os.environ.get("MMLSPARK_TPU_BENCH_RESNET_BATCHES",
                         "128,256,512")
    try:
        batches = tuple(int(b) for b in raw.split(",") if b.strip())
        assert batches
    except (ValueError, AssertionError):
        batches = (128, 256, 512)  # a bad knob must never cost the line
    (ips, mfu, batch, fpi), per_batch = _mfu_sweep(
        loaded.module, loaded.variables, make_input, batches,
        fallback_flops_per_item=RESNET50_FLOPS_PER_IMAGE,
        output_key="pooled")
    extras["resnet50_mfu"] = round(mfu, 4)
    extras["resnet50_best_batch"] = batch
    extras["resnet50_ips_by_batch"] = per_batch
    extras["resnet50_flops_per_image"] = fpi
    extras["platform"] = jax.devices()[0].platform
    # the headline vs_baseline stays the batch-128 point (the A100
    # figure is a batch~128 number and earlier rounds measured 128);
    # the sweep best is in extras
    extras["resnet50_best_images_per_sec"] = round(ips, 1)

    # end-to-end ImageFeaturizer: HOST-resident images → device →
    # pooled features, exercising TPUModel's double-buffered dispatch
    # (the number a user's featurize pipeline actually sees). Fault-
    # isolated: a failure here must not zero the headline already taken.
    try:
        from mmlspark_tpu.core import DataFrame
        from mmlspark_tpu.image import ImageFeaturizer
        n_img = 512
        imgs = rng.normal(size=(n_img, 224, 224, 3)).astype(np.float32)
        feat = ImageFeaturizer(model=loaded, cutOutputLayers=1,
                               inputCol="image", outputCol="features",
                               autoResize=False, miniBatchSize=128)
        df = DataFrame({"image": imgs})
        feat.transform(df)  # warm the (now per-instance-cached) compile
        t0 = time.perf_counter()
        feat.transform(df)
        extras["featurizer_e2e_images_per_sec"] = round(
            n_img / (time.perf_counter() - t0), 1)
        # realistic ingest: decoded JPEGs are uint8 — the wire keeps
        # them uint8 (4x fewer host->device bytes than f32), so this is
        # the number a real image pipeline sees
        imgs_u8 = (imgs - imgs.min()) / (np.ptp(imgs) + 1e-6)
        df_u8 = DataFrame(
            {"image": (imgs_u8 * 255).astype(np.uint8)})
        # depth 4: more in-flight batches overlap the transfers
        feat_u8 = ImageFeaturizer(model=loaded, cutOutputLayers=1,
                                  inputCol="image", outputCol="features",
                                  autoResize=False, miniBatchSize=128,
                                  pipelineDepth=4)
        feat_u8.transform(df_u8)  # warm
        t0 = time.perf_counter()
        feat_u8.transform(df_u8)
        extras["featurizer_e2e_u8_images_per_sec"] = round(
            n_img / (time.perf_counter() - t0), 1)
        # the u8 row runs depth 4 (vs the f32 row's default 2) — record
        # it so cross-round deltas aren't misread as framework changes
        extras["featurizer_e2e_u8_pipeline_depth"] = 4
        # attribution: host prep vs async submit (incl. transfer
        # enqueue) vs device-wait+pull (review round 3 Weak #6)
        if feat_u8.last_transform_stats:
            extras["featurizer_e2e_breakdown_ms"] = \
                feat_u8.last_transform_stats
    except Exception:
        extras["error_featurizer"] = traceback.format_exc()[-800:]

    # int8 post-training quantization (models/quantize.py): the v5e
    # MXU runs int8 at 2x the bf16 rate — measure what that buys the
    # featurizer's scoring path, with the fidelity number alongside so
    # the speedup is never quoted without its accuracy cost. Fault-
    # isolated; skipped off-accelerator (int8 conv on CPU crawls).
    try:
        if _PLATFORM != "tpu":
            extras["resnet50_int8_skipped"] = \
                f"no accelerator ({_PLATFORM})"
        else:
            from mmlspark_tpu.models.quantize import (
                quantization_fidelity, quantize_resnet)
            qf, qp = quantize_resnet(loaded.module, loaded.variables)
            qp = jax.device_put(qp, jax.devices()[0])
            q_compiled = jax.jit(qf)
            xb = jax.device_put(
                jnp.asarray(rng.normal(size=(batch, 224, 224, 3)),
                            jnp.float32), jax.devices()[0])
            jax.block_until_ready(q_compiled(qp, xb))

            def loop(n):
                t0 = time.perf_counter()
                for _ in range(n):
                    out = q_compiled(qp, xb)
                out.block_until_ready()
                return time.perf_counter() - t0

            per_iter = _diff_timed(loop, 20, 4)
            if per_iter is not None:
                q_ips = batch / per_iter
                extras["resnet50_int8_images_per_sec"] = round(q_ips, 1)
                extras["resnet50_int8_vs_bf16"] = round(
                    q_ips / max(ips, 1e-9), 3)
            small = np.asarray(rng.normal(size=(8, 224, 224, 3)),
                               np.float32)
            extras["resnet50_int8_fidelity_cos"] = round(
                quantization_fidelity(loaded.module, loaded.variables,
                                      q_compiled, qp, small), 5)
    except Exception:
        extras["error_resnet_int8"] = traceback.format_exc()[-600:]
    return per_batch.get(128, ips)


def bench_train(extras: dict) -> None:
    """ResNet-50 TRAINING throughput (SGD, bf16 activations) — the
    transfer-learning north star is a training workload; inference-only
    coverage was the r2 gap. FLOPs from XLA cost analysis of the
    COMPILED step (fwd+bwd+update), the same accounting bench_resnet
    uses — the round-3 analytic 3×fwd estimate undercounted the real
    conv FLOPs ~2× and made train MFU incomparable with inference MFU.
    Knobs: MMLSPARK_TPU_BENCH_TRAIN_REMAT=1 (block rematerialization),
    MMLSPARK_TPU_BENCH_TRAIN_OPT_BF16=1 (bf16 momentum buffer — halves
    the optimizer-state HBM traffic per step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mmlspark_tpu.dl.train import (init_train_state, make_train_step,
                                       train_epoch)
    from mmlspark_tpu.models import ModelDownloader

    remat = os.environ.get("MMLSPARK_TPU_BENCH_TRAIN_REMAT") == "1"
    opt_bf16 = os.environ.get("MMLSPARK_TPU_BENCH_TRAIN_OPT_BF16") == "1"
    loaded = ModelDownloader().download_by_name(
        "ResNet50", num_classes=100, allow_random_init=True,
        remat=remat or None)
    if remat:
        extras["train_remat"] = True
    tx = optax.sgd(1e-2, momentum=0.9,
                   accumulator_dtype=jnp.bfloat16 if opt_bf16 else None)
    if opt_bf16:
        extras["train_opt_bf16"] = True
    rng = np.random.default_rng(3)
    raw = os.environ.get("MMLSPARK_TPU_BENCH_TRAIN_BATCHES", "128,256")
    try:
        batches = tuple(int(b) for b in raw.split(",") if b.strip())
        assert batches
    except (ValueError, AssertionError):
        batches = (128, 256)
    device = jax.devices()[0]
    step = make_train_step(loaded.module, tx)
    per_batch: dict[int, float] = {}
    flops_per_image = 0.0
    e2e_step, e2e_batch = None, 0  # first SUCCESSFUL point's executable
    iters = 10
    loss = None
    for batch in batches:
        try:
            # fresh state per point: the step donates its input state,
            # and a larger batch must not inherit a donated-away buffer
            state = jax.device_put(
                init_train_state(loaded.module, jax.random.PRNGKey(0),
                                 np.zeros((1, 224, 224, 3), np.float32),
                                 tx),
                device)
            x = jax.device_put(jnp.asarray(
                rng.normal(size=(batch, 224, 224, 3)), jnp.float32),
                device)
            y = jax.device_put(jnp.asarray(
                rng.integers(0, 100, size=batch), jnp.int32), device)
            # ONE compile per point (AOT), serving cost analysis too
            compiled = step.lower(state, x, y).compile()
            if not flops_per_image:  # any successful point serves it
                from mmlspark_tpu.parallel.compat import cost_analysis
                cost = cost_analysis(compiled)
                flops_per_image = \
                    (cost["flops"] if cost else 0.0) / batch
            state, loss = compiled(state, x, y)   # warm
            jax.block_until_ready(loss)

            # overhead-cancelling differencing (same as _mfu_sweep).
            # The donated train state threads through a box.
            box = {"s": state, "loss": loss}

            def loop(n):
                s = box["s"]
                t0 = time.perf_counter()
                for _ in range(n):
                    s, lo = compiled(s, x, y)
                jax.block_until_ready(lo)
                box["s"], box["loss"] = s, lo
                return time.perf_counter() - t0

            per_iter = _diff_timed(loop, iters, 2)
            if per_iter is None:
                raise RuntimeError("timing noise swamped the delta")
            per_batch[batch] = round(batch / per_iter, 1)
            state, loss = box["s"], box["loss"]
            assert np.isfinite(float(loss))
            if e2e_step is None:  # first point that RAN successfully
                e2e_step, e2e_batch = compiled, batch
            del state, x, y
        except Exception:
            # one failing point (e.g. the largest batch OOMing HBM)
            # must not discard the measurements already taken
            extras[f"error_train_batch_{batch}"] = \
                traceback.format_exc()[-400:]
    if not per_batch:
        raise RuntimeError("every train batch size failed")
    if not flops_per_image:  # cost analysis unavailable: analytic 3×fwd
        flops_per_image = 3 * RESNET50_FLOPS_PER_IMAGE
    # headline stays the FIRST (=128 by default) point for cross-round
    # comparability, like bench_resnet; the sweep best rides extras
    headline = per_batch.get(batches[0], next(iter(per_batch.values())))
    best_batch = max(per_batch, key=per_batch.get)
    extras["train_images_per_sec"] = round(headline, 1)
    extras["train_best_batch"] = best_batch
    extras["train_best_images_per_sec"] = per_batch[best_batch]
    extras["train_ips_by_batch"] = per_batch
    extras["train_flops_per_image"] = flops_per_image
    # under remat the cost analysis counts recompute FLOPs, so the
    # ratio is hardware-FLOPs utilization (HFU), not MFU — report it
    # under a distinct key so remat/non-remat runs stay comparable
    util_key = "train_hfu_est" if remat else "train_mfu_est"
    extras[util_key] = round(
        headline * flops_per_image / V5E_PEAK_BF16_FLOPS, 4)
    extras[util_key.replace("_est", "_best")] = round(
        per_batch[best_batch] * flops_per_image / V5E_PEAK_BF16_FLOPS, 4)

    # e2e: HOST-resident batches through the overlapped-transfer loop
    # (dl.train.train_epoch) — the number a fine-tune pipeline sees,
    # fault-isolated like the featurizer e2e. Reuses the batch[0] AOT
    # executable: lower().compile() bypasses step's jit cache, so
    # calling `step` here would re-trace + re-compile the whole graph.
    try:
        eb = e2e_batch
        state = jax.device_put(
            init_train_state(loaded.module, jax.random.PRNGKey(0),
                             np.zeros((1, 224, 224, 3), np.float32), tx),
            device)
        host_batches = [
            (rng.normal(size=(eb, 224, 224, 3)).astype(np.float32),
             rng.integers(0, 100, size=eb).astype(np.int32))
            for _ in range(4)]
        state, _ = train_epoch(e2e_step, state, host_batches[:1])  # warm
        t0 = time.perf_counter()
        state, losses = train_epoch(e2e_step, state, host_batches)
        extras["train_e2e_images_per_sec"] = round(
            eb * len(host_batches) / (time.perf_counter() - t0), 1)
    except Exception:
        extras["error_train_e2e"] = traceback.format_exc()[-400:]


def bench_vit(extras: dict) -> None:
    """ViT-B/16 inference MFU: transformer blocks are pure matmuls, the
    cleanest MXU utilization read the zoo offers."""
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models import ModelDownloader

    loaded = ModelDownloader().download_by_name(
        "ViT_B_16", allow_random_init=True)
    rng = np.random.default_rng(1)

    def make_input(batch):
        return jnp.asarray(rng.normal(size=(batch, 224, 224, 3)),
                           jnp.bfloat16)

    # analytic fallback when XLA cost analysis is unavailable:
    # ViT-B/16 at 224² is ~17.6 GFLOPs/image (the published figure)
    (ips, mfu, batch, _), per_batch = _mfu_sweep(
        loaded.module, loaded.variables, make_input, (64, 128, 256),
        fallback_flops_per_item=17.6e9, output_key="pooled")
    extras["vit_images_per_sec"] = round(ips, 1)
    extras["vit_mfu"] = round(mfu, 4)
    extras["vit_best_batch"] = batch
    extras["vit_ips_by_batch"] = per_batch


def make_bench_encoder(impl: str):
    """TextEncoder forward MFU at a long-context shape, one attention
    impl per sub-bench (XLA dense vs the fused Pallas flash kernel,
    ``dl/pallas_attention.py``). Separate watchdog keys: a slow pallas
    compile must not discard a completed dense measurement."""

    def bench(extras: dict) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from mmlspark_tpu.dl.text_encoder import TextEncoder, \
            make_attention_fn

        raw_shape = os.environ.get("MMLSPARK_TPU_BENCH_ENCODER_SHAPE",
                                   "512,8,2048,2048")
        try:
            W, depth, mlp, T = (int(x) for x in raw_shape.split(","))
        except ValueError:
            W, depth, mlp, T = 512, 8, 2048, 2048
        rng = np.random.default_rng(2)
        ids0 = jnp.asarray(rng.integers(1, 32768, size=(1, T)),
                           jnp.int32)

        def make_input(batch):
            return jnp.asarray(rng.integers(1, 32768, size=(batch, T)),
                               jnp.int32)

        # analytic transformer-FLOPs fallback: per token per block,
        # qkv+out 8W², mlp 4·W·mlp, attention 4·T·W
        flops_per_seq = depth * T * (8 * W * W + 4 * W * mlp
                                     + 4 * T * W)
        module = TextEncoder(vocab=32768, width=W, depth=depth, heads=8,
                             mlp_dim=mlp,
                             attention_fn=make_attention_fn(impl))
        # init traces the forward: do it with the dense attention_fn
        # (attention has no params, so the variables are identical) —
        # tracing the Pallas kernel under a CPU default_device would
        # either fail to lower or crawl through the interpreter
        init_module = TextEncoder(vocab=32768, width=W, depth=depth,
                                  heads=8, mlp_dim=mlp,
                                  attention_fn=make_attention_fn("dense"))
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            variables = init_module.init(jax.random.PRNGKey(0), ids0,
                                         False)
        (ips, mfu, batch, _), per_batch = _mfu_sweep(
            module, variables, make_input, (8, 16, 32), iters=10,
            fallback_flops_per_item=float(flops_per_seq),
            output_key="pooled", force_fallback_flops=True)
        extras[f"encoder_mfu_{impl}"] = round(mfu, 4)
        extras[f"encoder_ips_by_batch_{impl}"] = per_batch
        extras[f"encoder_seqs_per_sec_{impl}"] = round(ips, 1)
        extras[f"encoder_best_batch_{impl}"] = batch

        # train-step pace at the same long-context shape: exercises the
        # backward (pallas = fused FA2-style dq/dkv kernels; dense = XLA
        # autodiff through the materialized scores). Fault-isolated: a
        # bwd OOM must not discard the forward numbers.
        try:
            import optax

            from mmlspark_tpu.dl.train import (init_train_state,
                                               make_train_step)
            tb = 8
            tx = optax.sgd(1e-3)
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                state0 = init_train_state(
                    init_module, jax.random.PRNGKey(1), ids0, tx)
            state = jax.device_put(state0, jax.devices()[0])
            del state0
            xb = make_input(tb)
            yb = jnp.asarray(rng.integers(0, 2, size=tb), jnp.int32)
            step = make_train_step(
                module, tx, fetch="pooled",
                loss_fn=lambda pooled, y: jnp.mean(
                    (pooled.mean(-1) - y) ** 2))
            state, loss = step(state, xb, yb)     # compile + warm
            jax.block_until_ready(loss)

            # same RTT-cancelling differencing as _mfu_sweep; the
            # train state threads through a mutable box so each timed
            # loop continues from the last
            box = {"state": state}

            def loop(n):
                s = box["state"]
                t0 = time.perf_counter()
                for _ in range(n):
                    s, loss = step(s, xb, yb)
                jax.block_until_ready(loss)
                box["state"] = s
                return time.perf_counter() - t0

            per_iter = _diff_timed(loop, 5, 2)
            if per_iter is None:
                raise RuntimeError("timing noise swamped the delta")
            extras[f"encoder_train_seqs_per_sec_{impl}"] = round(
                tb / per_iter, 1)
        except Exception:
            extras[f"error_encoder_train_{impl}"] = \
                traceback.format_exc()[-500:]

    return bench


_ENCODER_IMPLS = ("dense", "pallas", "blockwise")


def _finalize_encoder(extras: dict, impls=_ENCODER_IMPLS) -> None:
    """Promote the fastest impl's numbers to the headline encoder keys."""
    best = None
    for impl in impls:
        ips = extras.get(f"encoder_seqs_per_sec_{impl}")
        if ips is not None and (best is None
                                or ips > extras[
                                    f"encoder_seqs_per_sec_{best}"]):
            best = impl
    if best is None:
        return  # every impl errored/timed out; error_* keys tell why
    extras["encoder_seqs_per_sec"] = extras[f"encoder_seqs_per_sec_{best}"]
    extras["encoder_mfu"] = extras[f"encoder_mfu_{best}"]
    extras["encoder_best_batch"] = extras[f"encoder_best_batch_{best}"]
    extras["encoder_ips_by_batch"] = extras[
        f"encoder_ips_by_batch_{best}"]
    extras["encoder_best_impl"] = best


def bench_encoder_int8(extras: dict) -> None:
    """int8 (w8a8-dynamic) TextEncoder vs the bf16 pallas path at the
    same long-context shape — what the 2x int8 MXU rate buys the
    embedding/scoring path, with fidelity alongside."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.dl.text_encoder import TextEncoder
    from mmlspark_tpu.models.quantize import quantize_text_encoder

    if _PLATFORM != "tpu":
        extras["encoder_int8_skipped"] = f"no accelerator ({_PLATFORM})"
        return
    raw_shape = os.environ.get("MMLSPARK_TPU_BENCH_ENCODER_SHAPE",
                               "512,8,2048,2048")
    try:
        W, depth, mlp, T = (int(x) for x in raw_shape.split(","))
    except ValueError:
        W, depth, mlp, T = 512, 8, 2048, 2048
    rng = np.random.default_rng(2)
    module = TextEncoder(vocab=32768, width=W, depth=depth, heads=8,
                         mlp_dim=mlp)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        variables = module.init(
            jax.random.PRNGKey(0),
            jnp.asarray(rng.integers(1, 32768, size=(1, T)),
                        jnp.int32), False)
    qf, qp = quantize_text_encoder(module, variables)
    qp = jax.device_put(qp, jax.devices()[0])
    f = jax.jit(qf)
    B = 8
    ids = jax.device_put(
        jnp.asarray(rng.integers(1, 32768, size=(B, T)), jnp.int32),
        jax.devices()[0])
    jax.block_until_ready(f(qp, ids))

    def loop(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(qp, ids)
        out.block_until_ready()
        return time.perf_counter() - t0

    per_iter = _diff_timed(loop, 10, 2)
    if per_iter is None:
        raise RuntimeError("timing noise swamped the delta")
    extras["encoder_int8_seqs_per_sec"] = round(B / per_iter, 1)
    # the int8-vs-bf16 ratio is computed in main() AFTER this
    # sub-bench merges: _watchdog hands each sub-bench a private
    # scratch dict, so the encoder rows are not visible from here
    from mmlspark_tpu.models.quantize import quantization_fidelity
    small = jnp.asarray(rng.integers(1, 32768, size=(2, 256)),
                        jnp.int32)
    extras["encoder_int8_fidelity_cos"] = round(
        quantization_fidelity(module, variables, f, qp, small), 5)


def bench_flash_causal(extras: dict) -> None:
    """Causal-vs-full flash attention timing at T=2048 (review round 4 task
    1b): the pruned-grid causal kernel should approach the ~2x saving
    the lower-triangular structure implies. Also times the packed
    kernel against the pl.when streaming formulation so the pruning
    claim is measured, not asserted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.dl.pallas_attention import flash_attention

    if _PLATFORM != "tpu":
        # off-TPU the kernel would crawl through the Pallas interpreter
        # at T=2048 and burn the whole watchdog (same reasoning as the
        # encoder bench's dense-path fallback)
        extras["flash_causal_skipped"] = f"no accelerator ({_PLATFORM})"
        return

    rng = np.random.default_rng(0)
    B, H, T, D = 2, 8, 2048, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
               for _ in range(3))
    q, k, v = (jax.device_put(a, jax.devices()[0]) for a in (q, k, v))

    # at this shape one kernel run is tens of µs — below a blocking
    # call's dispatch noise. Chain the kernel on-device (one jit whose
    # scan feeds each output back as the next query) so executions
    # serialize, AND difference two scan lengths so the single blocking
    # call's dispatch cost cancels out. iters must be large enough that
    # the kernel delta (iters × tens of µs) dwarfs the call-to-call
    # jitter: iters=50 produced negative differences
    def timed(causal, iters=400, base=50, reps=5):
        progs: dict = {}

        def run_loop(n):
            f = progs.get(n)
            if f is None:
                @jax.jit
                def chained(q0, _n=n):
                    def body(qc, _):
                        return flash_attention(qc, k, v,
                                               causal=causal), None
                    return jax.lax.scan(body, q0, None, length=_n)[0]
                jax.block_until_ready(chained(q))  # compile + warm
                progs[n] = f = chained
            t0 = time.perf_counter()
            jax.block_until_ready(f(q))
            return time.perf_counter() - t0

        per_iter = _diff_timed(run_loop, iters, base, reps=reps)
        if per_iter is None:
            raise RuntimeError("timing noise swamped the delta")
        return per_iter

    t_full = timed(False)
    t_causal = timed(True)
    extras["flash_full_ms_t2048"] = round(t_full * 1e3, 3)
    extras["flash_causal_ms_t2048"] = round(t_causal * 1e3, 3)
    extras["flash_causal_speedup_t2048"] = round(t_full / t_causal, 3)

    # the causal saving is the pruned-cell fraction, which approaches
    # the triangle's 2x only when T >> block: ~37% of cells prune at
    # T=2048 (bq=256, bk=512) vs ~47% at T=8192 — so also measure a
    # genuinely long sequence (B=1 keeps it inside the packed-KV VMEM
    # budget)
    B, H, T = 1, 8, 8192
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
               for _ in range(3))
    q, k, v = (jax.device_put(a, jax.devices()[0]) for a in (q, k, v))
    t_full = timed(False)
    t_causal = timed(True)
    extras["flash_full_ms_t8192"] = round(t_full * 1e3, 3)
    extras["flash_causal_ms_t8192"] = round(t_causal * 1e3, 3)
    extras["flash_causal_speedup_t8192"] = round(t_full / t_causal, 3)


def bench_gen(extras: dict) -> None:
    """Autoregressive decode throughput over the causal LM: batched
    prefill + KV-cached scan (``dl/generate.py``). Rows: prefill
    tokens/sec (one causal forward seeding the caches — MXU-batched),
    per-step decode latency/throughput, a batch sweep, and the
    cached-vs-re-encode speedup the KV cache exists to buy. No
    reference counterpart (text generation is the framework's
    extension axis, SURVEY §5)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.dl import MaskedLMModel, TextEncoder
    from mmlspark_tpu.dl.generate import generate
    from mmlspark_tpu.dl.text_encoder import make_attention_fn

    rng = np.random.default_rng(0)
    vocab, W, depth, mlp = 32768, 512, 8, 2048
    enc = TextEncoder(vocab=vocab, width=W, depth=depth, heads=8,
                      mlp_dim=mlp,
                      attention_fn=make_attention_fn("dense",
                                                     causal=True))
    module = MaskedLMModel(enc)
    # random weights: throughput does not depend on what the model
    # learned; init on the host CPU backend (same stance as
    # bench_encoder)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        variables = {"params": module.init(
            jax.random.PRNGKey(0),
            jnp.ones((1, 8), jnp.int32))["params"]}
    variables = jax.device_put(variables, jax.devices()[0])

    # 129 so the prefill bucket (multiples of 64) covers all but the
    # last prompt position — the split below then measures a FULL
    # batched prefill, not a half-streamed one
    Tp, new = 129, 128

    def timed(ids, n_new, use_cache=True, iters=3, max_len=None):
        generate(module, variables, ids, max_new_tokens=n_new,
                 use_cache=use_cache, max_len=max_len)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            generate(module, variables, ids, max_new_tokens=n_new,
                     use_cache=use_cache, max_len=max_len)
        return (time.perf_counter() - t0) / iters

    def prompts(B, T=Tp):
        return rng.integers(2, vocab, size=(B, T)).astype(np.int32)

    # prefill/decode split: new=1 is prefill + one scan step; the
    # difference to new=1+N spreads over exactly N more scan steps.
    # max_len is pinned so both programs run the same buffer/cache
    # shapes — the difference is then exactly N scan steps (and the
    # per-call dispatch RTT cancels)
    B = 32
    ids = prompts(B)
    L = Tp + new + 1
    t_one = timed(ids, 1, max_len=L)
    t_full = timed(ids, new + 1, max_len=L)
    per_step = (t_full - t_one) / new
    # t_one still contains one full blocking-dispatch RTT (the
    # differencing above only cancels it out of per_step) — measure
    # the null-dispatch floor explicitly and take it out of the
    # prefill. Discard the row if noise leaves nothing.
    nul = jax.jit(lambda a: a + 1)
    z = jnp.zeros((8,), jnp.int32)
    jax.block_until_ready(nul(z))
    t_rtt = min(_t_block(nul, z) for _ in range(5))
    t_prefill = t_one - per_step - t_rtt
    if t_prefill > 0:
        extras["gen_prefill_tokens_per_sec"] = round(
            B * Tp / t_prefill, 1)
    extras["gen_decode_ms_per_step"] = round(per_step * 1000, 3)
    extras["gen_decode_tokens_per_sec"] = round(B / per_step, 1)
    extras["gen_tokens_per_sec"] = round(B * (new + 1) / t_full, 1)

    by_batch = {}
    for b in (1, 8, 32):
        by_batch[str(b)] = round(
            b * (new + 1) / timed(prompts(b), new + 1), 1)
    extras["gen_tokens_per_sec_by_batch"] = by_batch

    # what the KV cache buys: the re-encode reference recomputes the
    # whole O(L²·W) forward every step. Two traps fixed here (round-5
    # bench saw 0.91x): the comparison must run at a length where the
    # quadratic term is visible (at L ≤ 64 both paths are launch-bound
    # scans and the ratio measures cache-update overhead), and the
    # per-call dispatch cost must not pad both sides of
    # the ratio — so compare PER-STEP costs by differencing 1 vs 64
    # new tokens at a pinned max_len.
    ids2 = prompts(8, 257)
    L2 = 257 + 65

    def per_step(use_cache):
        t1 = timed(ids2, 1, use_cache=use_cache, max_len=L2)
        t64 = timed(ids2, 64, use_cache=use_cache, max_len=L2)
        return max((t64 - t1) / 63, 1e-9)

    extras["gen_cached_vs_reencode_speedup"] = round(
        per_step(False) / per_step(True), 2)

    # speculative decode, B=1 (the launch-latency-bound case): draft =
    # target is the acceptance UPPER BOUND (every proposal accepted,
    # k+1 tokens per verify pass) — random weights give a real draft
    # no way to agree, so this row measures what the machinery buys at
    # full acceptance, labeled as such. Output equality with plain
    # greedy is pinned by test regardless.
    try:
        from mmlspark_tpu.dl.speculative import generate_speculative
        ids1 = prompts(1)
        new1 = 64

        def timed_spec(iters=3):
            generate_speculative(module, variables, module, variables,
                                 ids1, max_new_tokens=new1, k=4)
            t0 = time.perf_counter()
            rate = 0.0
            for _ in range(iters):
                _, rate = generate_speculative(
                    module, variables, module, variables, ids1,
                    max_new_tokens=new1, k=4)
            return (time.perf_counter() - t0) / iters, rate

        t_spec, rate = timed_spec()
        t_plain = timed(ids1, new1, max_len=Tp + new1)
        extras["gen_spec_tokens_per_sec_b1"] = round(new1 / t_spec, 1)
        extras["gen_spec_tokens_per_pass"] = round(rate, 2)
        extras["gen_spec_vs_plain_b1"] = round(t_plain / t_spec, 2)

        # batched greedy speculation (sync-on-min): B=8 self-draft
        ids8 = prompts(8)
        generate_speculative(module, variables, module, variables,
                             ids8, max_new_tokens=new1, k=4)
        t0 = time.perf_counter()
        for _ in range(3):
            _, rate8 = generate_speculative(
                module, variables, module, variables, ids8,
                max_new_tokens=new1, k=4)
        t_spec8 = (time.perf_counter() - t0) / 3
        extras["gen_spec_tokens_per_sec_b8"] = round(
            8 * new1 / t_spec8, 1)
        extras["gen_spec_b8_tokens_per_pass"] = round(rate8, 2)
    except Exception:
        extras["error_gen_spec"] = traceback.format_exc()[-500:]


def bench_gbdt(extras: dict) -> None:
    """LightGBM-equivalent training throughput, Higgs-shaped synthetic
    (28 features, the dataset of the reference's speed claim)."""
    import numpy as np

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.lightgbm import LightGBMClassifier

    n_rows = int(os.environ.get("MMLSPARK_TPU_BENCH_GBDT_ROWS", 500_000))
    n_iters = int(os.environ.get("MMLSPARK_TPU_BENCH_GBDT_ITERS", 20))
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(n_rows, 28)).astype(np.float32)
    margin = feats[:, :4].sum(1) + feats[:, 4] * feats[:, 5]
    labels = (margin + rng.normal(size=n_rows) > 0).astype(np.float32)
    df = DataFrame({"features": feats, "label": labels})

    clf = LightGBMClassifier(numIterations=n_iters, numLeaves=31,
                             learningRate=0.1)
    clf.fit(df)  # warm the compile cache (binning + tree growth kernels)
    t0 = time.perf_counter()
    model = clf.fit(df)
    dt = time.perf_counter() - t0

    rows_per_sec = n_rows * n_iters / dt
    extras["gbdt_rows_per_sec"] = round(rows_per_sec, 1)
    extras["gbdt_fit_seconds"] = round(dt, 3)
    extras["gbdt_vs_lightgbm_cpu"] = round(
        rows_per_sec / GBDT_BASELINE_ROW_ITERS, 3)

    # scoring pace (the serving-relevant half; the reference scores
    # per-row over JNI, LightGBMBooster.score — here one batched
    # dispatch routes all rows through all trees)
    model.transform(df)  # warm
    t0 = time.perf_counter()
    model.transform(df)
    extras["gbdt_score_rows_per_sec"] = round(
        n_rows / (time.perf_counter() - t0), 1)


def bench_ranker(extras: dict) -> None:
    """LightGBMRanker lambdarank training pace on MSLR-WEB30K-shaped data
    (100 docs/query, graded 0-4 relevance from a latent utility)."""
    import numpy as np

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.lightgbm import LightGBMRanker

    n_queries = int(os.environ.get("MMLSPARK_TPU_BENCH_RANKER_QUERIES",
                                   1000))
    docs, n_iters = 100, 10
    n = n_queries * docs
    rng = np.random.default_rng(5)
    x = rng.normal(size=(n, 32)).astype(np.float32)
    w_true = rng.normal(size=32).astype(np.float32)
    util = x @ w_true + rng.normal(scale=2.0, size=n).astype(np.float32)
    rel = np.digitize(util, np.quantile(util, [0.5, 0.75, 0.9, 0.97])) \
        .astype(np.float32)
    qid = np.repeat(np.arange(n_queries), docs)
    df = DataFrame({"features": x, "label": rel, "query": qid})
    kw = dict(groupCol="query", numIterations=n_iters, numLeaves=31,
              seed=0)
    LightGBMRanker(**kw).fit(df)  # warm the compile cache
    t0 = time.perf_counter()
    m = LightGBMRanker(**kw).fit(df)
    dt = time.perf_counter() - t0
    extras["ranker_rows_per_sec"] = round(n * n_iters / dt, 1)
    extras["ranker_fit_seconds"] = round(dt, 3)
    extras["ranker_ndcg10"] = round(m.evaluate_ndcg(df, k=10), 4)


def bench_gbdt_sparse(extras: dict) -> None:
    """Padded-COO GBDT training pace on hashed-text-shaped data (high
    logical width, few entries per row) — the sparse engine
    (``lightgbm/sparse.py``) had no perf number before this."""
    import numpy as np

    from mmlspark_tpu.lightgbm.sparse import SparseData
    from mmlspark_tpu.lightgbm.trainer import TrainConfig, train

    n_rows = int(os.environ.get("MMLSPARK_TPU_BENCH_SPARSE_ROWS",
                                200_000))
    width, F, n_iters = 32, 10_000, 10
    rng = np.random.default_rng(13)
    # unique indices per row (the SparseData invariant): draw a wide
    # permutation block-wise to stay cheap at bench scale
    idx = np.stack([rng.choice(F, size=width, replace=False)
                    for _ in range(512)])
    idx = np.tile(idx, (n_rows // 512 + 1, 1))[:n_rows].astype(np.int32)
    val = rng.normal(size=(n_rows, width)).astype(np.float32)
    w_sig = rng.normal(size=F).astype(np.float32)
    margin = (val * w_sig[idx]).sum(1)
    y = (margin > 0).astype(np.float32)
    sd = SparseData(idx, val, F)
    cfg = TrainConfig(objective="binary", num_iterations=n_iters,
                      num_leaves=31, learning_rate=0.1)
    train(sd, y, None, cfg)  # warm the compile cache
    t0 = time.perf_counter()
    train(sd, y, None, cfg)
    dt = time.perf_counter() - t0
    extras["gbdt_sparse_rows_per_sec"] = round(n_rows * n_iters / dt, 1)
    extras["gbdt_sparse_fit_seconds"] = round(dt, 3)


def bench_vw(extras: dict) -> None:
    """VowpalWabbit-equivalent online learning pace: murmur-hash
    featurization (native batch hasher) + AdaGrad sparse SGD on device —
    the reference's third engine (``vw/VowpalWabbitBase.scala``) had no
    bench row before this."""
    import numpy as np

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.vw import (VowpalWabbitClassifier,
                                 VowpalWabbitFeaturizer)

    n_rows = int(os.environ.get("MMLSPARK_TPU_BENCH_VW_ROWS", 200_000))
    rng = np.random.default_rng(9)
    feats = rng.normal(size=(n_rows, 30)).astype(np.float32)
    labels = (feats[:, :5].sum(1) > 0).astype(np.float32)
    df = DataFrame({"features": feats, "label": labels})

    featurizer = VowpalWabbitFeaturizer(inputCols=["features"],
                                        outputCol="vw_features")
    hashed = featurizer.transform(df)       # warm any native load
    t0 = time.perf_counter()
    hashed = featurizer.transform(df)
    extras["vw_featurize_rows_per_sec"] = round(
        n_rows / (time.perf_counter() - t0), 1)

    passes = 3
    clf = VowpalWabbitClassifier(featuresCol="vw_features",
                                 numPasses=passes, numBits=18)
    clf.fit(hashed)  # warm the compile cache
    t0 = time.perf_counter()
    model = clf.fit(hashed)
    dt = time.perf_counter() - t0
    extras["vw_rows_per_sec"] = round(n_rows * passes / dt, 1)
    extras["vw_fit_seconds"] = round(dt, 3)

    model.transform(hashed)  # warm
    t0 = time.perf_counter()
    model.transform(hashed)
    extras["vw_score_rows_per_sec"] = round(
        n_rows / (time.perf_counter() - t0), 1)


def bench_observability(extras: dict) -> None:
    """Tracing/profiler overhead guard (ISSUE 8): the synthetic serving
    pipeline's p99 with the full tracing+profiler stack ON must stay
    within 5% of OFF, and the seeded chaos run must yield complete
    cross-process span trees. Reports the measured overhead so the bench
    JSON records what continuous observability actually costs."""
    from mmlspark_tpu.testing.benchmarks import (chaos_scenario,
                                                 tracing_overhead_scenario)

    r = tracing_overhead_scenario()
    extras["tracing_p99_off_ms"] = round(r["p99_off_s"] * 1e3, 3)
    extras["tracing_p99_on_ms"] = round(r["p99_on_s"] * 1e3, 3)
    extras["tracing_overhead_pct"] = round(r["overhead_pct"], 2)
    extras["tracing_overhead_within_5pct"] = bool(r["within_bound"])
    extras["tracing_feature_records"] = int(r["feature_records"])

    # the chaos trace acceptance, bench-side: every answered request's
    # cross-process tree is complete (driver queue + worker execute +
    # device under one trace id)
    c = chaos_scenario(seed=11, n_requests=24, n_workers=3)
    extras["tracing_chaos_answered"] = int(c["answered_200"])
    extras["tracing_chaos_complete_traces"] = int(c["complete_traces"])
    if c["sampled_trace"] is not None:
        extras["tracing_chaos_sampled_trace"] = \
            c["sampled_trace"]["trace_id"]


def bench_elasticity(extras: dict) -> None:
    """Multi-tenant elasticity acceptance (ISSUE 9): the seeded
    mixed-workload chaos scenario — three SLO-tiered tenants under
    diurnal load, one worker kill, one persistent degradation, 5%%
    injected 503s — reported as per-tenant p99 / shed-rate, utilization,
    and autoscale event counts, with the contract flags alongside so a
    regression shows up as a flipped boolean, not a silently drifting
    number."""
    from mmlspark_tpu.testing.benchmarks import mixed_tenant_scenario

    r = mixed_tenant_scenario()
    for name, p in r["per_tenant"].items():
        extras[f"tenant_{name}_p99_ms"] = round(p["p99_s"] * 1e3, 2)
        extras[f"tenant_{name}_shed_rate"] = round(p["shed_rate"], 4)
    extras["tenant_gold_within_slo"] = bool(r["within_gold_slo"])
    extras["tenant_silver_within_slo"] = bool(r["within_silver_slo"])
    extras["tenant_be_absorbed_burst"] = bool(r["be_absorbed_burst"])
    extras["tenant_utilization"] = round(r["utilization"], 3)
    extras["tenant_lease_replays"] = int(r["lease_replays"])
    extras["autoscale_ups"] = int(r["autoscale_ups"])
    extras["autoscale_downs"] = int(r["autoscale_downs"])
    extras["autoscale_replaces"] = int(r["autoscale_replaces"])
    extras["autoscale_workers_peak"] = int(r["workers_peak"])
    extras["autoscale_cooldown_violations"] = \
        int(r["cooldown_violations"])
    extras["autoscale_tracked_diurnal"] = bool(r["scaled_with_diurnal"])


def bench_pipeline_fusion(extras: dict) -> None:
    """Whole-pipeline XLA compilation acceptance (ISSUE 10): fused vs
    per-stage e2e latency and dispatch count on the featurizer
    (clean→assemble→infer→postproc) and text (host-tokenize→encoder)
    pipelines. Contract flags ride alongside the raw numbers: the
    featurizer pipeline must collapse to ≤ 2 dispatches per request,
    run ≥ 3× faster than eager per-stage execution, and stay
    bit-equivalent (atol 1e-5) on every benchmarked pipeline."""
    from mmlspark_tpu.testing.benchmarks import pipeline_fusion_scenario

    r = pipeline_fusion_scenario(n_rows=256, width=128, reps=40)
    for name in ("featurizer", "text"):
        p = r[name]
        extras[f"pipeline_fusion_{name}_eager_ms"] = round(
            p["eager_ms"], 3)
        extras[f"pipeline_fusion_{name}_fused_ms"] = round(
            p["fused_ms"], 3)
        extras[f"pipeline_fusion_{name}_speedup"] = round(
            p["speedup"], 2)
        extras[f"pipeline_fusion_{name}_dispatches"] = int(
            p["dispatches"])
        extras[f"pipeline_fusion_{name}_segments"] = int(p["segments"])
        extras[f"pipeline_fusion_{name}_equivalent"] = bool(
            p["equivalent"])
    extras["pipeline_fusion_le_2_dispatches"] = bool(
        r["featurizer_fused_le_2_dispatches"])
    extras["pipeline_fusion_speedup_ge_3x"] = bool(
        r["featurizer_speedup_ge_3x"])
    extras["pipeline_fusion_all_equivalent"] = bool(
        r["all_equivalent"])


def bench_aot(extras: dict) -> None:
    """AOT executable-store acceptance (ISSUE 11): compilation as a
    build step, not a request-latency event. Reports the store build
    wall time, the cold-vs-warm scale-up first-request latencies
    against steady-state p99, store hit/miss counts, and the contract
    flags — an autoscaler-added worker must serve its first request
    with zero runtime compiles (``profile_runtime_compiles_total == 0``,
    ``aot_store_hit_total >= 1``) within 2x steady-state p99, with
    AOT-loaded output bit-equal to the runtime-compiled segments."""
    from mmlspark_tpu.testing.benchmarks import aot_scale_up_scenario

    r = aot_scale_up_scenario()
    extras["aot_build_wall_s"] = round(r["build_wall_s"], 3)
    extras["aot_store_entries"] = int(r["store_entries"])
    extras["aot_steady_p99_ms"] = round(r["steady_p99_s"] * 1e3, 3)
    extras["aot_cold_first_ms"] = round(r["cold_first_s"] * 1e3, 3)
    extras["aot_warm_first_ms"] = round(r["warm_first_s"] * 1e3, 3)
    extras["aot_cold_over_steady"] = round(r["cold_over_steady"], 1)
    extras["aot_warm_over_steady"] = round(r["warm_over_steady"], 2)
    extras["aot_store_hits"] = int(r["store_hits"])
    extras["aot_store_misses"] = int(r["store_misses"])
    extras["aot_runtime_compiles"] = int(r["runtime_compiles"])
    extras["aot_scale_decision"] = r["scale_decision"]
    extras["aot_warm_within_2x_steady"] = bool(
        r["warm_within_2x_steady"])
    extras["aot_zero_runtime_compiles"] = bool(
        r["zero_runtime_compiles"])
    extras["aot_warm_hit_ge_1"] = bool(r["warm_hit_ge_1"])
    extras["aot_equivalent"] = bool(r["equivalent"])


def bench_costmodel(extras: dict) -> None:
    """Learned-performance-loop acceptance (ISSUE 12). Reports: (1) the
    cost model's held-out MAE vs the per-bucket EWMA baseline on a
    synthetic FeatureLog stream — the model must win (it sees entity
    bytes and queue depth; the EWMA cannot); (2) the deterministic
    predictive-autoscaling lead/lag — ticks between load rise and
    scale-up, reactive vs predictive; (3) the mixed-tenant diurnal
    scenario re-run with predictive autoscaling — scale-up lag vs the
    diurnal rise reported with the PR 8 gold contract flags alongside
    (zero gold sheds must survive the new brain); (4) autotuned-vs-
    default GBDT-histogram kernel timings on the acquired backend
    (interpreter off-TPU — the numbers are then schedule-relative, not
    device-representative, and are flagged as such)."""
    from mmlspark_tpu.perf import autotune
    from mmlspark_tpu.testing.benchmarks import (autoscale_lead_scenario,
                                                 costmodel_scenario,
                                                 mixed_tenant_scenario)

    r = costmodel_scenario()
    extras["costmodel_model_mae_ms"] = round(r["model_mae_ms"], 4)
    extras["costmodel_ewma_mae_ms"] = round(r["ewma_mae_ms"], 4)
    extras["costmodel_beats_ewma"] = bool(r["model_beats_ewma"])
    extras["costmodel_holdout_rows"] = int(r["n_holdout"])
    extras["costmodel_cold_falls_back"] = bool(r["cold_falls_back"])

    ll = autoscale_lead_scenario()
    extras["autoscale_lag_reactive_ticks"] = ll["lag_reactive_ticks"]
    extras["autoscale_lag_predictive_ticks"] = \
        ll["lag_predictive_ticks"]
    extras["autoscale_predictive_leads"] = bool(ll["predictive_leads"])

    m = mixed_tenant_scenario(predictive=True)
    extras["costmodel_predictive_gold_sheds"] = int(m["gold_sheds"])
    extras["costmodel_predictive_gold_within_slo"] = bool(
        m["within_gold_slo"])
    if m["scale_up_lag_s"] is not None:
        extras["costmodel_predictive_scale_up_lag_s"] = round(
            m["scale_up_lag_s"], 3)

    # autotune the histogram kernel at a modest shape on the acquired
    # backend; off-TPU the Pallas interpreter measures the schedule,
    # not the silicon — flagged so nobody reports an interpreter number
    # as a device one. The in-process winner table is restored after:
    # an interpreter-derived winner must not steer the hist kernel in
    # later bench sections of this same process.
    from mmlspark_tpu.lightgbm.pallas_hist import (DEFAULT_BLOCK_ROWS,
                                                   FEAT_BLOCK)
    on_tpu = _PLATFORM == "tpu"
    shape = dict(n=(1 << 16), F=32, num_bins=64) if on_tpu else \
        dict(n=1024, F=8, num_bins=16)
    import tempfile
    tune_path = os.path.join(tempfile.mkdtemp(prefix="mmlspark_tpu_tune_"),
                             "autotune.json")
    prev_winners = dict(autotune._WINNERS)
    try:
        rec = autotune.tune_hist(shape["n"], shape["F"],
                                 shape["num_bins"], reps=3,
                                 interpret=None if on_tpu else True,
                                 path=tune_path)
    finally:
        autotune._WINNERS.clear()
        autotune._WINNERS.update(prev_winners)
    extras["autotune_hist_device_representative"] = bool(on_tpu)
    extras["autotune_hist_candidates"] = int(rec["candidates"])
    extras["autotune_hist_valid"] = int(rec["valid"])
    if rec["winner"] is not None:
        default_ms = next(
            (t["ms"] for t in rec["trials"]
             if t.get("feat_block") == FEAT_BLOCK
             and t.get("block_rows") == DEFAULT_BLOCK_ROWS
             and t.get("ms") is not None), None)
        extras["autotune_hist_best_ms"] = rec["winner"]["ms"]
        extras["autotune_hist_winner"] = {
            k: rec["winner"][k] for k in ("feat_block", "block_rows")}
        if default_ms is not None:
            extras["autotune_hist_default_ms"] = default_ms
            extras["autotune_hist_speedup_vs_default"] = round(
                default_ms / max(rec["winner"]["ms"], 1e-9), 3)


def bench_fleet(extras: dict) -> None:
    """Fleet telemetry plane acceptance (ISSUE 15). Reports: (1) the
    cost of one federated ``/metrics?scope=fleet`` exposition (8 ranks
    x 200 samples merged with identity relabeling) against the
    per-process alternative (8 separate ``/metrics`` renders) — the
    overhead a pod operator pays for the single-scrape view; (2) the
    chaos trajectory: waves from an injected ``worker.slow`` to the
    ``fleet_straggler`` flip (detection latency), the straggler-sourced
    autoscaler replace, the healthz ok→degraded→ok walk, and the gold
    burn-rate staying under the page threshold."""
    from mmlspark_tpu.obs.fleet import FleetAggregator
    from mmlspark_tpu.obs.metrics import MetricsRegistry
    from mmlspark_tpu.testing.benchmarks import fleet_chaos_scenario

    n_ranks, n_samples, reps = 8, 200, 50
    src = MetricsRegistry()
    g = src.gauge("profile_step_seconds_sum", "per-stage wall seconds")
    c = src.gauge("serving_requests_total", "requests by route")
    for j in range(n_samples // 2):
        g.set(j * 0.01, stage=f"s{j}")
        c.set(float(j), route=f"/r{j}")
    snap = src.snapshot()
    agg = FleetAggregator(MetricsRegistry(), max_sources=n_ranks)
    for rank in range(n_ranks):
        agg.ingest_snapshot(dict(snap), process=str(rank),
                            channel="bench")
    t0 = time.perf_counter()
    for _ in range(reps):
        fleet_text = agg.exposition()
    fleet_ms = (time.perf_counter() - t0) / reps * 1e3
    t0 = time.perf_counter()
    for _ in range(reps):
        for _rank in range(n_ranks):
            src.exposition()
    per_proc_ms = (time.perf_counter() - t0) / reps * 1e3
    extras["fleet_scrape_ms"] = round(fleet_ms, 3)
    extras["fleet_per_process_scrape_ms"] = round(per_proc_ms, 3)
    extras["fleet_scrape_overhead_x"] = round(
        fleet_ms / max(per_proc_ms, 1e-9), 3)
    extras["fleet_scrape_samples"] = sum(
        1 for ln in fleet_text.splitlines()
        if ln and not ln.startswith("#"))

    r = fleet_chaos_scenario(seed=31)
    extras["fleet_ticks_to_flag"] = int(r["ticks_to_flag"] or -1)
    extras["fleet_flagged"] = bool(r["flagged"])
    extras["fleet_straggler_replaces"] = int(r["straggler_replaces"])
    extras["fleet_healthz_trajectory"] = "->".join(r["verdicts"])
    extras["fleet_healthz_flipped"] = bool(r["healthz_flipped"])
    extras["fleet_recovered"] = bool(r["recovered"])
    extras["fleet_recover_waves"] = int(r["recover_waves"])
    extras["fleet_gold_burn"] = round(r["gold_burn"], 3)
    extras["fleet_gold_under_page"] = bool(r["gold_under_page"])
    extras["fleet_be_burn"] = round(r["be_burn"], 3)
    extras["fleet_hbm_devices"] = int(r["hbm_devices"])
    extras["fleet_mem_gauges_present"] = bool(r["mem_gauges_present"])


def bench_deploy(extras: dict) -> None:
    """Zero-downtime model-lifecycle acceptance (ISSUE 19). Reports the
    rollout scenario's contract surface: a blue/green flip across the
    autoscaled mixed-tenant fleet with zero non-canary 5xx, zero
    dropped in-flight requests and zero runtime compiles
    (``rollout_zero_5xx``), the seeded bad canary auto-rolled-back
    from burn rate alone within a bounded number of controller ticks
    (``rollback_ticks``) with the gold tier untouched
    (``canary_gold_sheds``) — plus a same-seed double run asserting
    the realized fault schedule is identical (the deploy plane's
    chaos is reproducible, same contract as bench_elasticity)."""
    from mmlspark_tpu.testing.benchmarks import rollout_scenario

    r = rollout_scenario(seed=29)
    r2 = rollout_scenario(seed=29, service="rollout-bench2")
    extras["rollout_zero_5xx"] = bool(
        r["rollout_zero_5xx"] and r["drained_completed"]
        and r["zero_runtime_compiles"])
    extras["rollout_non_canary_5xx"] = int(r["non_canary_5xx"])
    extras["rollout_unanswered"] = int(r["unanswered"])
    extras["rollout_byte_identical"] = bool(r["byte_identical"])
    extras["rollout_draining_final"] = int(r["draining_inflight_final"])
    extras["rollout_runtime_compiles"] = int(r["runtime_compiles"])
    extras["rollout_worker_killed"] = bool(r["worker_killed"])
    extras["rollout_lease_replays"] = int(r["lease_replays"])
    extras["rollback_ticks"] = int(r["rollback_ticks"] or -1)
    extras["rollback_reason"] = str(r["rollback_reason"])
    extras["rollback_restored_active"] = str(r["active_after"])
    extras["canary_5xx"] = int(r["canary_5xx"])
    extras["canary_gold_sheds"] = int(r["canary_gold_sheds"])
    extras["rollout_gold_unharmed"] = bool(r["gold_unharmed"])
    extras["rollout_workers_peak"] = int(r["workers_peak"])
    extras["rollout_schedule_reproducible"] = bool(
        r["schedule"] == r2["schedule"] and r["schedule"])


def bench_attribution(extras: dict) -> None:
    """Cost-attribution acceptance (ISSUE 20). Reports the scenario's
    contract surface: per-program roofline placement off real compiled
    programs (the matmul reads compute-bound, the wide add
    memory-bound, every utilization share <= 1.0), the fleet
    ``goodput_ratio`` under seeded chaos with the waste taxonomy
    itemized and the per-tick trace reproducible by seed, and the
    cost model's v6 analytic columns at least matching the v5
    baseline on held-out MAE."""
    from mmlspark_tpu.testing.benchmarks import attribution_scenario

    r = attribution_scenario(seed=29)
    r2 = attribution_scenario(seed=29)
    extras["attr_rooflines"] = r["rooflines"]
    extras["attr_matmul_compute_bound"] = bool(
        r["matmul_compute_bound"])
    extras["attr_add_memory_bound"] = bool(r["add_memory_bound"])
    extras["attr_utilization_max"] = round(
        float(r["utilization_max"]), 6)
    extras["attr_utilization_bounded"] = bool(
        r["utilization_max"] <= 1.05)
    extras["goodput_ratio"] = round(float(r["goodput_ratio"]), 6)
    extras["goodput_waste_seconds"] = r["goodput_waste_seconds"]
    extras["goodput_waste_itemized"] = bool(r["goodput_waste_itemized"])
    extras["goodput_schedule_reproducible"] = bool(
        r["goodput_ratio_trace"] == r2["goodput_ratio_trace"]
        and r["goodput_ratio_trace"])
    extras["costmodel_v6_mae_ms"] = round(float(r["v6_mae_ms"]), 4)
    extras["costmodel_v5_mae_ms"] = round(float(r["v5_mae_ms"]), 4)
    extras["costmodel_v6_no_worse"] = bool(r["v6_no_worse"])


def bench_serving(extras: dict) -> None:
    """End-to-end HTTP request→jitted pipeline→response latency against
    the reference's ~1 ms continuous-mode figure."""
    import http.client

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.io.http.schema import HTTPResponseData
    from mmlspark_tpu.serving.server import serving_query

    # score on the acquired device: a TPU host is colocated with its
    # chips, and the request's device round trip is part of what a
    # user's request pays
    dev = jax.devices()[0]
    w = jax.device_put(
        jnp.asarray(np.random.default_rng(3).normal(size=(16, 16)),
                    jnp.float32), dev)

    @jax.jit
    def score(x):
        return jnp.tanh(x @ w).sum(axis=-1)

    # precompile EVERY power-of-two bucket the dynamic batcher can
    # produce under the loaded rows (bucket_pad below maps batches onto
    # these shapes): production servers warm their buckets at startup,
    # and an unwarmed bucket's compile otherwise lands in the loaded
    # tail as a ~50 ms outlier. The max bucket derives from the SAME
    # env knob the loaded rows read, so raising the concurrency cannot
    # reintroduce a novel shape mid-measurement.
    try:
        conc = int(os.environ.get("MMLSPARK_TPU_BENCH_SERVING_CONC",
                                  "16"))
    except ValueError:
        conc = 16  # a malformed knob must not cost every serving row
    conc = max(1, min(conc, 256))
    b = 1
    while b < 2 * max(conc, 16):
        score(jax.device_put(np.zeros((b, 16), np.float32),
                             dev)).block_until_ready()
        b *= 2

    # the floor under every request: one blocking dispatch of a tiny
    # program on the device the requests score on
    y = jax.device_put(jnp.ones((8, 8), jnp.float32), dev)
    f = jax.jit(lambda a: a @ a)
    f(y).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        f(y).block_until_ready()
    extras["device_dispatch_rtt_ms"] = round(
        (time.perf_counter() - t0) / 20 * 1e3, 3)

    from mmlspark_tpu.serving import bucket_pad

    def transform(df):
        xs = np.stack([
            np.frombuffer(r.entity, np.float32) if r.entity and
            len(r.entity) == 64 else np.zeros(16, np.float32)
            for r in df["request"]])
        # power-of-two batch buckets: a dynamic batcher produces every
        # batch size up to the in-flight count, and each NOVEL shape
        # pays a jit compile at request latency — measured as the
        # entire 16-way loaded tail (~96 ms p99 → ~5 ms)
        xs, n_real = bucket_pad(xs)
        ys = np.asarray(score(jax.device_put(xs, dev)))[:n_real]
        replies = np.empty(len(ys), object)
        replies[:] = [HTTPResponseData(
            status_code=200, entity=json.dumps(float(y)).encode())
            for y in ys]
        return df.with_column("reply", replies)

    def latency_loop(addr, payload, n=300, warmup=50):
        """One keep-alive connection, n sequential requests → (p50 ms,
        p99 ms, non-200 count). Shared by the toy and real-model rows
        so the measurement protocol cannot drift between them."""
        conn = http.client.HTTPConnection(*addr, timeout=10)
        lat, errors = [], 0
        for _ in range(n):
            t0 = time.perf_counter()
            conn.request("POST", "/", body=payload)
            resp = conn.getresponse()
            resp.read()
            if resp.status != 200:
                errors += 1
            lat.append((time.perf_counter() - t0) * 1e3)
        conn.close()
        lat = np.sort(np.asarray(lat[warmup:]))
        return (float(np.percentile(lat, 50)),
                float(np.percentile(lat, 99)), errors)

    def measure(backend: str, suffix: str, *, transform_fn=None,
                payload=None, n=300, warmup=50, prefix="serving",
                conc=1):
        """Spin a query, run the latency loop, report results under
        ``{prefix}{suffix}_*`` — ONE measurement protocol for the toy,
        real-model, and concurrency rows. ``conc > 1`` fans the loop
        out over that many keep-alive connections and reports aggregate
        throughput + worst per-connection tail latency instead of
        single-connection percentiles."""
        import threading

        query = serving_query(f"bench{prefix}{suffix}",
                              transform_fn or transform,
                              reply_timeout=10.0, backend=backend)
        try:
            if payload is None:
                payload = np.zeros(16, np.float32).tobytes()
            addr = query.server.address
            if conc == 1:
                p50, p99, errors = latency_loop(addr, payload, n=n,
                                                warmup=warmup)
                if errors:
                    raise RuntimeError(
                        f"{errors}/{n} serving requests returned "
                        "non-200 — latency figures would be "
                        "meaningless")
                extras[f"{prefix}{suffix}_p50_ms"] = round(p50, 3)
                extras[f"{prefix}{suffix}_p99_ms"] = round(p99, 3)
                return
            latency_loop(addr, payload, n=20, warmup=10)  # warm
            # loaded rows drive the closed loop from the NATIVE load
            # generator when it builds: a Python http.client worker
            # burns ~0.25 ms of GIL per request, capping the CLIENT at
            # ~4k req/s and stealing cycles from the server under test
            # (the native client measured the same native front at
            # 10k req/s where the python client reported 4k)
            try:
                import gc

                from mmlspark_tpu.serving.loadgen import run_load

                # the bench process carries models/arrays from earlier
                # rows; a GC pass mid-loop lands straight in the tail.
                # Collect first, hold GC off for the loop (the server
                # threads live in THIS process), and take the better
                # of two runs — a single p99 estimate at n=300 is
                # noisy and the first run double-serves as bucket
                # warmup under real concurrency.
                runs = []
                for _ in range(2):
                    gc.collect()
                    was = gc.isenabled()
                    gc.disable()
                    try:
                        runs.append(run_load(addr[0], addr[1], payload,
                                             nconn=conc, nreq=n))
                    finally:
                        if was:
                            gc.enable()
                r = min(runs, key=lambda x: x["loaded_p99_ms"])
                if r["errors"]:
                    raise RuntimeError(
                        f"{r['errors']} non-200s under {conc}-way "
                        "native-client load")
                extras[f"{prefix}{suffix}_concurrency"] = conc
                extras[f"{prefix}{suffix}_throughput_rps"] = round(
                    r["throughput_rps"], 1)
                extras[f"{prefix}{suffix}_loaded_p99_ms"] = round(
                    r["loaded_p99_ms"], 3)
                extras[f"{prefix}{suffix}_load_client"] = "native"
                if r.get("slowest"):
                    # flight-recorder lookup keys for the loaded tail:
                    # these trace ids resolve at GET /debug/trace on
                    # the server under test (ISSUE 8)
                    extras[f"{prefix}{suffix}_p99_slowest_traces"] = \
                        [s["trace_id"] for s in r["slowest"][:4]]
                return
            except Exception:
                # record WHY before falling back — a server failing
                # only at native-client rates must not silently bank
                # clean python-client numbers (and a loadgen build
                # failure must be distinguishable from a server error)
                extras[f"error_{prefix}{suffix}_loadgen"] = \
                    traceback.format_exc()[-500:]
                extras[f"{prefix}{suffix}_load_client"] = "python"
            results: list = [None] * conc

            def worker(i):
                # store failures — a thread exception would otherwise
                # vanish to stderr and surface only as a NoneType error
                try:
                    results[i] = latency_loop(addr, payload, n=n,
                                              warmup=0)
                except Exception as e:
                    results[i] = e

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(conc)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            failed = [r for r in results if isinstance(r, Exception)]
            if failed:
                raise RuntimeError(
                    f"{len(failed)}/{conc} connections failed under "
                    f"load; first: {failed[0]!r}")
            errors = sum(r[2] for r in results)
            if errors:
                raise RuntimeError(
                    f"{errors} non-200s under {conc}-way load")
            extras[f"{prefix}{suffix}_concurrency"] = conc
            extras[f"{prefix}{suffix}_throughput_rps"] = round(
                conc * n / dt, 1)
            extras[f"{prefix}{suffix}_loaded_p99_ms"] = round(
                max(r[1] for r in results), 3)
        finally:
            query.stop()

    measure("python", "")
    extras["serving_vs_1ms_target"] = round(
        SERVING_TARGET_MS / extras["serving_p99_ms"], 3)

    # concurrency throughput (the reference's serving story includes
    # sustained load, docs/mmlspark-serving.md; round-2 measured ~9k
    # req/s at 32-way by hand — this reports it). Same python front as
    # the baseline p50/p99 rows so loaded-vs-unloaded compares like
    # with like. Fault-isolated.
    try:
        measure("python", "", n=200, conc=conc)  # conc: warm-loop knob
    except Exception:
        extras["error_serving_throughput"] = \
            traceback.format_exc()[-500:]

    # REAL-model serving (review round 3 Missing #5 / BASELINE configs[5]):
    # a FITTED LightGBM pipeline behind the front — request = one
    # feature row, reply = probability. This is the reference's actual
    # serving story ("the same ML pipeline as a web service",
    # docs/mmlspark-serving.md:9-12), not a toy matmul. Fault-isolated
    # and BEFORE the native measure: that one intentionally propagates
    # failures, and a native regression must not drop this row.
    try:
        from mmlspark_tpu.core import DataFrame
        from mmlspark_tpu.lightgbm import LightGBMClassifier
        rng2 = np.random.default_rng(17)
        xm = rng2.normal(size=(5000, 28)).astype(np.float32)
        ym = (xm[:, :4].sum(1) > 0).astype(np.float32)
        model = LightGBMClassifier(numIterations=5, numLeaves=15,
                                   seed=0).fit(
            DataFrame({"features": xm, "label": ym}))
        prob_col = model.getProbabilityCol()
        row_bytes = 28 * 4

        def model_transform(df):
            rows = np.stack([
                np.frombuffer(r.entity, np.float32)
                if r.entity and len(r.entity) == row_bytes
                else np.zeros(28, np.float32) for r in df["request"]])
            rows, n_real = bucket_pad(rows)  # same novel-shape guard
            probs = model.transform(
                DataFrame({"features": rows}))[prob_col][:n_real]
            replies = np.empty(len(df), object)
            replies[:] = [HTTPResponseData(
                status_code=200, entity=np.float32(p[1]).tobytes())
                for p in probs]
            return df.with_column("reply", replies)

        # the fitted GBDT scores on the acquired device: every request
        # pays its dispatch inside the handler
        from mmlspark_tpu.native.loader import get_httpfront
        backends = [("python", "")]
        if get_httpfront() is not None:
            backends.append(("native", "_native"))
        # per-backend fault isolation: a python-leg failure must not
        # skip the native leg, and a native regression here gets its
        # own error key rather than vanishing into the python leg's
        for backend, suffix in backends:
            try:
                measure(backend, suffix, transform_fn=model_transform,
                        payload=xm[0].tobytes(), n=250,
                        prefix="serving_model")
            except Exception:
                extras[f"error_serving_model{suffix}"] = \
                    traceback.format_exc()[-500:]
    except Exception:
        extras["error_serving_model"] = traceback.format_exc()[-500:]

    # ResNet endpoint (BASELINE configs[5] names one): device-resident
    # zoo weights scoring one image per request
    # (device_dispatch_rtt_ms above is the floor under it).
    try:
        from mmlspark_tpu.core import DataFrame
        from mmlspark_tpu.image import ImageFeaturizer
        from mmlspark_tpu.models import ModelDownloader
        loaded = ModelDownloader().download_by_name(
            "ResNet50", allow_random_init=True)
        feat = ImageFeaturizer(model=loaded, cutOutputLayers=1,
                               inputCol="image", outputCol="features",
                               autoResize=False, miniBatchSize=8)
        img_bytes = 224 * 224 * 3 * 4

        def resnet_transform(df):
            imgs = np.stack([
                np.frombuffer(r.entity, np.float32)
                .reshape(224, 224, 3)
                if r.entity and len(r.entity) == img_bytes
                else np.zeros((224, 224, 3), np.float32)
                for r in df["request"]])
            out = feat.transform(DataFrame({"image": imgs}))
            replies = np.empty(len(df), object)
            replies[:] = [HTTPResponseData(
                status_code=200, entity=np.asarray(f).tobytes())
                for f in out["features"]]
            return df.with_column("reply", replies)

        # warm the fixed-shape compile outside the timed loop
        probe = np.zeros((1, 224, 224, 3), np.float32)
        feat.transform(DataFrame({"image": probe}))
        payload = np.random.default_rng(23).normal(
            size=(224, 224, 3)).astype(np.float32).tobytes()
        measure("python", "", transform_fn=resnet_transform,
                payload=payload, n=120, warmup=20,
                prefix="serving_resnet")
    except Exception:
        extras["error_serving_resnet"] = traceback.format_exc()[-500:]

    from mmlspark_tpu.native.loader import get_httpfront
    if get_httpfront() is not None:
        # a failure here is a native-front regression and must surface
        # (the watchdog records it as error_serving)
        measure("native", "_native")
        # native front under the SAME 16-way load as the python row:
        # the loaded-tail comparison is the whole point of having two
        # fronts. Fault-isolated like the python concurrency row.
        try:
            measure("native", "_native", n=200, conc=conc)
        except Exception:
            extras["error_serving_native_throughput"] = \
                traceback.format_exc()[-500:]
        # moderate (non-saturating) load: closed-loop saturation makes
        # latency = conc/throughput (Little's law), so the tail claim
        # needs a row where the server is NOT the bottleneck
        try:
            measure("native", "_native", n=400, conc=4,
                    prefix="serving_moderate")
        except Exception:
            extras["error_serving_moderate"] = \
                traceback.format_exc()[-500:]


def bench_llm_serving(extras: dict) -> None:
    """LLM serving bench, in-process on the acquired device: the
    paged-KV serving engine
    (``testing.benchmarks.llm_serving_scenario``: warmed prefill/decode
    programs, repeated-prefix workload, CompileTracker steady state)
    reports its registry-backed numbers — tokens/sec, TTFT p99 and the
    cold/warm split, prefix-cache hit rate. A second pass runs the
    speculative variant (self-draft ⇒ acceptance upper bound, labeled
    as such — same stance as bench_gen's spec rows). The platform rides
    in ``llm_platform``."""
    import jax

    from mmlspark_tpu.obs.metrics import MetricsRegistry
    from mmlspark_tpu.testing.benchmarks import llm_serving_scenario

    r = llm_serving_scenario(service="llm-bench",
                             registry=MetricsRegistry(), seed=17)
    spec = llm_serving_scenario(service="llm-bench-spec",
                                registry=MetricsRegistry(), spec_k=2,
                                seed=29)
    extras["llm_platform"] = jax.devices()[0].platform
    extras["llm_tokens_per_sec"] = round(r["tokens_per_s"], 1)
    extras["gen_ttft_p99_ms"] = round(r["ttft_p99_ms"], 3)
    extras["llm_ttft_cold_p50_ms"] = round(r["ttft_cold_p50_ms"], 3)
    extras["llm_ttft_warm_p50_ms"] = round(r["ttft_warm_p50_ms"], 3)
    extras["llm_prefix_hit_rate"] = round(r["prefix_hit_rate"], 3)
    extras["llm_ttft_warm_vs_cold"] = round(
        extras["llm_ttft_cold_p50_ms"]
        / max(extras["llm_ttft_warm_p50_ms"], 1e-9), 2)
    extras["llm_steady_state_ok"] = bool(r.get("steady_state_ok"))
    extras["llm_aot_fingerprints"] = r.get("aot_fingerprints", 0)
    extras["llm_spec_tokens_per_sec"] = round(spec["tokens_per_s"], 1)
    extras["llm_spec_accept_ratio"] = spec["spec_accept_ratio"]


def bench_llm_decode(extras: dict) -> None:
    """Long-context decode throughput of the paged kernel, in-process on
    the acquired device (``testing.benchmarks.llm_decode_scenario``:
    >=4k tokens of resident KV, decode-only timed window,
    CompileTracker steady state). The platform rides in
    ``llm_decode_platform``."""
    import jax

    from mmlspark_tpu.obs.metrics import MetricsRegistry
    from mmlspark_tpu.testing.benchmarks import llm_decode_scenario

    paged = llm_decode_scenario(service="llm-decode-paged",
                                registry=MetricsRegistry())
    extras["llm_decode_platform"] = jax.devices()[0].platform
    extras["llm_decode_context_tokens"] = paged["context_tokens"]
    extras["llm_decode_tokens_per_sec"] = round(
        paged["tokens_per_s"], 1)
    extras["llm_decode_attn_ms_per_step"] = round(
        paged["attn_ms_per_step"], 3)
    extras["llm_decode_steady_state_ok"] = bool(
        paged["steady_state_ok"])


def _emit(images_per_sec: float, extras: dict, device: dict) -> None:
    """The one line. ``device`` names what was measured: ``platform``,
    ``device_kind`` and ``device_count`` as JAX reports them."""
    print(json.dumps({
        "metric": "imagefeaturizer_resnet50_inference",
        "value": round(images_per_sec, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(images_per_sec / A100_IMAGES_PER_SEC, 3),
        **device,
        "extras": extras,
    }), flush=True)


def _compare_main(argv) -> int:
    """``bench.py --compare OLD.json NEW.json``: diff one bench run
    against another through the obs.regression trajectory gate and
    print the table and the one-line verdict. Writes nothing. Host-side
    only — no backend, no jax."""
    from mmlspark_tpu.obs.regression import (compare_benches, format_table,
                                             gate_verdict, load_bench)
    args = [a for a in argv if a != "--compare"]
    if len(args) != 2:
        print("usage: bench.py --compare OLD.json NEW.json")
        return 2
    old_p, new_p = args
    rows = compare_benches(load_bench(old_p), load_bench(new_p))
    print(f"{old_p} -> {new_p}")
    print(format_table(rows))
    verdict = gate_verdict(rows)
    print(verdict)
    return 1 if verdict.startswith("REGRESSION") else 0


def main() -> int:
    extras: dict = {}
    images_per_sec = 0.0
    only = os.environ.get("MMLSPARK_TPU_BENCH_ONLY", "")

    # the one process that holds the chip: acquire it first, and measure
    # nothing without it — no replay, no CPU stand-in
    import jax

    from mmlspark_tpu.core.aot import place_jax_cache
    place_jax_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "device_count": len(devices)}
    global _PLATFORM
    _PLATFORM = devices[0].platform
    if _PLATFORM != "tpu":
        extras["error_backend"] = (
            f"bench.py measures a TPU; JAX found {_PLATFORM} "
            f"({devices[0].device_kind})")
        _emit(0.0, extras, device)
        return 1

    # the full suite runs long: a SIGTERM/SIGINT must still produce the
    # one-line JSON with whatever was measured so far, instead of dying
    # silently mid-suite
    import signal

    def _on_term(signum, frame):
        try:
            extras.setdefault(
                "error_killed", f"signal {signum} mid-suite; partial results")
            _emit(images_per_sec, extras, device)
        finally:
            # 128+signum: a killed partial run must not look like a
            # clean one to drivers/shells checking the exit status
            os._exit(128 + signum)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _on_term)
        except (ValueError, OSError):
            pass  # non-main thread / unsupported platform

    def want(name: str) -> bool:
        return not only or name in only.split(",")

    # load-average guard (review round 3 Weak #3: round 3's only GBDT number
    # was taken while pytest saturated the host) — timings taken on a
    # contended host are stamped, never passed off as clean
    try:
        load1 = os.getloadavg()[0]
        extras["load_avg_start"] = round(load1, 2)
        if load1 > 0.5 * (os.cpu_count() or 1):
            extras["contended"] = True
    except OSError:
        pass

    # headline first, then the trainer numbers, then the sweeps
    if want("resnet"):
        images_per_sec = _watchdog(bench_resnet, extras, "resnet",
                                   600.0) or 0.0
    if want("gbdt"):
        _watchdog(bench_gbdt, extras, "gbdt", 420.0)
    if want("ranker"):
        _watchdog(bench_ranker, extras, "ranker", 420.0)
    if want("vw"):
        _watchdog(bench_vw, extras, "vw", 300.0)
    if want("gbdt_sparse"):
        _watchdog(bench_gbdt_sparse, extras, "gbdt_sparse", 300.0)
    if want("train"):
        _watchdog(bench_train, extras, "train", 600.0)
    if want("vit"):
        _watchdog(bench_vit, extras, "vit", 600.0)
    if want("encoder"):
        raw_impls = os.environ.get("MMLSPARK_TPU_BENCH_ENCODER_IMPLS",
                                   ",".join(_ENCODER_IMPLS))
        impls = tuple(i.strip() for i in raw_impls.split(",")
                      if i.strip()) or _ENCODER_IMPLS
        for impl in impls:
            _watchdog(make_bench_encoder(impl), extras,
                      f"encoder_{impl}", 420.0)
        _finalize_encoder(extras, impls)
    if want("encoder_int8"):
        _watchdog(bench_encoder_int8, extras, "encoder_int8",
                  420.0)
        # like-for-like ratio: int8 runs at B=8, so compare the
        # best bf16 impl's B=8 point (not its best-of-batch)
        by_batch = extras.get("encoder_ips_by_batch") or {}
        bf16_b8 = by_batch.get("8") or by_batch.get(8)
        int8 = extras.get("encoder_int8_seqs_per_sec")
        if int8 and bf16_b8:
            extras["encoder_int8_vs_bf16_b8"] = round(
                int8 / bf16_b8, 3)
    if want("flashcausal"):
        _watchdog(bench_flash_causal, extras, "flashcausal", 300.0)
    if want("gen"):
        _watchdog(bench_gen, extras, "gen", 420.0)
    if want("llm_serving"):
        # generation bench (paged KV + prefill/decode executors),
        # in-process on the acquired device
        _watchdog(bench_llm_serving, extras, "llm_serving", 600.0)
    if want("llm_decode"):
        # long-context decode throughput of the paged kernel,
        # in-process on the acquired device
        _watchdog(bench_llm_decode, extras, "llm_decode", 900.0)
    if want("observability"):
        # pure host-side (scheduler + in-thread mesh)
        _watchdog(bench_observability, extras, "observability",
                  240.0)
    if want("elasticity"):
        # pure host-side (synthetic tenants + autoscaled pool)
        _watchdog(bench_elasticity, extras, "elasticity", 240.0)
    if want("pipeline_fusion"):
        # fused vs per-stage pipelines on the acquired device
        _watchdog(bench_pipeline_fusion, extras, "pipeline_fusion",
                  240.0)
    if want("aot"):
        # build-step compilation vs request-latency compilation on
        # the acquired device (store in a scenario-owned tmp dir)
        _watchdog(bench_aot, extras, "aot", 240.0)
    if want("costmodel"):
        # learned cost model vs EWMA, predictive-autoscale lead/lag,
        # and the kernel autotuner (host-side except the tune run)
        _watchdog(bench_costmodel, extras, "costmodel", 240.0)
    if want("fleet"):
        # fleet federation + chaos health trajectory (in-thread
        # mesh + synthetic snapshots: host-side)
        _watchdog(bench_fleet, extras, "fleet", 240.0)
    if want("deploy"):
        # blue/green flip + seeded-bad-canary rollback across the
        # synthetic fleet (host-side only)
        _watchdog(bench_deploy, extras, "deploy", 240.0)
    if want("attribution"):
        # roofline placement + goodput ledger + v6 cost-model value
        # (compiles two tiny programs on the acquired device; the
        # rest is host-side)
        _watchdog(bench_attribution, extras, "attribution", 240.0)
    if want("serving"):
        # includes a small GBDT fit for the real-model row
        _watchdog(bench_serving, extras, "serving", 360.0)

    # disarm before the final print: a signal landing between _emit and
    # _exit would otherwise print a SECOND JSON line
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, signal.SIG_DFL)
        except (ValueError, OSError):
            pass
    _emit(images_per_sec, extras, device)
    # any recorded failure fails the run. Hard exit: a sub-bench thread
    # the watchdog abandoned would otherwise block interpreter shutdown
    # after the line printed
    os._exit(1 if any(k.startswith("error") for k in extras) else 0)


if __name__ == "__main__":
    import sys
    if "--compare" in sys.argv[1:]:
        sys.exit(_compare_main(sys.argv[1:]))
    sys.exit(main())
