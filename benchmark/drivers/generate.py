"""Driver of the generate cells: a closed loop of callers over
``serving.llm.LLMEngine.submit/step``. One operation is one step
boundary of the engine plus the refill: every caller whose request
finished at the boundary sends its next one, which the engine admits at
the boundary after. Its work units are the generated tokens committed at
that boundary (a sequence's first token, which prefill produces, counts
once; prompt tokens do not count), read from the engine's own counters:
``gen_tokens_total`` and the count of ``gen_ttft_seconds``.

The configuration is XGLM's (``references/xglm.py``): the benchmark makes
the weights from the seed and hands the program the same numbers, as the
tree its one decoder (``dl.MaskedLMModel`` over ``dl.TextEncoder``) takes.
"""

from __future__ import annotations

import json
import math
import sys
import time

import numpy as np

from benchmark import traffic, traffic_requests
from benchmark.references import xglm as ref

SERVICE = "bench-generate"
MAX_IDLE_BOUNDARIES = 64       # boundaries with nothing committed in a row


def program_variables(weights: dict, cfg: dict) -> dict:
    """The reference's weights as the program's ``params`` tree, in one
    jitted call: q, k and v fused into ``qkv``; the embedding table
    holding ``sqrt(d_model) * E`` and a separate head holding ``E``
    transposed with a zero bias, which is what XGLM's scaled embedding
    and tied head compute with."""
    import jax
    import jax.numpy as jnp
    n = int(cfg["num_layers"])
    scale = math.sqrt(int(cfg["d_model"])) if cfg["scale_embedding"] else 1.0

    @jax.jit
    def build(w):
        dtype = w["embed"].dtype

        def dense(kernel, bias):
            return {"kernel": kernel, "bias": bias}

        def norm(prefix, i=None):
            pick = (lambda a: a) if i is None else (lambda a: a[i])
            return {"scale": pick(w[f"{prefix}_scale"]),
                    "bias": pick(w[f"{prefix}_bias"])}

        encoder = {
            "embed": {"embedding": (w["embed"].astype(jnp.float32)
                                    * scale).astype(dtype)},
            "ln": norm("ln_f")}
        for i in range(n):
            encoder[f"block{i}"] = {
                "ln_1": norm("ln1", i), "ln_2": norm("ln2", i),
                "qkv": dense(
                    jnp.concatenate([w[f"{p}_w"][i] for p in "qkv"], axis=1),
                    jnp.concatenate([w[f"{p}_b"][i] for p in "qkv"])),
                "out": dense(w["o_w"][i], w["o_b"][i]),
                "mlp_1": dense(w["fc1_w"][i], w["fc1_b"][i]),
                "mlp_2": dense(w["fc2_w"][i], w["fc2_b"][i])}
        return {"encoder": encoder,
                "lm_head": dense(w["embed"].T,
                                 jnp.zeros(w["embed"].shape[0], dtype))}

    return {"params": build(weights)}


def build_engine(cfg: dict, params: dict, variables: dict, registry):
    import jax.numpy as jnp
    from mmlspark_tpu.dl import MaskedLMModel, TextEncoder
    from mmlspark_tpu.dl.text_encoder import make_attention_fn
    from mmlspark_tpu.serving.llm import LLMEngine

    module = MaskedLMModel(TextEncoder(
        vocab=int(cfg["vocab_size"]), width=int(cfg["d_model"]),
        depth=int(cfg["num_layers"]), heads=int(cfg["attention_heads"]),
        mlp_dim=int(cfg["ffn_dim"]), dtype=jnp.dtype(cfg["cache_dtype"]),
        attention_fn=make_attention_fn("dense", causal=True)))
    eng = params["engine"]
    return LLMEngine(
        module, variables, slots=int(eng["slots"]),
        block_len=int(eng["block_len"]), max_seq_len=int(eng["max_seq_len"]),
        num_blocks=int(eng["num_blocks"]),
        prefill_batch=int(eng["prefill_batch"]),
        hbm_fraction=float(eng["hbm_fraction"]),
        pad_id=int(cfg["pad_token_id"]), service=SERVICE, registry=registry)


def _metric(registry, name: str):
    """The engine's own counter, gauge or histogram of that name."""
    return next(m for m in registry.metrics(name) if m.name == name)


def setup(cfg: dict, params: dict, seed: int) -> dict:
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    weights = ref.make_weights(cfg, seed)
    variables = program_variables(weights, cfg)
    del weights
    registry = MetricsRegistry()          # this run's counters alone
    engine = build_engine(cfg, params, variables, registry)
    stream = traffic_requests.RequestStream(params["inputs"], seed)
    return {
        "cfg": cfg, "params": params, "seed": seed, "engine": engine,
        "variables": variables, "stream": stream,
        "tokens": _metric(registry, "gen_tokens_total"),
        "decode_steps": _metric(registry, "gen_decode_steps_total"),
        "ttft": _metric(registry, "gen_ttft_seconds"),
        "gather_bytes": _metric(registry, "kv_dense_gather_bytes_total"),
        "blocks_used": _metric(registry, "kv_blocks_used"),
        "next": 0, "live": {}, "finished": [], "stats": [],
        "prefilled_at": {}, "boundary": 0, "refill": True,
        "record": True, "stalled_boundaries": 0, "idle": 0,
        "counted": (0, 0)}


def _submit_next(ctx: dict) -> None:
    k = ctx["next"]
    prompt, max_new = ctx["stream"].request(k)
    ctx["engine"].submit(k, prompt, max_new)
    ctx["live"][k] = (len(prompt), max_new)
    ctx["next"] = k + 1


def _prefills(ctx: dict) -> int:
    return sum(ctx["ttft"].count(service=SERVICE, reuse=r)
               for r in ("cold", "warm"))


def step(ctx: dict) -> int:
    """One boundary and the refill; returns the generated tokens
    committed at it."""
    t0 = time.perf_counter()
    done = ctx["engine"].step()
    boundary = ctx["boundary"]
    ctx["boundary"] = boundary + 1
    decoded, prefills = int(ctx["tokens"].value(service=SERVICE)), \
        _prefills(ctx)
    was_decoded, was_prefills = ctx["counted"]
    ctx["counted"] = (decoded, prefills)
    prefilled = prefills - was_prefills
    committed = decoded - was_decoded + prefilled
    # admitted a boundary ago and still without its first token: the
    # pool had no blocks for it (or no slot was free)
    stalled = ctx["next"] - prefills
    ctx["stalled_boundaries"] += stalled > 0
    for seq_id, tokens in done:
        prompt_len, max_new = ctx["live"].pop(seq_id)
        ctx["finished"].append({
            "request": seq_id, "boundary": boundary,
            "prompt_len": prompt_len, "max_new": max_new,
            "tokens": np.asarray(tokens), "in_window": ctx["record"]})
        if ctx["refill"]:
            _submit_next(ctx)
    ctx["idle"] = 0 if committed else ctx["idle"] + 1
    if ctx["idle"] > MAX_IDLE_BOUNDARIES:
        raise RuntimeError(
            f"{MAX_IDLE_BOUNDARIES} boundaries in a row committed no token "
            f"({len(ctx['live'])} requests live, {stalled} without blocks): "
            "the pool is too small for this traffic")
    if prefilled:
        ctx["prefilled_at"][boundary] = prefilled
    if ctx["record"]:
        ctx["stats"].append({
            "boundary": boundary, "seconds": time.perf_counter() - t0,
            "tokens": committed, "prefilled": prefilled,
            "finished": len(done), "stalled": stalled,
            "blocks_used": ctx["blocks_used"].value(service=SERVICE),
            "decode_tokens_total": decoded,
            "decode_steps_total": int(
                ctx["decode_steps"].value(service=SERVICE)),
            "stalled_boundaries": ctx["stalled_boundaries"],
            "dense_gather_bytes": ctx["gather_bytes"].value(
                service=SERVICE, phase="decode") + ctx["gather_bytes"].value(
                service=SERVICE, phase="prefill")})
    return committed


def warm(ctx: dict) -> None:
    """Compile the decode program and every prefill window the table's
    prompt lengths are fed through, then run the loop until
    ``warm_requests`` have finished, so that the callers are out of step
    with each other as in a long job."""
    params = ctx["params"]
    lengths = sorted({int(n) for n in ctx["stream"].table[:, 0]})
    ctx["engine"].warm(prefill_windows=tuple(lengths), mark_steady=False)
    for _ in range(int(params["callers"])):
        _submit_next(ctx)
    while len(ctx["finished"]) < int(params["warm_requests"]):
        step(ctx)
    ctx["finished"].clear()
    ctx["stats"].clear()
    ctx["stalled_boundaries"] = 0


def after_window(ctx: dict, trace: bool) -> None:
    """The window is closed. For a traced run, let what is in flight run
    to its end with no refill, so that the per-layer readers know where
    every sequence of the window stood at every boundary (a sequence's
    prefill boundary follows from the boundary it finished at)."""
    ctx["record"] = False
    ctx["refill"] = False
    if trace:
        while ctx["live"]:
            step(ctx)


def _check_samples(ctx: dict) -> list:
    """Indices of the finished requests to compare: the first and the
    last to finish, the longest prompt, one whose prefill shared a batch
    where there is one, the rest drawn from the seed."""
    done = [f for f in ctx["finished"] if f["in_window"]]
    if not done:
        return []
    always = [0, len(done) - 1,
              max(range(len(done)), key=lambda i: done[i]["prompt_len"])]
    for i, f in enumerate(done):
        first = f["boundary"] - max(f["max_new"] - 2, 0)
        if ctx["prefilled_at"].get(first, 0) > 1:
            always.append(i)
            break
    picks = traffic.sample_rows(
        ctx["seed"], len(done), int(ctx["params"]["check_sequences"]),
        always)
    return [done[int(i)] for i in picks]


def outputs_for_check(ctx: dict) -> dict:
    """What the window produced (the sampled requests' prompts as sent
    and tokens as served); drops the program's state so the reference has
    the device."""
    samples = []
    for f in _check_samples(ctx):
        prompt, _ = ctx["stream"].request(f["request"])
        samples.append({"request": f["request"], "prompt": prompt,
                        "tokens": f["tokens"], "max_new": f["max_new"]})
    out = {"samples": samples,
           "finished": sum(f["in_window"] for f in ctx["finished"]),
           "chunk": int(ctx["params"]["prefill_chunk"])}
    for key in ("engine", "variables"):
        ctx.pop(key, None)
    return out


def _pairs(outputs: dict) -> list:
    """``(prompt, served tokens)`` of each sample; a request that came
    back with another prompt or length than was sent has no served
    tokens to score."""
    pairs = []
    for s in outputs["samples"]:
        p = len(s["prompt"])
        echoed = len(s["tokens"]) == p + s["max_new"] and np.array_equal(
            s["tokens"][:p], s["prompt"])
        pairs.append((s["prompt"], s["tokens"][p:] if echoed
                      else np.zeros(0, np.int32)))
    return pairs


def check(outputs: dict, cfg: dict, params: dict, seed: int,
          variant: str | None = None, weights: dict | None = None) -> list:
    """The comparison of ``references/xglm.compare`` over the sampled
    requests; ``variant`` puts a control in the program's place."""
    pairs = _pairs(outputs)
    if any(len(served) == 0 for _, served in pairs) or not pairs:
        return [(name, ref.NOT_CORRECT, params["limits"][name])
                for name in ref.NUMBERS]
    details: dict = {}
    out = ref.compare(
        weights or ref.make_weights(cfg, seed), cfg, pairs,
        params["limits"], variant=variant, chunk=outputs["chunk"],
        details=details,
        pad={"pad_to": int(params["engine"]["max_seq_len"]),
             "pad_rows_to": int(params["inputs"]["output"]["max"])})
    print(json.dumps({"compared": {
        "variant": variant, "finished_in_window": outputs["finished"],
        "requests": [s["request"] for s in outputs["samples"]],
        **details}}), file=sys.stderr)
    return out


def control_checks(cfg: dict, params: dict, seed: int) -> list:
    """The program's own numbers, then the controls, each in the
    program's place at the prompts and tokens the program served in a
    short window at the cell's own load: the reference with both operands
    of every matrix product rounded to scaled e4m3 (one step below the
    configuration's bfloat16), and the fault of the path planted in the
    reference."""
    ctx = setup(cfg, params, seed)
    warm(ctx)
    for _ in range(int(params["control_boundaries"])):
        step(ctx)
    after_window(ctx, False)
    outputs = outputs_for_check(ctx)
    del ctx
    weights = ref.make_weights(cfg, seed)
    out = []
    for variant in (None, "e4m3") + ref.FAULTS:
        out += [(f"{variant or 'program'}.{name}", value, limit)
                for name, value, limit
                in check(outputs, cfg, params, seed, variant, weights)]
    return out
