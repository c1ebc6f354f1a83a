"""Driver of the document question-answering cells: a closed loop of
callers over ``serving.llm.LLMEngine.submit/step`` in which every request
is one of a few long documents plus its own short suffix
(``traffic_docqa``). The documents are prefilled once during set-up and
stay in the engine's prefix index through the window, so a request
prefills its suffix alone. One operation is one step boundary plus the
refill, as in ``drivers/generate.py``, whose boundary this driver runs
(``generate.step``) and adds to: which request was prefilled at which
boundary, the expert layers' counts and the prefix index's.

The configuration is DeepSeek-V2's (``references/deepseek_v2.py``): the
benchmark makes the weights from the seed and hands the program the SAME
arrays as its parameters (``dl.LatentMoEDecoder`` takes the reference's
tree as it is; at 10 GB there is no room for a second copy).
"""

from __future__ import annotations

import json
import sys
from collections import deque


from benchmark import traffic_docqa
from benchmark.drivers import generate
from benchmark.references import deepseek_v2 as ref

SERVICE = generate.SERVICE
PAD_ID = 0
COUNTERS = {"moe_held": "moe_pairs_held_total",
            "moe_absent": "moe_pairs_absent_total",
            "moe_touched": "moe_experts_touched_total",
            "prefix_reused": "kv_prefix_tokens_reused_total"}


class _Since:
    """A histogram's counts since a mark: the documents' own prefills in
    set-up are not the callers' (``generate.step`` takes every request it
    sent and has not seen prefilled for stalled)."""

    def __init__(self, hist):
        self.hist, self.base = hist, {}

    def mark(self) -> None:
        self.base = {r: self.hist.count(service=SERVICE, reuse=r)
                     for r in ("cold", "warm")}

    def count(self, *, service: str, reuse: str) -> int:
        return self.hist.count(service=service, reuse=reuse) \
            - self.base.get(reuse, 0)


def build_engine(cfg: dict, params: dict, weights: dict, registry):
    import jax.numpy as jnp
    from mmlspark_tpu.dl.latent_moe_decoder import LatentMoEDecoder
    from mmlspark_tpu.serving.llm import LLMEngine

    module = LatentMoEDecoder(cfg, dtype=jnp.dtype(cfg["cache_dtype"]))
    eng = params["engine"]
    return LLMEngine(
        module, {"params": weights}, slots=int(eng["slots"]),
        block_len=int(eng["block_len"]), max_seq_len=int(eng["max_seq_len"]),
        num_blocks=int(eng["num_blocks"]),
        prefill_batch=int(eng["prefill_batch"]),
        hbm_fraction=float(eng["hbm_fraction"]), pad_id=PAD_ID,
        service=SERVICE, registry=registry)


def setup(cfg: dict, params: dict, seed: int) -> dict:
    # a program without this decoder fails here, at once, and not after
    # 10 GB of weights are made
    from mmlspark_tpu.dl import latent_moe_decoder  # noqa: F401
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    weights = ref.make_weights(cfg, seed)
    registry = MetricsRegistry()          # this run's counters alone
    engine = build_engine(cfg, params, weights, registry)
    stream = traffic_docqa.DocQAStream(params["inputs"], seed)
    metric = generate._metric
    ctx = {
        "cfg": cfg, "params": params, "seed": seed, "engine": engine,
        "variables": weights, "stream": stream,
        "tokens": metric(registry, "gen_tokens_total"),
        "decode_steps": metric(registry, "gen_decode_steps_total"),
        "ttft": _Since(metric(registry, "gen_ttft_seconds")),
        "gather_bytes": metric(registry, "kv_dense_gather_bytes_total"),
        "blocks_used": metric(registry, "kv_blocks_used"),
        "load_max": metric(registry, "moe_expert_load_max"),
        "next": 0, "live": {}, "finished": [], "stats": [],
        "prefilled_at": {}, "boundary": 0, "refill": True,
        "record": True, "stalled_boundaries": 0, "idle": 0,
        "counted": (0, 0),
        # requests sent and not yet prefilled, oldest first, and the
        # boundary at which each request was prefilled
        "awaiting": deque(), "started": {},
        "counters": {key: metric(registry, name)
                     for key, name in COUNTERS.items()},
        "counts_before": {key: 0.0 for key in COUNTERS}}
    return ctx


def _note_started(ctx: dict, boundary: int) -> int:
    """The requests prefilled at ``boundary`` are the oldest sent and
    not yet prefilled (the scheduler admits in order). Returns their
    prompts' tokens, document and suffix."""
    stream, tokens = ctx["stream"], 0
    for _ in range(ctx["prefilled_at"].get(boundary, 0)):
        k = ctx["awaiting"].popleft()
        ctx["started"][k] = boundary
        doc, suffix_len, _ = stream.size(k)
        tokens += stream.doc_lens[doc] + suffix_len
    return tokens


def step(ctx: dict) -> int:
    """One boundary and the refill (``generate.step``); notes which
    requests it prefilled and what the engine's counters moved by."""
    first = ctx["next"]
    committed = generate.step(ctx)
    prompt_tokens = _note_started(ctx, ctx["boundary"] - 1)
    ctx["awaiting"].extend(range(first, ctx["next"]))
    now = {key: c.value(service=SERVICE)
           for key, c in ctx["counters"].items()}
    moved = {key: now[key] - ctx["counts_before"][key] for key in now}
    ctx["counts_before"] = now
    if ctx["record"]:
        ctx["stats"][-1].update(
            moved, load_max=ctx["load_max"].value(service=SERVICE),
            prompt_tokens=prompt_tokens)
    return committed


def warm(ctx: dict) -> None:
    """Compile the decode program and every prefill window the documents
    and the table's suffixes are fed through; prefill each document once
    (a request of one new token, after which its blocks stay in the
    prefix index); then run the loop until ``warm_requests`` have
    finished, so that the callers are out of step with each other."""
    params, stream, engine = ctx["params"], ctx["stream"], ctx["engine"]
    lengths = sorted({int(n) for n in stream.table[:, 1]}
                     | set(stream.doc_lens))
    engine.warm(prefill_windows=tuple(lengths), mark_steady=False)
    for d in range(len(stream.doc_lens)):
        engine.submit(f"document-{d}", stream.document(d), 1)
    engine.run_until_drained()
    # what the set-up moved is not the window's
    ctx["ttft"].mark()
    ctx["counted"] = (int(ctx["tokens"].value(service=SERVICE)), 0)
    for _ in range(int(params["callers"])):
        generate._submit_next(ctx)
    ctx["awaiting"].extend(range(ctx["next"]))
    while len(ctx["finished"]) < int(params["warm_requests"]):
        step(ctx)
    ctx["finished"].clear()
    ctx["stats"].clear()
    ctx["stalled_boundaries"] = 0


def after_window(ctx: dict, trace: bool) -> None:
    """The window is closed: nothing more is sent or recorded. (What is
    in flight need not finish: the readers know from ``started`` where
    every request of the window stood at every boundary.)"""
    ctx["record"] = False
    ctx["refill"] = False


def chunk_start(doc_len: int, prompt_len: int, chunk: int) -> int:
    """Where the last prefill chunk of a request began whose document
    was in the index: the engine feeds the suffix in chunks of
    ``chunk``."""
    return doc_len + (prompt_len - doc_len - 1) // chunk * chunk


def outputs_for_check(ctx: dict) -> dict:
    """What the window produced (the sampled requests' prompts as sent
    and tokens as served); drops the program's state, the weights with
    it, so that the reference has the device."""
    stream = ctx["stream"]
    chunk = int(ctx["params"]["prefill_chunk"])
    samples = []
    for f in generate._check_samples(ctx):
        prompt, _ = stream.request(f["request"])
        doc_len = stream.doc_lens[stream.size(f["request"])[0]]
        samples.append({
            "request": f["request"], "prompt": prompt,
            "tokens": f["tokens"], "max_new": f["max_new"],
            "chunk_start": chunk_start(doc_len, len(prompt), chunk)})
    out = {"samples": samples,
           "finished": sum(f["in_window"] for f in ctx["finished"])}
    for key in ("engine", "variables"):
        ctx.pop(key, None)
    return out


def check(outputs: dict, cfg: dict, params: dict, seed: int,
          variant: str | None = None, weights: dict | None = None,
          kept: dict | None = None) -> list:
    """The comparison of ``references/deepseek_v2.compare`` over the
    sampled requests; ``variant`` puts a control in the program's
    place."""
    pairs = generate._pairs(outputs)
    if any(len(served) == 0 for _, served in pairs) or not pairs:
        return [(name, ref.NOT_CORRECT, params["limits"][name])
                for name in ref.NUMBERS]
    samples = [(prompt, served, s["chunk_start"])
               for (prompt, served), s in zip(pairs, outputs["samples"])]
    details: dict = {}
    out = ref.compare(
        weights or ref.make_weights(cfg, seed), cfg, samples,
        params["limits"], variant=variant, details=details, kept=kept,
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
    configuration's bfloat16), and each fault of the path planted in the
    reference."""
    ctx = setup(cfg, params, seed)
    warm(ctx)
    for _ in range(int(params["control_boundaries"])):
        step(ctx)
    after_window(ctx, False)
    outputs = outputs_for_check(ctx)
    del ctx
    weights = ref.make_weights(cfg, seed)
    out, kept = [], {}
    for variant in (None, "e4m3") + ref.FAULTS:
        out += [(f"{variant or 'program'}.{name}", value, limit)
                for name, value, limit
                in check(outputs, cfg, params, seed, variant, weights, kept)]
    return out
