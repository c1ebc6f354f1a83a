"""Driver of the long-document question-answering cells: the closed loop of
``drivers/doc_qa.py`` (callers over ``serving.llm.LLMEngine.submit/step``,
every request one of a few resident documents plus its own short suffix,
``traffic_docqa`` as it is, one operation one ``generate.step`` boundary
plus the refill) over a decoder that keeps two kinds of cache: keys,
values and compressed keys in paged blocks, and a recurrent state a
sequence. The documents are prefilled once during set-up and stay in the
prefix index WITH the snapshot of the state at each one's end, so a
request restores that snapshot and prefills its suffix alone.

The configuration is MiniCPM-SALA's (``references/minicpm_sala.py``): the
benchmark makes the weights from the seed and hands the program the SAME
arrays as its parameters (``dl.SparseLinearDecoder`` takes the reference's
tree as it is; at 7.9 GB there is no room for a second copy).

What it adds to a boundary's record: the sparse layers' walk counts, the
prefix index's and the state rows' counters. After a traced window it
sums its own kernels' seconds from the trace, which is still on disk then
(``run.py`` sums one ``kernel_pattern``; this cell has four kernels).
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import sys
from collections import deque

import numpy as np

from benchmark import traffic_docqa, traffic_requests, trace_reduce
from benchmark.drivers import doc_qa as base
from benchmark.drivers import generate
from benchmark.references import minicpm_sala as ref

SERVICE = generate.SERVICE
PAD_ID = 0
COUNTERS = {"blocks_chosen": "sparse_blocks_chosen_total",
            "blocks_in_chain": "sparse_blocks_in_chain_total",
            "dense_rows": "sparse_dense_rows_total",
            "prefix_reused": "kv_prefix_tokens_reused_total",
            "state_restores": "kv_state_restores_total",
            "snapshot_evictions": "kv_state_snapshot_evictions_total"}
GAUGES = {"state_slots_used": "kv_state_slots_used",
          "state_snapshots": "kv_state_snapshots",
          "state_bytes": "kv_state_bytes"}
#: the program's kernels by the names their events carry in a device trace
KERNELS = {"sparse_attn": "paged_sparse_attn",
           "sparse_select": "paged_sparse_select",
           "lightning_step": "lightning_step",
           "lightning_chunk": "lightning_chunk"}


class Stream(traffic_docqa.DocQAStream):
    """``traffic_docqa.DocQAStream`` as it is, over a table laid out for
    ANY count of documents: its own ``size_table`` walks the documents in
    steps of 3 and so refuses a count that is a multiple of 3 (this cell
    has six); here request ``k`` of round ``r`` asks document ``(a k + r)
    mod g`` with ``a`` the least of 3, 5, 7, 11 that shares no factor with
    ``g``. Every round still holds every document once, and over the
    rounds a document meets every g-tile of the suffixes."""

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.doc_lens = [int(n) for n in spec["documents"]]
        g = len(self.doc_lens)
        if g * g != int(spec["table"]):
            raise ValueError("table has to be the documents' count squared")
        sizes = traffic_requests.size_table(
            {**spec, "prompt": spec["suffix"]})
        a = next(a for a in (3, 5, 7, 11) if math.gcd(a, g) == 1)
        doc = [(a * k + r) % g for r in range(g) for k in range(g)]
        self.table = np.column_stack([np.asarray(doc, np.int64), sizes])
        self._orders, self._docs = {}, {}


def build_engine(cfg: dict, params: dict, weights: dict, registry):
    import jax.numpy as jnp
    from mmlspark_tpu.dl.sparse_linear_decoder import SparseLinearDecoder
    from mmlspark_tpu.serving.llm import LLMEngine

    module = SparseLinearDecoder(cfg, dtype=jnp.dtype(cfg["cache_dtype"]),
                                 max_window=int(params["prefill_chunk"]))
    eng = params["engine"]
    return LLMEngine(
        module, {"params": weights}, slots=int(eng["slots"]),
        block_len=int(eng["block_len"]), max_seq_len=int(eng["max_seq_len"]),
        num_blocks=int(eng["num_blocks"]),
        state_slots=int(eng["state_slots"]),
        prefill_batch=int(eng["prefill_batch"]),
        hbm_fraction=float(eng["hbm_fraction"]), pad_id=PAD_ID,
        service=SERVICE, registry=registry)


def setup(cfg: dict, params: dict, seed: int) -> dict:
    # a program without this decoder fails here, at once, and not after
    # 8 GB of weights are made
    from mmlspark_tpu.dl import sparse_linear_decoder  # noqa: F401
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    weights = ref.make_weights(cfg, seed)
    registry = MetricsRegistry()          # this run's counters alone
    engine = build_engine(cfg, params, weights, registry)
    stream = Stream(params["inputs"], seed)
    metric = generate._metric
    return {
        "cfg": cfg, "params": params, "seed": seed, "engine": engine,
        "variables": weights, "stream": stream,
        "tokens": metric(registry, "gen_tokens_total"),
        "decode_steps": metric(registry, "gen_decode_steps_total"),
        "ttft": base._Since(metric(registry, "gen_ttft_seconds")),
        "gather_bytes": metric(registry, "kv_dense_gather_bytes_total"),
        "blocks_used": metric(registry, "kv_blocks_used"),
        "next": 0, "live": {}, "finished": [], "stats": [],
        "prefilled_at": {}, "boundary": 0, "refill": True,
        "record": True, "stalled_boundaries": 0, "idle": 0,
        "counted": (0, 0),
        # requests sent and not yet prefilled, oldest first, and the
        # boundary at which each request was prefilled
        "awaiting": deque(), "started": {},
        "counters": {key: metric(registry, name)
                     for key, name in COUNTERS.items()},
        "gauges": {key: metric(registry, name)
                   for key, name in GAUGES.items()},
        "counts_before": {key: 0.0 for key in COUNTERS}}


def step(ctx: dict) -> int:
    """One boundary and the refill (``generate.step``); notes which
    requests it prefilled and what the engine's counters moved by."""
    first = ctx["next"]
    committed = generate.step(ctx)
    prompt_tokens = base._note_started(ctx, ctx["boundary"] - 1)
    ctx["awaiting"].extend(range(first, ctx["next"]))
    now = {key: c.value(service=SERVICE)
           for key, c in ctx["counters"].items()}
    moved = {key: now[key] - ctx["counts_before"][key] for key in now}
    ctx["counts_before"] = now
    if ctx["record"]:
        ctx["stats"][-1].update(
            moved, prompt_tokens=prompt_tokens,
            **{key: g.value(service=SERVICE)
               for key, g in ctx["gauges"].items()})
    return committed


def warm(ctx: dict) -> None:
    """Compile the decode program, the state-row copy and every prefill
    window the documents and the table's suffixes are fed through;
    prefill each document once (a request of one new token, after which
    its blocks and the snapshot of its state stay in the prefix index);
    then run the loop until ``warm_requests`` have finished, so that the
    callers are out of step with each other."""
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
    ctx["counts_before"] = {key: c.value(service=SERVICE)
                            for key, c in ctx["counters"].items()}
    for _ in range(int(params["callers"])):
        generate._submit_next(ctx)
    ctx["awaiting"].extend(range(ctx["next"]))
    while len(ctx["finished"]) < int(params["warm_requests"]):
        step(ctx)
    ctx["finished"].clear()
    ctx["stats"].clear()
    ctx["stalled_boundaries"] = 0


def kernel_seconds(trace: dict) -> dict:
    """Seconds and calls of each of ``KERNELS`` on the first device plane
    of a loaded trace; nothing where the trace has no device plane."""
    lines = next((ln for name, ln in sorted(trace.items())
                  if trace_reduce.DEVICE_PLANE.match(name)), None)
    if lines is None:
        return {}
    events = [ev for ln in trace_reduce.OP_LINES for ev in lines.get(ln, ())]
    out = {}
    for key, pattern in KERNELS.items():
        hits = [d for name, _, d in events if re.search(pattern, name)]
        out[key] = {"seconds": sum(hits) / 1e9, "calls": len(hits)}
    return out


def after_window(ctx: dict, trace: bool) -> None:
    """The window is closed: nothing more is sent or recorded. A traced
    run's trace is still on disk: its kernels' seconds are read now."""
    ctx["record"] = False
    ctx["refill"] = False
    if not trace:
        return
    # the one trace this process wrote (``run.py`` removes a cell's
    # directory before it starts the profiler)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ctx["kernels"] = {}
    for trace_dir in sorted(glob.glob(os.path.join(
            root, ".bench_out", "trace", "*"))):
        try:
            ctx["kernels"] = kernel_seconds(
                trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
        except FileNotFoundError:
            continue


def _check_samples(ctx: dict) -> list:
    """The finished requests to compare: the first to finish on each of
    the ``check_long_documents`` longest documents, then the first to
    finish on documents of at most ``check_short_document_max`` tokens,
    ``check_sequences`` in all (the reference computes a whole document's
    forward pass for each)."""
    params, stream = ctx["params"], ctx["stream"]
    done = [f for f in ctx["finished"] if f["in_window"]]
    by_len = sorted(set(stream.doc_lens), reverse=True)
    long_lens = by_len[:int(params["check_long_documents"])]
    picks, seen_long = [], set()
    for f in done:
        n = stream.doc_lens[stream.size(f["request"])[0]]
        if n in long_lens and n not in seen_long:
            seen_long.add(n)
            picks.append(f)
    for f in done:
        if len(picks) >= int(params["check_sequences"]):
            break
        n = stream.doc_lens[stream.size(f["request"])[0]]
        if n <= int(params["check_short_document_max"]) and f not in picks:
            picks.append(f)
    return picks[:int(params["check_sequences"])]


def outputs_for_check(ctx: dict) -> dict:
    """What the window produced (the sampled requests' prompts as sent
    and tokens as served); drops the program's state, the weights with
    it, so that the reference has the device."""
    stream = ctx["stream"]
    samples = []
    for f in _check_samples(ctx):
        prompt, _ = stream.request(f["request"])
        samples.append({
            "request": f["request"], "prompt": prompt,
            "tokens": f["tokens"], "max_new": f["max_new"],
            "doc_len": stream.doc_lens[stream.size(f["request"])[0]]})
    out = {"samples": samples,
           "finished": sum(f["in_window"] for f in ctx["finished"])}
    for key in ("engine", "variables"):
        ctx.pop(key, None)
    return out


def check(outputs: dict, cfg: dict, params: dict, seed: int,
          variant: str | None = None, weights: dict | None = None,
          kept: dict | None = None) -> list:
    """The comparison of ``references/minicpm_sala.compare`` over the
    sampled requests; ``variant`` puts a control in the program's
    place."""
    pairs = generate._pairs(outputs)
    if any(len(served) == 0 for _, served in pairs) or not pairs:
        return [(name, ref.NOT_CORRECT, params["limits"][name])
                for name in ref.NUMBERS]
    samples = [(prompt, served, s["doc_len"])
               for (prompt, served), s in zip(pairs, outputs["samples"])]
    inputs = params["inputs"]
    tail = int(inputs["suffix"]["max"]) + int(inputs["output"]["max"])
    pads = [{"pad_to": s["doc_len"] + tail,
             "pad_rows_to": int(inputs["output"]["max"])}
            for s in outputs["samples"]]
    details: dict = {}
    out = ref.compare(
        weights or ref.make_weights(cfg, seed), cfg, samples,
        params["limits"], variant=variant, details=details, kept=kept,
        pads=pads)
    print(json.dumps({"compared": {
        "variant": variant, "finished_in_window": outputs["finished"],
        "requests": [s["request"] for s in outputs["samples"]],
        "documents": [s["doc_len"] for s in outputs["samples"]],
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
