"""Driver of the chat cells: the closed loop of ``drivers/generate.py``
(callers over ``serving.llm.LLMEngine.submit/step``, one operation one
``generate.step`` boundary plus the refill) in which every request is ONE
system prompt, the same for all, plus its own turn of hundreds to thousands
of tokens, unshared, and an answer run to its end. The system prompt is
prefilled once during set-up and stays in the prefix index WITH the
snapshot of every per-sequence array at its end (a DeltaNet layer's state
and its convolution's tail), so a request restores that snapshot and
prefills its turn, window by window, riding with the decoding rows
(``traffic_docqa.DocQAStream`` cannot take one document and a table of 64:
it asks for the documents' count squared).

The configuration is Qwen3-Next's (``references/qwen3_next.py``): the
benchmark makes the weights from the seed and hands the program the SAME
arrays as its parameters (``dl.GatedDeltaMoEDecoder`` takes the reference's
tree as it is; at 7.3 GB there is no room for a second copy).

What it adds to a boundary's record: the ONE program the boundary ran, as
the rows it computed by position — each decoding sequence's row, each
window that rode (read off the engine's executors before and after the
step: ``decoder.ptr``, ``prefiller._queue``) — and the moves of the
engine's counters (``moe_*``, ``gdn_*``, ``kv_state_*``,
``gen_prefill_rows_total``). After a traced window it sums its own kernels'
seconds from the trace, which is still on disk then (``run.py`` sums one
``kernel_pattern``; this cell has three kernels).
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

from benchmark import trace_reduce, traffic_docqa, traffic_requests
from benchmark.drivers import doc_qa as base
from benchmark.drivers import generate
from benchmark.references import qwen3_next as ref

SERVICE = generate.SERVICE
PAD_ID = 0
COUNTERS = {"moe_held": "moe_pairs_held_total",
            "moe_absent": "moe_pairs_absent_total",
            "moe_touched": "moe_experts_touched_total",
            "gdn_step_rows": "gdn_step_rows_total",
            "gdn_chunk_rows": "gdn_chunk_rows_total",
            "gdn_windows_carried": "gdn_windows_carried_total",
            "prefix_reused": "kv_prefix_tokens_reused_total",
            "state_restores": "kv_state_restores_total",
            "snapshot_evictions": "kv_state_snapshot_evictions_total"}
GAUGES = {"state_slots_used": "kv_state_slots_used",
          "state_snapshots": "kv_state_snapshots",
          "state_bytes": "kv_state_bytes",
          "load_max": "moe_expert_load_max"}
#: the program's kernels by the names their events carry in a device trace
KERNELS = {"gdn_step": "gated_delta_step", "gdn_chunk": "gated_delta_chunk",
           "gqa_attn": "paged_gqa_attn"}


class Stream(traffic_docqa.DocQAStream):
    """``traffic_docqa.DocQAStream``'s requests (a document from the seed,
    then the request's own ids; made when asked for, the same whenever
    asked) over ``traffic_requests``' table: ONE document, the system
    prompt, and ``table`` = g * g sizes of (own turn, answer) in g balanced
    rounds, every seed the same rounds in another order."""

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.doc_lens = [int(spec["system_prompt"])]
        sizes = traffic_requests.size_table({**spec, "prompt": spec["turn"]})
        self.table = np.column_stack(
            [np.zeros(len(sizes), np.int64), sizes])
        self._orders, self._docs = {}, {}

    def size(self, k: int) -> tuple:
        """``(0, turn length, max_new_tokens)``."""
        n = len(self.table)
        g = math.isqrt(n)
        epoch, within = divmod(int(k), n)
        if epoch not in self._orders:
            rng = np.random.default_rng([self.seed, 0, epoch])
            self._orders[epoch] = np.concatenate(
                [r * g + rng.permutation(g) for r in rng.permutation(g)])
        return tuple(int(v) for v in self.table[self._orders[epoch][within]])


def build_engine(cfg: dict, params: dict, weights: dict, registry):
    import jax.numpy as jnp
    from mmlspark_tpu.dl.gated_delta_moe_decoder import GatedDeltaMoEDecoder
    from mmlspark_tpu.serving.llm import LLMEngine

    module = GatedDeltaMoEDecoder(cfg, dtype=jnp.dtype(cfg["cache_dtype"]),
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
    # 7 GB of weights are made
    from mmlspark_tpu.dl import gated_delta_moe_decoder  # noqa: F401
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
        "prefill_rows": metric(registry, "gen_prefill_rows_total"),
        "next": 0, "live": {}, "finished": [], "stats": [],
        "prefilled_at": {}, "boundary": 0, "refill": True,
        "record": True, "stalled_boundaries": 0, "idle": 0,
        "counted": (0, 0),
        # requests sent and not yet prefilled, oldest first, the boundary
        # at which each was prefilled, and those whose prompt is wholly in
        "awaiting": deque(), "started": {}, "fed": set(),
        "counters": {key: metric(registry, name)
                     for key, name in COUNTERS.items()},
        "gauges": {key: metric(registry, name)
                   for key, name in GAUGES.items()},
        "counts_before": {key: 0.0 for key in COUNTERS},
        "rows_before": (0.0, 0.0)}


def _decoding(engine) -> dict:
    """``request -> committed tokens`` of the slots that hold one."""
    dec = engine.decoder
    return {sid: int(dec.ptr[i]) for i, sid in enumerate(dec.seq_ids)
            if sid is not None and dec.active[i]}


def _feeding(engine) -> dict:
    """``request -> next position to feed`` of the prompts on their way
    into the cache."""
    return {f.seq_id: int(f.at)
            for f in getattr(engine.prefiller, "_queue", ())}


def _rows_of_program(ctx: dict, before: tuple, live_before: dict) -> list:
    """``[(first position, rows)]`` of every sequence the boundary's
    program computed rows of: a decoding sequence's one row, a prompt's
    window. Read from where the engine's executors stood before the step
    and stand after it."""
    engine = ctx["engine"]
    dec_before, feed_before = before
    dec_after, feed_after = _decoding(engine), _feeding(engine)
    calls = []
    for sid, ptr in dec_before.items():
        # a slot that left at this boundary decoded its last row in it
        n = dec_after[sid] - ptr if sid in dec_after else 1
        if n > 0:
            calls.append((ptr - 1, n))
    for sid, (prompt_len, _) in live_before.items():
        if sid in ctx["fed"] or sid in dec_before:
            ctx["fed"].add(sid)
            continue
        if sid in feed_after:
            start = feed_before.get(sid)
            if start is None:               # admitted at this boundary
                start = min(int(engine.kv.handle(sid).reused_tokens),
                            prompt_len - 1)
            if feed_after[sid] > start:
                calls.append((start, feed_after[sid] - start))
            continue
        if sid in dec_after or sid not in ctx["live"]:
            # wholly in since this boundary: its last window ended here
            start = feed_before.get(sid)
            if start is None:               # admitted at this boundary too
                start = min(int(engine.kv.handle(sid).reused_tokens),
                            prompt_len - 1) if sid in dec_after else 0
            calls.append((start, prompt_len - start))
            ctx["fed"].add(sid)
            if sid in dec_after and dec_after[sid] > prompt_len + 1:
                calls.append((prompt_len, dec_after[sid] - prompt_len - 1))
    return calls


def step(ctx: dict) -> int:
    """One boundary and the refill (``generate.step``); notes which
    requests it prefilled, the rows its program computed and what the
    engine's counters moved by."""
    first = ctx["next"]
    engine = ctx["engine"]
    before = (_decoding(engine), _feeding(engine))
    live_before = dict(ctx["live"])
    committed = generate.step(ctx)
    prompt_tokens = base._note_started(ctx, ctx["boundary"] - 1)
    ctx["awaiting"].extend(range(first, ctx["next"]))
    calls = _rows_of_program(ctx, before, live_before)
    now = {key: c.value(service=SERVICE)
           for key, c in ctx["counters"].items()}
    moved = {key: now[key] - ctx["counts_before"][key] for key in now}
    ctx["counts_before"] = now
    rows = tuple(ctx["prefill_rows"].value(service=SERVICE, ride=r)
                 for r in ("decode", "alone"))
    rode, alone = (a - b for a, b in zip(rows, ctx["rows_before"]))
    ctx["rows_before"] = rows
    if ctx["record"]:
        entry = ctx["stats"][-1]
        entry.update(
            moved, prompt_tokens=prompt_tokens, calls=calls,
            ride_rows=rode, alone_rows=alone,
            doc_len=int(ctx["stream"].doc_lens[0]),
            logit_rows=sum(1 for _, n in calls if n == 1)
            + entry["prefilled"],
            **{key: g.value(service=SERVICE)
               for key, g in ctx["gauges"].items()})
    return committed


def window_rows(prompt_len: int, doc_len: int, block_len: int,
                chunk: int) -> list:
    """The real rows of each window a prompt is fed in after its system
    prompt was found in the index: the engine feeds ``[doc_len, cut)``
    and then ``[cut, prompt_len)`` in windows of ``chunk``, ``cut`` the
    prompt's last whole block where it brings new whole blocks (its
    snapshot is taken there)."""
    cut = prompt_len // block_len * block_len
    stretches = [(doc_len, cut), (cut, prompt_len)] \
        if doc_len < cut < prompt_len else [(doc_len, prompt_len)]
    return [min(chunk, stop - at) for start, stop in stretches
            for at in range(start, stop, chunk)]


def warm(ctx: dict) -> None:
    """Compile the decode program, the state-row copy and every window
    the table's turns are fed through, alone (by bucket) and riding (by
    the next multiple of 32 rows): the table's 64 sizes are the same for
    every seed, and so are the windows they are cut into (whole windows of
    ``prefill_chunk``, the window that ends at a prompt's last whole
    block, and the rest), which is fewer programs than every width the
    ladder has; prefill the system prompt once (a request of one new
    token, after which its blocks and the snapshot of its state and tail
    stay in the prefix index); then run the loop until ``warm_requests``
    have finished, so that the callers are out of step with each
    other."""
    params, stream, engine = ctx["params"], ctx["stream"], ctx["engine"]
    chunk = int(params["prefill_chunk"])
    doc_len = int(stream.doc_lens[0])
    rows = {n for turn in stream.table[:, 1] for n in window_rows(
        doc_len + int(turn), doc_len, int(params["engine"]["block_len"]),
        chunk)}
    # a whole window and the rest of one also with no prompt's end in it
    lengths = sorted({-(-n // 32) * 32 for n in rows}
                     | {chunk + n for n in rows if n < chunk} | {doc_len})
    engine.warm(prefill_windows=tuple(lengths), mark_steady=False)
    engine.submit("system-prompt", stream.document(0), 1)
    engine.run_until_drained()
    # what the set-up moved is not the window's
    ctx["ttft"].mark()
    ctx["counted"] = (int(ctx["tokens"].value(service=SERVICE)), 0)
    ctx["counts_before"] = {key: c.value(service=SERVICE)
                            for key, c in ctx["counters"].items()}
    ctx["rows_before"] = tuple(
        ctx["prefill_rows"].value(service=SERVICE, ride=r)
        for r in ("decode", "alone"))
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


def last_window_start(prompt_len: int, doc_len: int, block_len: int,
                      chunk: int) -> int:
    """Where the last prefill window of a request began whose system
    prompt was in the index (:func:`window_rows`)."""
    return prompt_len - window_rows(prompt_len, doc_len, block_len,
                                    chunk)[-1]


def outputs_for_check(ctx: dict) -> dict:
    """What the window produced (the sampled requests' prompts as sent
    and tokens as served: the first and the last to finish, the longest
    turn, the rest drawn from the seed); drops the program's state, so
    that the reference has the device beside the weights."""
    stream, params = ctx["stream"], ctx["params"]
    doc_len = int(stream.doc_lens[0])
    samples = []
    for f in generate._check_samples(ctx):
        prompt, _ = stream.request(f["request"])
        samples.append({
            "request": f["request"], "prompt": prompt,
            "tokens": f["tokens"], "max_new": f["max_new"],
            "doc_len": doc_len,
            "window_start": last_window_start(
                len(prompt), doc_len, int(params["engine"]["block_len"]),
                int(params["prefill_chunk"]))})
    # the weights are the benchmark's own (made from the seed, handed to
    # the program, which only reads them): the reference takes the same
    # arrays and not a second draw of 7 GB; the engine and its pools go
    out = {"samples": samples, "weights": ctx.get("variables"),
           "finished": sum(f["in_window"] for f in ctx["finished"])}
    for key in ("engine", "variables"):
        ctx.pop(key, None)
    return out


def check(outputs: dict, cfg: dict, params: dict, seed: int,
          variant: str | None = None, weights: dict | None = None,
          kept: dict | None = None) -> list:
    """The comparison of ``references/qwen3_next.compare`` over the
    sampled requests; ``variant`` puts a control in the program's
    place."""
    pairs = generate._pairs(outputs)
    if any(len(served) == 0 for _, served in pairs) or not pairs:
        return [(name, ref.NOT_CORRECT, params["limits"][name])
                for name in ref.NUMBERS]
    samples = [(prompt, served, s["doc_len"], s["window_start"])
               for (prompt, served), s in zip(pairs, outputs["samples"])]
    details: dict = {}
    out = ref.compare(
        weights or outputs.get("weights") or ref.make_weights(cfg, seed),
        cfg, samples,
        params["limits"], variant=variant, details=details, kept=kept,
        # a sequence is padded to whole row blocks of the reference (a
        # few shapes of the attention layers), not to ``max_seq_len``:
        # most turns are a quarter of it
        pad={"pad_rows_to": int(params["inputs"]["output"]["max"])})
    print(json.dumps({"compared": {
        "variant": variant, "finished_in_window": outputs["finished"],
        "requests": [s["request"] for s in outputs["samples"]],
        "prompts": [len(s["prompt"]) for s in outputs["samples"]],
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
