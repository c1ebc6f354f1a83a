"""Driver of the long-reasoning cells: the closed loop of ``drivers/chat.py``
(callers over ``serving.llm.LLMEngine.submit/step``, one operation one
``generate.step`` boundary plus the refill) at the lengths of a reasoning
model's traffic: a short prompt, unshared, then an answer of thousands of
tokens run to its end, against a long and growing context.

A window of seconds sees a few hundred of an answer's thousands of tokens,
so the population it decodes is brought to mid-life in set-up: each
caller's FIRST request is sent at an age drawn uniformly over its own
answer, its prompt plus that many tokens of the answer (ids drawn as the
prompt's) prefilled in set-up — 1.3 M tokens at the cell's sizes, through
the chunked KDA kernel — and only the rest of the answer is left to
decode. A request that finishes is replaced by a fresh one, whose prompt
prefills riding with the decoding rows.

The configuration is Kimi-Linear's (``references/kimi_linear.py``): the
benchmark makes the weights from the seed and hands the program the SAME
arrays as its parameters (``dl.KimiLinearDecoder`` takes the reference's
tree as it is).

What it adds to a boundary's record is ``drivers/chat.py``'s: the ONE
program the boundary ran, as the rows it computed by position, and the
moves of the engine's counters (here ``moe_*``, ``kda_*``, the latent
positions the decoding rows read, ``kv_state_*``,
``gen_prefill_rows_total``). After a traced window it sums its own
kernels' seconds from the trace: the two KDA kernels, the paged latent
kernel and the expert products.
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

from benchmark import trace_reduce, traffic, traffic_requests
from benchmark.drivers import chat, generate
from benchmark.drivers import doc_qa as base
from benchmark.references import kimi_linear as ref

SERVICE = generate.SERVICE
PAD_ID = 0
COUNTERS = {"moe_held": "moe_pairs_held_total",
            "moe_absent": "moe_pairs_absent_total",
            "moe_touched": "moe_experts_touched_total",
            "kda_step_rows": "kda_step_rows_total",
            "kda_chunk_rows": "kda_chunk_rows_total",
            "kda_windows": "kda_windows_total",
            "kda_windows_carried": "kda_windows_carried_total",
            "latent_positions": "latent_decode_positions_total",
            "prefix_reused": "kv_prefix_tokens_reused_total",
            "state_restores": "kv_state_restores_total",
            "snapshot_evictions": "kv_state_snapshot_evictions_total"}
GAUGES = chat.GAUGES
#: the program's kernels by the names their events carry in a device trace
KERNELS = {"kda_step": "kda_step", "kda_chunk": "kda_chunk",
           "latent_attn": "paged_latent",
           "expert": "ragged-dot-gate-up|ragged-dot-down"}

step = chat.step
last_window_start = chat.last_window_start


def log_uniform_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths: the quantiles ``(i + 0.5) / n`` of a
    log-uniform over ``[min, max]``."""
    lo, hi = math.log(int(spec["min"])), math.log(int(spec["max"]))
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(lo + q * (hi - lo))).astype(np.int64)


def size_table(spec: dict) -> np.ndarray:
    """``[table, 2]`` of ``(prompt length, answer length)``, the same for
    every seed: ``traffic_requests.size_table``'s balanced rounds (a Latin
    square of the two lengths' g-tiles) over the quantiles of a clipped
    lognormal prompt and a log-uniform answer."""
    n = int(spec["table"])
    g = math.isqrt(n)
    if g * g != n:
        raise ValueError(f"table has to be a square number, not {n}")
    prompt = np.sort(traffic_requests.lognormal_quantiles(spec["prompt"], n))
    output = np.sort(log_uniform_quantiles(spec["output"], n))

    def rank(tile: int, r: int) -> int:
        return r if tile % 2 == 0 else g - 1 - r

    return np.asarray([(prompt[k * g + rank(k, r)],
                        output[((k + r) % g) * g + rank((k + r) % g, r)])
                       for r in range(g) for k in range(g)], np.int64)


class Stream(traffic_requests.RequestStream):
    """``traffic_requests.RequestStream``'s order over :func:`size_table`
    (each seed the same rounds in another order); requests ``0 .. aged -
    1`` (the callers' first) come AGED: ``age`` tokens of the answer,
    spread uniformly over ``[0, answer)`` (:meth:`age`), are part of the
    prompt sent, and ``max_new_tokens`` is what is left. No document:
    ``doc_lens`` is ``[0]`` for ``drivers/chat.py``'s bookkeeping."""

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.table = size_table(spec)
        self._orders: dict = {}
        self.aged = int(spec["aged"])
        self.doc_lens = [0]

    def age(self, k: int) -> int:
        """The share of request ``k``'s answer it is sent with: for a
        table row's first copy ``u`` of a stratified grid over ``[0, 1)``
        (the rows' places on it drawn from the seed), for its second copy
        ``1 - u``, so that every seed's aged population holds the same
        tokens and the seed orders the work without changing it."""
        if k >= self.aged:
            return 0
        _, answer = super().size(k)
        n = len(self.table)
        epoch, within = divmod(int(k), n)
        row = int(self._orders[epoch][within])
        place = np.random.default_rng([self.seed, 2]).permutation(n)[row]
        u = (place + 0.5) / n
        return int(answer * (u if epoch % 2 == 0 else 1.0 - u))

    def size(self, k: int) -> tuple:
        """``(0, prompt length as sent, max_new_tokens)``."""
        prompt_len, answer = super().size(k)
        age = self.age(k)
        return 0, prompt_len + age, answer - age

    def request(self, k: int) -> tuple:
        _, prompt_len, max_new = self.size(k)
        rng = np.random.default_rng([self.seed, 1, int(k)])
        prompt = rng.integers(int(self.spec["token_low"]),
                              int(self.spec["token_high"]),
                              size=prompt_len, dtype=np.int32)
        return prompt, max_new


def build_engine(cfg: dict, params: dict, weights: dict, registry):
    import jax.numpy as jnp
    from mmlspark_tpu.dl.kimi_linear_decoder import KimiLinearDecoder
    from mmlspark_tpu.serving.llm import LLMEngine

    module = KimiLinearDecoder(cfg, dtype=jnp.dtype(cfg["cache_dtype"]),
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
    # 4.6 GB of weights are made
    from mmlspark_tpu.dl import kimi_linear_decoder  # noqa: F401
    from mmlspark_tpu.obs.metrics import MetricsRegistry

    weights = ref.make_weights(cfg, seed)
    registry = MetricsRegistry()          # this run's counters alone
    engine = build_engine(cfg, params, weights, registry)
    metric = generate._metric
    return {
        "cfg": cfg, "params": params, "seed": seed, "engine": engine,
        "variables": weights, "stream": Stream(params["inputs"], seed),
        "tokens": metric(registry, "gen_tokens_total"),
        "decode_steps": metric(registry, "gen_decode_steps_total"),
        "ttft": base._Since(metric(registry, "gen_ttft_seconds")),
        "gather_bytes": metric(registry, "kv_dense_gather_bytes_total"),
        "blocks_used": metric(registry, "kv_blocks_used"),
        "prefill_rows": metric(registry, "gen_prefill_rows_total"),
        "next": 0, "live": {}, "finished": [], "stats": [],
        "prefilled_at": {}, "boundary": 0, "refill": True,
        "record": True, "stalled_boundaries": 0, "idle": 0,
        "counted": (0, 0), "awaiting": deque(), "started": {},
        "fed": set(),
        "counters": {key: metric(registry, name)
                     for key, name in COUNTERS.items()},
        "gauges": {key: metric(registry, name)
                   for key, name in GAUGES.items()},
        "counts_before": {key: 0.0 for key in COUNTERS},
        "rows_before": (0.0, 0.0)}


def window_lengths(chunk: int) -> tuple:
    """Suffix lengths whose windows are every program a request of any
    length can need, the same for every seed: alone, each bucket of the
    window ladder up to ``chunk`` with its head and ``chunk`` without one
    (a prompt of several chunks); riding, every multiple of 32 rows up to
    ``chunk``."""
    small = tuple(1 << i for i in range(5))
    return small + tuple(range(32, chunk + 1, 32)) + (2 * chunk,)


def warm(ctx: dict) -> None:
    """Compile the decode program, the state-row copy and every prefill
    window (:func:`window_lengths`); send every caller's first, aged,
    request and run the loop until all of them decode (their prompts,
    1.3 M tokens at the cell's sizes, are prefilled here) and
    ``warm_boundaries`` more have passed."""
    params, engine = ctx["params"], ctx["engine"]
    engine.warm(prefill_windows=window_lengths(int(params["prefill_chunk"])),
                mark_steady=False)
    for _ in range(int(params["callers"])):
        generate._submit_next(ctx)
    ctx["awaiting"].extend(range(ctx["next"]))
    callers = int(params["callers"])
    while len(ctx["started"]) < callers:
        step(ctx)
    for _ in range(int(params["warm_boundaries"])):
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


def _served(ctx: dict) -> list:
    """``(request, prompt as sent, tokens served)`` of every request that
    served a token in the window: those that finished there, and those
    still decoding at its end (the tokens that have come home)."""
    stream, engine = ctx["stream"], ctx["engine"]
    out = []
    for f in ctx["finished"]:
        if f["in_window"]:
            prompt, _ = stream.request(f["request"])
            out.append((f["request"], prompt, f["tokens"][len(prompt):]))
    for sid, meta in sorted(engine._meta.items()):
        if meta.first_token is None:
            continue
        served = np.asarray([meta.first_token] + list(meta.generated),
                            np.int32)
        out.append((sid, np.asarray(meta.prompt), served))
    return out


def outputs_for_check(ctx: dict) -> dict:
    """What the window produced: ``check_sequences`` of the requests that
    served tokens (finished or still decoding), drawn from the seed
    whatever their length, the two at the longest contexts among them —
    each prompt as sent and tokens as served, and where its last prefill
    window began; drops the program's state, so that the reference has
    the device beside the weights."""
    params = ctx["params"]
    served = _served(ctx)
    if not served:
        samples = []
    else:
        contexts = [len(p) + len(s) for _, p, s in served]
        longest = list(np.argsort(contexts, kind="stable")[-2:])
        picks = traffic.sample_rows(ctx["seed"], len(served),
                                    int(params["check_sequences"]), longest)
        samples = [{
            "request": served[i][0], "prompt": served[i][1],
            "served": served[i][2],
            "window_start": last_window_start(
                len(served[i][1]), 0, int(params["engine"]["block_len"]),
                int(params["prefill_chunk"]))} for i in picks]
    # the weights are the benchmark's own (made from the seed, handed to
    # the program, which only reads them): the reference takes the same
    # arrays; the engine and its pools go
    out = {"samples": samples, "weights": ctx.get("variables"),
           "finished": sum(f["in_window"] for f in ctx["finished"])}
    for key in ("engine", "variables"):
        ctx.pop(key, None)
    return out


def check(outputs: dict, cfg: dict, params: dict, seed: int,
          variant: str | None = None, weights: dict | None = None,
          kept: dict | None = None) -> list:
    """The comparison of ``references/kimi_linear.compare`` over the
    sampled requests; ``variant`` puts a control in the program's
    place."""
    samples = [(s["prompt"], s["served"], s["window_start"])
               for s in outputs["samples"]]
    if not samples or any(len(served) == 0 for _, served, _ in samples):
        return [(name, ref.NOT_CORRECT, params["limits"][name])
                for name in ref.NUMBERS]
    details: dict = {}
    out = ref.compare(
        weights or outputs.get("weights") or ref.make_weights(cfg, seed),
        cfg, samples, params["limits"], variant=variant, details=details,
        kept=kept, most=int(params["compared_rows"]),
        # every sequence's keys padded to ``max_seq_len``, so that the
        # reference's latent attention is compiled once
        pad={"pad_to": int(params["engine"]["max_seq_len"])})
    print(json.dumps({"compared": {
        "variant": variant, "finished_in_window": outputs["finished"],
        "requests": [s["request"] for s in outputs["samples"]],
        "prompts": [len(s["prompt"]) for s in outputs["samples"]],
        **details}}, default=str), file=sys.stderr)
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
    weights = outputs["weights"]
    out, kept = [], {}
    for variant in (None, "e4m3") + ref.FAULTS:
        out += [(f"{variant or 'program'}.{name}", value, limit)
                for name, value, limit
                in check(outputs, cfg, params, seed, variant, weights, kept)]
    return out
