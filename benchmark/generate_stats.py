"""What the generate cells' per-layer readers share: the window's
boundaries as the driver recorded them (``ctx["stats"]``: one entry a
boundary with its seconds, committed tokens, prefills and the engine's
counters), where every sequence stood at every boundary, and the needed
work that follows (``work_decoder``).

A sequence that finished at boundary ``f`` with ``n`` new tokens was
prefilled at ``f - (n - 2)`` (its first token and its first decode step
fall at one boundary; every later boundary commits one token) and fed the
decode program the token at position ``prompt_len + j`` at its ``j``-th
boundary. A traced run lets what is in flight finish after the window
(``drivers/generate.after_window``), so every sequence has its ``f``.
"""

from __future__ import annotations

import numpy as np

from benchmark import work_decoder


def window(ctx: dict) -> list:
    """The window's boundaries; nothing where the driver kept none."""
    return [s for s in ctx.get("stats", ()) if "prefilled" in s]


def decode_only(ctx: dict) -> list:
    return [s for s in window(ctx) if s["prefilled"] == 0]


def with_prefill(ctx: dict) -> list:
    return [s for s in window(ctx) if s["prefilled"] > 0]


def mean_ms(stats: list):
    if not stats:
        return None
    return 1e3 * sum(s["seconds"] for s in stats) / len(stats)


def sequences(ctx: dict) -> list:
    """``(prefill boundary, last boundary, prompt_len, new tokens)`` of
    every finished sequence the driver knows."""
    out = []
    for f in ctx["driver_ctx"].get("finished", ()):
        n = int(f["max_new"])
        out.append((int(f["boundary"]) - max(n - 2, 0), int(f["boundary"]),
                    int(f["prompt_len"]), n))
    return out


def needed(ctx: dict, boundaries: list) -> dict | None:
    """Needed FLOPs and HBM bytes of ``boundaries`` (a run of the
    window's): every prompt prefilled and every token decoded at them,
    one decode call a boundary and one prefill call a boundary that
    prefilled, each reading the weights once. ``kernel_bytes`` is what
    attention alone had to read of the cache: every cached position of
    every decoding sequence once a boundary, every prompt position
    once."""
    if not boundaries:
        return None
    cfg = ctx["cfg"]
    lo, hi = boundaries[0]["boundary"], boundaries[-1]["boundary"]
    flops = 0
    contexts = np.zeros(hi - lo + 1, np.int64)     # cached positions read
    prompts = np.zeros(hi - lo + 1, np.int64)      # prompt positions written
    for first, last, prompt_len, n in sequences(ctx):
        if lo <= first <= hi:
            flops += work_decoder.span_flops(cfg, 0, prompt_len, 1)
            prompts[first - lo] += prompt_len
        if n < 2:
            continue
        a, b = max(first, lo), min(last, hi)
        if a > b:
            continue
        # its j-th boundary feeds the token at prompt_len + j, which
        # attends prompt_len + j + 1 positions
        start, stop = prompt_len + a - first, prompt_len + b - first + 1
        flops += work_decoder.span_flops(cfg, start, stop, stop - start)
        contexts[a - lo:b - lo + 1] += np.arange(start, stop) + 1
    kv = work_decoder.kv_bytes_per_token(cfg)
    weights = work_decoder.weight_bytes(cfg)
    decode_calls = int(np.count_nonzero(contexts))
    prefill_calls = int(np.count_nonzero(prompts))
    kernel_bytes = kv * int(contexts.sum() + prompts.sum())
    return {"flops": int(flops), "kernel_bytes": kernel_bytes,
            "bytes": kernel_bytes + weights * (decode_calls + prefill_calls),
            "seconds": sum(s["seconds"] for s in boundaries)}


def traced_boundaries(ctx: dict) -> list:
    """The boundaries the profiler's stretch covered: ``run.measure``
    starts it before the window's second operation and stops it after
    the first operation that ends ``TRACE_SECONDS`` or more later."""
    tr = ctx.get("trace")
    stats = window(ctx)[1:]
    if not tr or not stats:
        return []
    out, total = [], 0.0
    for s in stats:
        if total + s["seconds"] > tr["window_s"] + 1e-3:
            break
        total += s["seconds"]
        out.append(s)
    return out
