"""What the chat cells' per-layer readers share: the window's boundaries as
``drivers/chat.py`` recorded them — each with the ONE program it ran: the
decoding rows and the prefill window that rode with them, by position
(``calls``), and the engine's counters' moves — the needed work that follows
(``work_gated_delta_moe``), and the kernels' seconds the driver summed from
the trace.

A boundary is reckoned as ``serving/llm.py`` makes it from PR 34 on: one
program, the weights once; ``decode_only`` means no prefill row rode
(``gen_prefill_rows_total``)."""

from __future__ import annotations

from benchmark import (docqa_stats, generate_stats, sala_stats,
                       work_gated_delta_moe)

traced_boundaries = generate_stats.traced_boundaries
accepted_reader = docqa_stats.accepted_reader
# the seconds the driver summed from the trace a kernel, and their share
# of the device's busy time: the long-document cells' readings as they are
kernel_seconds = sala_stats.kernel_seconds
kernel_share = sala_stats.kernel_share
KERNELS = ("gdn_step", "gdn_chunk", "gqa_attn")


def window(ctx: dict) -> list:
    """The window's boundaries that carry this driver's record; nothing
    where the driver kept none."""
    return [s for s in ctx.get("stats", ()) if "calls" in s]


def decode_only(ctx: dict) -> list:
    return [s for s in window(ctx) if s["ride_rows"] == 0]


def needed(ctx: dict, boundaries: list) -> dict | None:
    """Needed FLOPs and bytes of ``boundaries`` (a run of the window's):
    the whole step's, and each kernel's under ``<kernel>_flops`` /
    ``<kernel>_bytes``."""
    boundaries = [s for s in boundaries if "calls" in s]
    if not boundaries:
        return None
    cfg = ctx["cfg"]
    total = {"flops": 0, "bytes": 0,
             "seconds": sum(s["seconds"] for s in boundaries)}
    for key in KERNELS:
        total[f"{key}_flops"] = total[f"{key}_bytes"] = 0
    for s in boundaries:
        call = [(0, s["doc_len"], int(first), int(rows))
                for first, rows in s["calls"]]
        if not call:
            continue
        step = work_gated_delta_moe.step_work(
            cfg, call, held_pairs=s["moe_held"],
            experts_touched=s["moe_touched"], logit_rows=s["logit_rows"])
        total["flops"] += step["flops"]
        total["bytes"] += step["bytes"]
        for key in KERNELS:
            total[f"{key}_flops"] += step["kernels"][key]["flops"]
            total[f"{key}_bytes"] += step["kernels"][key]["bytes"]
    return total


def roofline(ctx: dict, key: str, *, flops: bool = True):
    """A kernel against its roofline: the least time the chip could take
    for its calls in the traced stretch — the larger of their needed
    FLOPs over the MXU's peak (where ``flops``) and their needed bytes
    over HBM's — over the kernel's seconds in the trace, in percent."""
    seconds = kernel_seconds(ctx, key)
    need = needed(ctx, traced_boundaries(ctx)) if seconds else None
    if not need or need[f"{key}_bytes"] <= 0:
        return None
    least = need[f"{key}_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    if flops:
        least = max(least, need[f"{key}_flops"]
                    / ctx["peaks"]["flops_per_s_bf16"])
    return 100.0 * least / seconds


def whole_step_share(ctx: dict, what: str, peak: str):
    """The window's needed ``what`` (``flops`` | ``bytes``) over its
    seconds times the published ``peak``, in percent."""
    if not ctx["on_chip"]:
        return None
    need = needed(ctx, window(ctx))
    if not need or need[what] <= 0:
        return None
    return 100.0 * need[what] / (ctx["elapsed_s"] * ctx["peaks"][peak])
