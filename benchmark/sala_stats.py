"""What the long-document cells' per-layer readers share: the window's
boundaries as ``drivers/long_doc_qa.py`` recorded them, the program calls
each boundary made (``docqa_stats.requests`` / ``calls``: from the
boundary at which each request was prefilled), the needed work that
follows (``work_sparse_linear``), and the kernels' seconds the driver
summed from the trace."""

from __future__ import annotations

from benchmark import docqa_stats, work_sparse_linear

window = docqa_stats.window
decode_only = docqa_stats.decode_only
traced_boundaries = docqa_stats.traced_boundaries
accepted_reader = docqa_stats.accepted_reader
KERNELS = ("sparse_attn", "sparse_select", "lightning")


def calls(ctx: dict, boundary: int, reqs: list) -> list:
    """``[(call, rows of logits)]`` of one boundary: the decode call (a
    row of logits a sequence), then the prefill calls as
    ``docqa_stats.calls`` lays them out (``prefill_batch`` requests share
    a call, a suffix wider than ``prefill_chunk`` is fed in several),
    each with a row of logits for every prompt that ends in it."""
    decode, _ = docqa_stats.calls(ctx, boundary, reqs)
    out = [(decode, len(decode))] if decode else []
    starting = [(doc, doc_len, doc_len, prompt_len - doc_len)
                for b0, doc, doc_len, prompt_len, _ in reqs
                if b0 == boundary]
    batch = int(ctx["params"]["engine"]["prefill_batch"])
    chunk = int(ctx["params"]["prefill_chunk"])
    for i in range(0, len(starting), batch):
        group = starting[i:i + batch]
        for lo in range(0, max(r for _, _, _, r in group), chunk):
            call = [(doc, doc_len, first + lo, min(rows - lo, chunk))
                    for doc, doc_len, first, rows in group if rows > lo]
            out.append((call, sum(lo < rows <= lo + chunk
                                  for _, _, _, rows in group)))
    return out


def needed(ctx: dict, boundaries: list) -> dict | None:
    """Needed FLOPs and bytes of ``boundaries`` (a run of the window's):
    the whole step's, and each kernel's under ``<kernel>_flops`` /
    ``<kernel>_bytes``."""
    if not boundaries or "started" not in ctx["driver_ctx"]:
        return None
    cfg = ctx["cfg"]
    reqs = docqa_stats.requests(ctx)
    total = {"flops": 0, "bytes": 0,
             "seconds": sum(s["seconds"] for s in boundaries)}
    for key in KERNELS:
        total[f"{key}_flops"] = total[f"{key}_bytes"] = 0
    for s in boundaries:
        for call, logit_rows in calls(ctx, s["boundary"], reqs):
            step = work_sparse_linear.step_work(cfg, call,
                                                logit_rows=logit_rows)
            total["flops"] += step["flops"]
            total["bytes"] += step["bytes"]
            for key in KERNELS:
                total[f"{key}_flops"] += step["kernels"][key]["flops"]
                total[f"{key}_bytes"] += step["kernels"][key]["bytes"]
    return total


def kernel_seconds(ctx: dict, *keys: str):
    """Seconds of the driver's kernels ``keys`` in the traced stretch;
    nothing where the driver read no trace or the kernels never ran."""
    kernels = ctx["driver_ctx"].get("kernels") or {}
    if not ctx["on_chip"] or not all(k in kernels for k in keys):
        return None
    seconds = sum(kernels[k]["seconds"] for k in keys)
    return seconds if seconds > 0 else None


def kernel_share(ctx: dict, *keys: str):
    """The kernels' seconds over the device's busy seconds, in percent."""
    tr, seconds = ctx["trace"], kernel_seconds(ctx, *keys)
    if not tr or tr["busy_s"] <= 0 or seconds is None:
        return None
    return 100.0 * seconds / tr["busy_s"]


def hbm_roofline(ctx: dict, work_key: str, *keys: str):
    """A kernel against HBM's roof: the least time the chip could take to
    move the bytes its calls in the traced stretch need, over its seconds
    in the trace, in percent."""
    seconds = kernel_seconds(ctx, *keys)
    need = needed(ctx, traced_boundaries(ctx)) if seconds else None
    if not need or need[f"{work_key}_bytes"] <= 0:
        return None
    return 100.0 * need[f"{work_key}_bytes"] \
        / ctx["peaks"]["hbm_bytes_per_s"] / seconds
