"""What the document question-answering cells' per-layer readers share:
the window's boundaries as ``drivers/doc_qa.py`` recorded them, which
program calls each boundary made (from ``started``: the boundary at which
each request was prefilled), and the needed work that follows
(``work_mla_moe``).

A request prefilled at boundary ``b0`` asking for ``n`` new tokens feeds
one prefill of its suffix at ``b0`` (its document is in the index) and the
decode program at boundaries ``b0 .. b0 + n - 2``, its ``j``-th decode row
at position ``prompt_len + j``.
"""

from __future__ import annotations

from benchmark import generate_stats, work_mla_moe

window = generate_stats.window
decode_only = generate_stats.decode_only
traced_boundaries = generate_stats.traced_boundaries


def accepted_reader(name: str):
    """``read`` of ``layer_metrics/<name>.py``, for a metric of these
    cells that reads what an accepted cell's reader reads (the driver
    records every boundary through ``generate.step``, so the entries
    hold the same keys), under a name of its own."""
    from benchmark import run
    return run._load_module("layer_metrics", name).read


def requests(ctx: dict) -> list:
    """``(prefill boundary, document, document length, prompt length,
    new tokens)`` of every request the driver saw prefilled."""
    drv = ctx["driver_ctx"]
    stream = drv["stream"]
    out = []
    for k, b0 in drv["started"].items():
        doc, suffix_len, max_new = stream.size(k)
        doc_len = stream.doc_lens[doc]
        out.append((b0, doc, doc_len, doc_len + suffix_len, max_new))
    return out


def calls(ctx: dict, boundary: int, reqs: list) -> tuple:
    """``(the decode call, the prefill calls)`` of one boundary, as
    ``work_mla_moe`` takes them; ``prefill_batch`` requests share a
    prefill call, and a suffix wider than ``prefill_chunk`` is fed in
    several."""
    decode, starting = [], []
    for b0, doc, doc_len, prompt_len, n in reqs:
        if b0 == boundary:
            starting.append((doc, doc_len, doc_len, prompt_len - doc_len))
        if n >= 2 and b0 <= boundary <= b0 + n - 2:
            decode.append((doc, doc_len, prompt_len + boundary - b0, 1))
    batch = int(ctx["params"]["engine"]["prefill_batch"])
    chunk = int(ctx["params"]["prefill_chunk"])
    prefills = []
    for i in range(0, len(starting), batch):
        group = starting[i:i + batch]
        for lo in range(0, max(r for _, _, _, r in group), chunk):
            prefills.append([(doc, doc_len, first + lo, min(rows - lo, chunk))
                             for doc, doc_len, first, rows in group
                             if rows > lo])
    return decode, prefills


def needed(ctx: dict, boundaries: list) -> dict | None:
    """Needed FLOPs and bytes of ``boundaries`` (a run of the window's):
    the whole step's and the latent attention kernel's."""
    if not boundaries:
        return None
    cfg = ctx["cfg"]
    reqs = requests(ctx)
    total = {"flops": 0, "bytes": 0, "kernel_flops": 0, "kernel_bytes": 0,
             "seconds": sum(s["seconds"] for s in boundaries)}
    for s in boundaries:
        decode, prefills = calls(ctx, s["boundary"], reqs)
        every = ([decode] if decode else []) + prefills
        if not every:
            continue
        rows = sum(r for call in every for _, _, _, r in call)
        for call in every:
            share = sum(r for _, _, _, r in call) / rows
            # the boundary's expert counts are of all its calls: a call
            # takes its rows' share
            step = work_mla_moe.step_work(
                cfg, call, held_pairs=round(s["moe_held"] * share),
                experts_touched=round(s["moe_touched"] * share),
                logit_rows=len(call))
            kernel = work_mla_moe.kernel_work(cfg, call)
            total["flops"] += step["flops"]
            total["bytes"] += step["bytes"]
            total["kernel_flops"] += kernel["flops"]
            total["kernel_bytes"] += kernel["bytes"]
    return total
