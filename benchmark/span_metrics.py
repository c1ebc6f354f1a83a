"""What the per-layer readers of the program's spans share. The spans
come from the tracer's always-on ring (``mmlspark_tpu.obs.tracer.recent``);
a program without the ring, or a window whose spans the ring does not
hold whole, gives nothing to read."""

from __future__ import annotations


def window_trees(ctx: dict, root_name: str):
    """``[(root, children)]`` for the window's operations: the last
    ``ctx["operations"]`` finished spans called ``root_name`` (the
    warm-ups lie before them) and each one's children by ``parent_id``.
    Nothing when the ring has dropped part of the window: fewer roots
    than operations, or a full ring whose oldest span ended after the
    oldest root began (a child of that root may be gone)."""
    from mmlspark_tpu.obs import tracer, tracing
    recent = getattr(tracer, "recent", None)
    want = int(ctx.get("operations") or 0)
    if recent is None or not want:
        return []
    spans = recent()
    roots = [s for s in spans if s.name == root_name][-want:]
    if len(roots) < want or (len(spans) >= tracing.RING_SIZE
                             and spans[0].end_ns >= roots[0].start_ns):
        return []
    children: dict = {r.span_id: [] for r in roots}
    for span in spans:
        if span.parent_id in children:
            children[span.parent_id].append(span)
    return [(r, children[r.span_id]) for r in roots]


def mean_child_ms(ctx: dict, root_name: str, child_name: str):
    """Mean milliseconds of one ``child_name`` span under the window's
    ``root_name`` spans; nothing when there is none."""
    seconds = [c.seconds for _, kids in window_trees(ctx, root_name)
               for c in kids if c.name == child_name]
    return 1e3 * sum(seconds) / len(seconds) if seconds else None
