"""What the readers of a boundary's host work share. A
boundary of ``LLMEngine`` is an ``llm.step`` span on the tracer's ring
(``mmlspark_tpu.obs.tracer.recent``) that says, as ``steps``, the
engine's ``gen_decode_steps_total`` as the boundary left it; the driver
records the same counter a boundary as ``decode_steps_total``, so the
ring's roots lie against ``ctx["stats"]`` and the warm-up before the
window and the drain after it fall away. The ring holds 4,096 spans and
a window of a thousand boundaries overruns it: the readers take the
boundaries the ring still holds WHOLE, if they are enough."""

from __future__ import annotations

import statistics

ENOUGH = 100        # boundaries a median is taken over, at the least


def boundaries(ctx: dict) -> list:
    """``[(seconds of llm.step, seconds of the llm.fetch spans beneath
    it)]`` for the window's boundaries the ring holds whole; a fetch lies
    under the boundary's ``llm.decode``, or under the root where the
    boundary first brought home what still flew. Nothing where the
    program's spans say no ``steps`` (the parent commit), where the
    driver recorded no boundary, or where the ring holds fewer than
    ``ENOUGH`` of the window's boundaries and not all of them."""
    from mmlspark_tpu.obs import tracer, tracing
    counted = [s["decode_steps_total"] for s in ctx.get("stats") or []
               if "decode_steps_total" in s]
    if not counted:
        return []
    spans = tracer.recent()
    # a full ring has dropped spans: a root that began before the oldest
    # one left ended may have lost a child
    cut = spans[0].end_ns if len(spans) >= tracing.RING_SIZE else -1
    fetched = {s.span_id: 0.0 for s in spans
               if s.name == "llm.step" and s.start_ns > cut
               and counted[0] <= s.attrs.get("steps", -1) <= counted[-1]}
    if len(fetched) < min(ENOUGH, len(counted)):
        return []
    under = {s.span_id: s.parent_id for s in spans
             if s.name == "llm.decode" and s.parent_id in fetched}
    for s in spans:
        root = under.get(s.parent_id, s.parent_id)
        if s.name == "llm.fetch" and root in fetched:
            fetched[root] += s.seconds
    return [(s.seconds, fetched[s.span_id]) for s in spans
            if s.span_id in fetched]


def host_ms(ctx: dict):
    """Median milliseconds of a boundary less its fetch: the host's own
    work a boundary (commit, handoff, finish, admission, allocation, the
    window, the block tables, the dispatch); what is left of the
    boundary, which ``*.decode_step_ms`` reads, is the host's slack.
    Nothing where there is nothing to read, and never 0."""
    hosts = [step - fetch for step, fetch in boundaries(ctx)]
    if not hosts:
        return None
    return 1e3 * statistics.median(hosts) or None
