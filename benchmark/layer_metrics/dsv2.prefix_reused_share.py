"""Prompt tokens served from the prefix index over prompt tokens sent, of
the requests prefilled in the window: ``kv_prefix_tokens_reused_total``
against their prompts as the driver sent them (a request's document is in
the index; its suffix is not)."""


def read(ctx):
    stats = [s for s in ctx["stats"] if "prefix_reused" in s]
    sent = sum(s["prompt_tokens"] for s in stats)
    if not ctx["on_chip"] or sent <= 0:
        return None
    return 100.0 * sum(s["prefix_reused"] for s in stats) / sent
