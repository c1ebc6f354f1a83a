"""State snapshots restored over requests prefilled in the window
(``kv_state_restores_total`` against the boundaries' prefills): 1 where
every request found its document's snapshot, less where one was evicted
and the document was prefilled again."""


def read(ctx):
    stats = [s for s in ctx["stats"] if "state_restores" in s]
    prefilled = sum(s["prefilled"] for s in stats)
    if not ctx["on_chip"] or prefilled <= 0:
        return None
    return sum(s["state_restores"] for s in stats) / prefilled
