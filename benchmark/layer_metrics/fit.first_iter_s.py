"""Wall seconds of one warm 1-iteration fit on the cell's rows: binning,
upload and one tree, the fixed cost a user pays per fit."""


def read(ctx):
    return ctx["driver_ctx"].get("first_iter_s")
