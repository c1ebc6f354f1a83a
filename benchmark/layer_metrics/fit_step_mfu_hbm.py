"""Whole boosting iteration against the HBM floor: the bytes an
iteration cannot avoid (``work.gbdt_iteration_min_bytes``) over the
published bandwidth, divided by the measured seconds per iteration (fit
time only, scoring left out)."""


def read(ctx):
    stats = [s for s in ctx["stats"] if "fit_s" in s]
    if not ctx["on_chip"] or not stats:
        return None
    inputs = ctx["params"]["inputs"]
    need = ctx["work"].gbdt_iteration_min_bytes(inputs["rows"],
                                                inputs["features"])
    per_iter = sum(s["fit_s"] for s in stats) / (
        len(stats) * ctx["params"]["iterations"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / per_iter
