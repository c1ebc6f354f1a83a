"""The paged attention kernel against the HBM roof: the cache bytes its
calls in the traced stretch had to read (every cached position of every
decoding sequence once a boundary, every prompt position once; from the
driver's own record of where each sequence stood) over the kernel's
seconds in the trace times the published bandwidth."""

from benchmark import generate_stats


def read(ctx):
    tr = ctx["trace"]
    if not ctx["on_chip"] or not tr or not tr.get("kernel_calls") \
            or not tr.get("kernel_s"):
        return None
    need = generate_stats.needed(ctx, generate_stats.traced_boundaries(ctx))
    if not need or need["kernel_bytes"] <= 0:
        return None
    return 100.0 * need["kernel_bytes"] / (
        tr["kernel_s"] * ctx["peaks"]["hbm_bytes_per_s"])
