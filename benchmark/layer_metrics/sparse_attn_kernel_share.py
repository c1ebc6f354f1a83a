"""Share of the device's busy time in the traced stretch that the
block-sparse attention kernel's events take (events matched by the
workload file's ``kernel_pattern``), prefill's and decode's calls
together."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("kernel_calls") or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["kernel_s"] / tr["busy_s"]
