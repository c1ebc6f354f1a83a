"""The paged latent attention kernel against its roofline: the least time
the chip could take for its calls in the traced stretch, which is the
larger of their needed FLOPs over the MXU's peak and their needed bytes
over HBM's (``work_mla_moe.kernel_work``; at 242 FLOP a cached byte the
two lie together), over the kernel's seconds in the trace."""

from benchmark import docqa_stats


def read(ctx):
    tr = ctx["trace"]
    if not ctx["on_chip"] or not tr or not tr.get("kernel_calls") \
            or not tr.get("kernel_s"):
        return None
    need = docqa_stats.needed(ctx, docqa_stats.traced_boundaries(ctx))
    if not need or need["kernel_bytes"] <= 0:
        return None
    least = max(need["kernel_flops"] / ctx["peaks"]["flops_per_s_bf16"],
                need["kernel_bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / tr["kernel_s"]
