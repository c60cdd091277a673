"""What the held experts' three matrices need over the rows routed to them
(18 d f FLOPs a held pair and the least HBM bytes, forward + backward,
``flops_ling3.py``; the rows the traced steps' own routers sent to the
held experts, which the step hands back) over the peaks, over
``moe-experts``' device time in those steps."""

from chipbench import flops_ling3, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(ctx, "moe-experts")
    if not ms:
        return None
    least, bound = flops_ling3.gmm_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"],
        ctx.get("moe_held_pairs"))
    ctx.setdefault("notes", {})["ling_gmm_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
