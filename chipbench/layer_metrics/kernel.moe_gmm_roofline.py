"""What the held experts' two matrices need over the rows routed to them
(FLOPs and least HBM bytes, forward + backward, ``flops_nemotron.py``;
the rows the traced steps' own routers sent to the held experts, which
the step hands back) over the peaks, over ``moe-experts``' device time in
those steps."""

from chipbench import flops_nemotron, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(ctx, "moe-experts")
    if not ms:
        return None
    least, bound = flops_nemotron.gmm_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"],
        ctx.get("moe_held_pairs"))
    ctx.setdefault("notes", {})["moe_gmm_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
