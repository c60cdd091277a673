"""What the held experts' matrices need over the rows routed to them
(FLOPs and least HBM bytes, forward + backward: ``gmm_roofline_seconds``
of the configuration's flops module, over the rows the traced steps' own
routers sent to the held experts, which the step hands back) over the
peaks, over ``moe-experts``' device time in those steps."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.roofline_pct(
        ctx, "gmm_roofline_seconds", "moe-experts",
        extra=(ctx.get("moe_held_pairs"),))
