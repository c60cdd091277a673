"""What the recurrence needs (FLOPs and least HBM bytes, forward +
backward, as ``kernel.ssd_roofline`` counts them, at this family's sizes:
eight B/C groups, chunk 128) over the peaks, over ``ssd-scan``'s device
time in the ``nemotron_h`` cell."""

from chipbench import flops_nemotron, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(ctx, "ssd-scan")
    if not ms:
        return None
    least, bound = flops_nemotron.ssd_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["ssd_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
