"""What the recurrence needs (FLOPs and least HBM bytes, forward +
backward, ``flops_hybrid.py``) over the peaks, over ``ssd-scan``'s device
time."""

from chipbench import flops_hybrid, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(ctx, "ssd-scan")
    if not ms:
        return None
    least, bound = flops_hybrid.ssd_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["ssd_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
