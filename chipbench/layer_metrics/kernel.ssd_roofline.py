"""What the state-space RECURRENCE needs (FLOPs and least HBM bytes,
forward + backward: ``ssd_roofline_seconds`` of the configuration's flops
module) over the peaks, over ``ssd-scan``'s device time."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.roofline_pct(ctx, "ssd_roofline_seconds", "ssd-scan")
