"""What causal attention needs at the cell's heads and widths (FLOPs and
least HBM bytes, forward + backward: ``flash_roofline_seconds`` of the
configuration's flops module) over the peaks, over the three flash
kernels' device time."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.roofline_pct(
        ctx, "flash_roofline_seconds", *scope_reduce.FLASH)
