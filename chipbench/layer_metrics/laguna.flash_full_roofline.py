"""What the causal TRIANGLE needs at the full rows' 48 query heads over
the peaks, over the flash kernels' device time in the full-attention
rows."""

from chipbench import laguna_reduce


def read(ctx):
    return laguna_reduce.flash_roofline_pct(ctx, "full_attention")
