"""What the causal TRIANGLE needs (134,225,920 pairs a head row at S =
16,384) over the peaks, over the flash kernels' device time in the
full-attention row."""

from chipbench import mellum_reduce


def read(ctx):
    return mellum_reduce.flash_roofline_pct(ctx, "full_attention")
