"""What the BAND needs (16,253,440 pairs a head row at S = 16,384, window
1024: 12 FLOPs a pair a head dimension forward + backward, and the least
bytes) over the peaks, over the flash kernels' device time in the
sliding-window rows.  Counted by the pairs, not by the tiles the kernels
run: a block edge that fills tiles with masked work reads as a lower
share."""

from chipbench import mellum_reduce


def read(ctx):
    return mellum_reduce.flash_roofline_pct(ctx, "sliding_attention")
