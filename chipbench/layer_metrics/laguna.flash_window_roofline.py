"""What the BAND of 512 needs at the sliding rows' 72 query heads (12
FLOPs a pair a head dimension forward + backward, and the least bytes)
over the peaks, over the flash kernels' device time in the sliding-window
rows.  Counted by the pairs, not by the tiles the kernels run: an edge
that fills tiles with masked work reads as a lower share."""

from chipbench import laguna_reduce


def read(ctx):
    return laguna_reduce.flash_roofline_pct(ctx, "sliding_attention")
