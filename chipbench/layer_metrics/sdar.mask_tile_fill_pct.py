"""The mask's pairs over the area of the tiles the flash kernels visit to
cover them (``sdar_reduce``: from the census the calls under
``attn-blockdiff`` published)."""

from chipbench import sdar_reduce


def read(ctx):
    return sdar_reduce.mask_tile_fill_pct(ctx)
