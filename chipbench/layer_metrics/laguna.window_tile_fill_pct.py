"""The band's pairs over the area of the tiles the flash kernels run to
cover them in a sliding-window row (``laguna_reduce``: from the census
the windowed calls published; 512 / (edge + 512) for a long row)."""

from chipbench import laguna_reduce


def read(ctx):
    return laguna_reduce.window_tile_fill_pct(ctx)
