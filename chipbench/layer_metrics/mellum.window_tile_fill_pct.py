"""The band's pairs over the area of the tiles the flash kernels run to
cover them in a sliding-window row (``mellum_reduce``: from the census
the windowed calls published)."""

from chipbench import mellum_reduce


def read(ctx):
    return mellum_reduce.window_tile_fill_pct(ctx)
