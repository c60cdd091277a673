"""Rows of the held experts' buffer that hold a pair over the rows of the
tiles they lie in (live tiles x 256), over the traced steps and layers:
the program's own count of where its routers sent the pairs
(``moe_dropless.load_stats`` of the choice the step hands back).  A group
of ~320 rows fills a tile and a quarter."""


def read(ctx):
    fill = ctx.get("moe_tile_fill")
    return None if fill is None else 100.0 * fill
