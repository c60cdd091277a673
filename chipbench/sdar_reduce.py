"""Device time and tiles of the BLOCK-DIFFUSION attention rows, for the
``sdar.*`` per-layer readers.

A row of a table trained by block diffusion is traced under
``attn-blockdiff`` (``models/transformer.py``), so the program's
attribution has the row's time as ``within["attn-blockdiff"]``
(``mellum_reduce.within_ms``, the same table and join as every ``within``
reader), and the census each flash call under the scope published rides
in the compiled step (``ScopeTable.tiles_within``).  (The three flash
kernels' time and their share of the mask's roofline are the shared
``kernel.flash_ms`` / ``kernel.flash_roofline``: every flash call of
such a step is under the scope.)  Every function returns ``None`` where
its source is not there: a run without a trace, a program from before
the scope.
"""

from chipbench import flops_sdar_moe, mellum_reduce, weights_sdar_moe

FLASH = mellum_reduce.FLASH
SCOPE = "attn-blockdiff"


def within_ms(ctx, *owners):
    """Device ms a step of ``fwd-bwd`` under the block-diffusion rows'
    scope: of the named owners, or of every owner."""
    return mellum_reduce.within_ms(ctx, SCOPE, *owners)


def mask_tile_fill_pct(ctx):
    """The mask's pairs over the area of the tiles the three kernels
    visit to cover them, a head row: ``visited`` tiles x ``block_q`` x
    ``block_k`` of the census each flash call under the scope published
    (the grid of such a call IS the list of live tiles, so ``visited`` is
    ``live``; a grid that entered dead tiles would read lower here)."""
    found = getattr(ctx.get("scope_table"), "tiles_within", {}).get(
        SCOPE, {})
    tiles = [t for region in FLASH for t in found.get(region, ())]
    if not tiles:
        return None
    z = weights_sdar_moe.sizes(ctx["config"])
    pairs = flops_sdar_moe.attended_pairs(
        int(ctx["mix"]["seq_len"]), z["block"])
    ctx.setdefault("notes", {})["blockdiff_tiles"] = tiles
    return 100.0 * pairs * len(tiles) / sum(
        t["visited"] * t["block_q"] * t["block_k"] for t in tiles)
