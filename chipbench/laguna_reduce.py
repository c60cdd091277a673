"""Device time and tiles by KIND OF ATTENTION ROW, for the ``laguna.*``
per-layer readers.

A windowed row's ops are traced under ``attn-window`` and a full row's
under ``attn-mixer`` (``models/transformer.py``), as the ``mellum``
rows are, so the readers are ``mellum_reduce.within_ms`` (the same
table, the same join) over this family's counts (``flops_laguna.py``: a
row kind's pairs at ITS OWN query heads).  The gate a head — its
projection ``x W_g``, the sigmoid and the product with the head's
output — is traced under ``mixer-gate`` inside either scope.  Every
function returns ``None`` where its source is not there: a run without a
trace, a program from before the scopes.
"""

from chipbench import flops_laguna, mellum_reduce, weights_laguna

FLASH = mellum_reduce.FLASH
SCOPE = mellum_reduce.SCOPE
within_ms = mellum_reduce.within_ms


def flash_roofline_pct(ctx, kind):
    """Needed FLOPs and least bytes of the (query, key) pairs the rows of
    ``kind`` attend at their own heads (``flops_laguna.py``) over the
    peaks, over the flash kernels' device time under that kind's
    scope."""
    ms = within_ms(ctx, SCOPE[kind], *FLASH)
    if not ms:
        return None
    least, bound = flops_laguna.flash_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"], kind)
    ctx.setdefault("notes", {})[f"flash_roofline_bound.{kind}"] = bound
    return 100.0 * least / (ms / 1e3)


def head_gate_ms(ctx):
    """Device ms a step the gate a head owns in the attention rows of
    both kinds (``mixer-gate`` within either scope)."""
    found = [within_ms(ctx, scope, "mixer-gate")
             for scope in SCOPE.values()]
    if all(ms is None for ms in found):
        return None
    return sum(ms or 0.0 for ms in found)


def window_tile_fill_pct(ctx):
    """The band's pairs over the area of the tiles the flash kernels run
    to cover them, a head row: ``live`` tiles x ``block_q`` x ``block_k``
    of the census each flash call of a windowed row published
    (``spans.tiles_scope``, read back off the compiled step as
    ``ScopeTable.tiles_within``); ``window / (edge + window)`` in the
    limit of a long row."""
    found = getattr(ctx.get("scope_table"), "tiles_within", {}).get(
        SCOPE["sliding_attention"], {})
    tiles = [t for region in FLASH for t in found.get(region, ())]
    if not tiles:
        return None
    pairs = flops_laguna.attended_pairs(
        int(ctx["mix"]["seq_len"]),
        weights_laguna.sizes(ctx["config"])["window"])
    ctx.setdefault("notes", {})["window_tiles"] = tiles
    return 100.0 * pairs * len(tiles) / sum(
        t["live"] * t["block_q"] * t["block_k"] for t in tiles)
