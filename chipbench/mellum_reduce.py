"""Device time and tiles by KIND OF ATTENTION ROW, for the ``mellum.*``
per-layer readers that ask for them.

A windowed row's ops are traced under ``attn-window`` and a full row's
under ``attn-mixer`` (``models/transformer.py``), and the program's
attribution has, beside the owner reading, ``within``: each owner's
seconds under every scope name on its path
(``observability.device_trace.attribute``).  So the three flash kernels'
time under one row kind is ``within[<kind's scope>][flash-*]``, and a row
kind's whole mixer the sum of ``within[<kind's scope>]``.  The readers go
through ``scope_reduce.attribution(ctx)`` — the same table, the same 98%
join — and return ``None`` where that returns nothing or its result
carries no ``"within"`` (a program from before it).
"""

from chipbench import flops_mellum2, parts_reduce, scope_reduce, weights_mellum2

FLASH = scope_reduce.FLASH
#: layer_types' kinds -> the scope their rows are traced under.
SCOPE = {"sliding_attention": "attn-window", "full_attention": "attn-mixer"}


def within_ms(ctx, outer, *owners):
    """Device ms a step of ``fwd-bwd`` under the scope ``outer``: of the
    named owners, or of every owner."""
    rows = parts_reduce.rows(ctx)
    if rows is None or any("within" not in g for g in rows):
        return None
    under = [g["within"].get(outer, {}) for g in rows]
    if not any(under):
        return None
    return scope_reduce._ms_per_step(ctx, (
        sum(s for name, s in g.items() if not owners or name in owners)
        for g in under))


def flash_roofline_pct(ctx, kind):
    """Needed FLOPs and least bytes of the (query, key) pairs the rows of
    ``kind`` attend (``flops_mellum2.py``: the band's or the triangle's
    exact count) over the peaks, over the three flash kernels' device
    time under that kind's scope."""
    ms = within_ms(ctx, SCOPE[kind], *FLASH)
    if not ms:
        return None
    least, bound = flops_mellum2.flash_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"], kind)
    ctx.setdefault("notes", {})[f"flash_roofline_bound.{kind}"] = bound
    return 100.0 * least / (ms / 1e3)


def window_tile_fill_pct(ctx):
    """The band's pairs over the area of the tiles the three kernels run
    to cover them, a head row: ``live`` tiles x ``block_q`` x ``block_k``
    of the census each flash call of a windowed row published
    (``spans.tiles_scope``, read back off the compiled step as
    ``ScopeTable.tiles_within``).  ``visited`` there counts grid steps,
    those a kernel skips among them; ``live`` are the ones whose matmuls
    run."""
    table = ctx.get("scope_table")
    found = getattr(table, "tiles_within", {}).get(
        SCOPE["sliding_attention"], {})
    tiles = [t for region in FLASH for t in found.get(region, ())]
    if not tiles:
        return None
    z = weights_mellum2.sizes(ctx["config"])
    pairs = flops_mellum2.attended_pairs(
        int(ctx["mix"]["seq_len"]), z["window"])
    ctx.setdefault("notes", {})["window_tiles"] = tiles
    return 100.0 * pairs * len(tiles) / sum(
        t["live"] * t["block_q"] * t["block_k"] for t in tiles)
