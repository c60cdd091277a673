"""Device ms a step of the ``zaya`` cell's sparse-expert branches, from the
router to the weighted sum: ``moe-layer`` and the regions nested in it
(no shared expert here)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "moe-layer", "moe-route", "moe-dispatch", "moe-experts")
