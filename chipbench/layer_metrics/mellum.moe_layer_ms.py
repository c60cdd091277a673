"""Device ms a step of the ``mellum`` cell's four sparse-expert branches,
from the router to the weighted sum of the routed parts: ``moe-layer`` and
the regions nested in it (no shared expert)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "moe-layer", "moe-route", "moe-dispatch", "moe-experts")
