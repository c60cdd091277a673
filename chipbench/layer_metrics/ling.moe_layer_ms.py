"""Device ms a step of the ``ling3flash`` cell's four sparse-expert
branches, from the router to the sum of the routed and the shared parts:
``moe-layer`` and the regions nested in it."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "moe-layer", "moe-route", "moe-dispatch", "moe-experts",
        "moe-shared")
