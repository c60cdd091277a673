"""Device ms a step of the sparse-expert layers, from the router to the sum
of the routed and shared parts: ``moe-layer`` and the four regions nested
in it."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "moe-layer", "moe-route", "moe-dispatch", "moe-experts",
        "moe-shared")
