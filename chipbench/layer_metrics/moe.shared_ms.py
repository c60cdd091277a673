"""Device ms a step of the shared experts (every token, two dense
matrices)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-shared")
