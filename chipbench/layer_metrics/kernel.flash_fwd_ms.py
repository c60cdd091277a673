"""Device ms a step of the flash forward kernel."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "flash-fwd")
