"""Device ms a step of the gather of the held experts' rows and their
weighted scatter back, both directions."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-dispatch")
