"""Device ms a step of the gather of the held experts' rows and their
weighted sum back, both directions, at ten experts a token (a buffer of
192 tiles for 32 groups)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-dispatch")
