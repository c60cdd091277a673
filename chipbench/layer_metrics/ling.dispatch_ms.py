"""Device ms a step of the gather of the held experts' rows and their
weighted sum back, both directions, at eight experts a token of which
one in 64 is held (a buffer of 8,192 rows)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-dispatch")
