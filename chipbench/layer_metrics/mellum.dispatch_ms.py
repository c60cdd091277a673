"""Device ms a step of the gather of the held experts' rows and their
weighted sum back, both directions, at eight experts a token (a buffer of
65,536 rows for 8 groups of ~2,048)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-dispatch")
