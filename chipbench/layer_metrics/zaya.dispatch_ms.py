"""Device ms a step of the gather of the held experts' rows and their
weighted scatter back, both directions, in the ``zaya`` cell (a buffer
for every pair: the rank holds half the experts)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-dispatch")
