"""Device ms a step of the fused cross-entropy scans over the tied table's
32,784-row slice in the ``zaya`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "fused-ce")
