"""Device ms a step of the chunked state-space scan, forward and backward."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "ssd-scan")
