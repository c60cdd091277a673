"""Device ms a step of the chunked state-space scan (eight B/C groups,
chunk 128) in the ``nemotron_h`` cell, forward and backward."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "ssd-scan")
