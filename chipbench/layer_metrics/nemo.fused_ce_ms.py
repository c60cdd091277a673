"""Device ms a step of the fused cross-entropy scans over the untied head
in the ``nemotron_h`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "fused-ce")
