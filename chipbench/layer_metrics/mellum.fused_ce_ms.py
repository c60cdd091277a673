"""Device ms a step of the fused cross-entropy scans over the untied head's
12,288-row slice in the ``mellum`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "fused-ce")
