"""Device ms a step of the fused cross-entropy scans over the untied head's
19,648-row slice in the ``ling3flash`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "fused-ce")
