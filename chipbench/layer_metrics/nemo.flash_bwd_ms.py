"""Device ms a step of the two flash backward kernels (dq, dk/dv) in
the ``nemotron_h`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "flash-bwd-dq", "flash-bwd-dkv")
