"""Device ms a step of the held experts' grouped matmuls in the ``zaya``
cell (three products forward, six backward, K = N = 2048), with the
weights' rounding to the compute type and the gate's product between them."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-experts")
