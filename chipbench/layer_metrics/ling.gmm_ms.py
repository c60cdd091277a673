"""Device ms a step of the held experts' grouped matmuls (three products
forward, six backward, 8 groups at K = 2560, N = 768), with the weights'
rounding to the compute type and the gate's product between them."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-experts")
