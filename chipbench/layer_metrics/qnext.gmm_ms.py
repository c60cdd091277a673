"""Device ms a step of the held experts' grouped matmuls (three products
forward, six backward, 32 groups at K = 2048, N = 512), with the weights'
rounding to the compute type and the gate's product between them."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-experts")
