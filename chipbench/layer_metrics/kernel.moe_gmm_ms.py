"""Device ms a step of the held experts' grouped matmuls, forward (and
the forward recomputed in backward) and both backward products, with the
weights' rounding to the compute type and the squared ReLU between them."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-experts")
