"""Device ms a step of the routers: matmul, sigmoid, top-k and the sort of
the (token, choice) pairs by expert, forward and backward."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-route")
