"""Device ms a step of the softmax routers: the 2304 x 64 float32 matmul,
softmax, top-8, the sort of the 131,072 pairs by expert."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-route")
