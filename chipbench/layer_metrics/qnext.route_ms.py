"""Device ms a step of the softmax routers: the 2048 x 512 float32 matmul,
softmax, top-10, the sort of the 163,840 pairs by expert."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-route")
