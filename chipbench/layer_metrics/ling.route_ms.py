"""Device ms a step of the group-limited sigmoid routers: the 2560 x 512
float32 matmul, sigmoid, the groups' two best and the four kept, top-8,
the sort of the 131,072 pairs by expert."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-route")
