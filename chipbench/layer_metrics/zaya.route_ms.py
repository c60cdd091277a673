"""Device ms a step of the MLP routers in the ``zaya`` cell: the
down-projection, the state carried from the layer before, the norm, the
three matrices, softmax, top-1, the sort of the pairs by expert."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-route")
