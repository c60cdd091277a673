"""Device ms a step owned by the residual's multiplier and add
(``residual``); 0 where every one of them is fused into a neighbour."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "residual")
