"""Device ms a step of the shared experts and their sigmoid gates (every
token, three dense matrices of 2048 x 512 and one 2048-vector)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "moe-shared")
