"""Device ms a step of the three flash kernels (GQA 32/8, D = 64,
S = 8192 in the hybrid cell)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
