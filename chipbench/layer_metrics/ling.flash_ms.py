"""Device ms a step of the three flash kernels (32 heads, scores over 192,
values of 128, S = 16,384, one layer) in the ``ling3flash`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
