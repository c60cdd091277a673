"""Device ms a step of the three flash kernels (GQA 16/2, D = 256,
S = 8192, one layer) in the ``qwen3_next`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
