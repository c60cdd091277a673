"""Device ms a step of the three flash kernels (GQA 8/2, D = 128,
S = 8192, in the latent, every layer) in the ``zaya`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
