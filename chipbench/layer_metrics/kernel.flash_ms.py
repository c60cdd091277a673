"""Device ms a step of the three flash attention kernels (``flash-fwd``,
``flash-bwd-dq``, ``flash-bwd-dkv``) together, every attention layer."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, *scope_reduce.FLASH)
