"""Device ms a step of the flash kernels in the sliding-window rows (a
band of 512, 72 query rows over 8 key/value rows, D = 128)."""

from chipbench import laguna_reduce


def read(ctx):
    return laguna_reduce.within_ms(ctx, "attn-window", *laguna_reduce.FLASH)
