"""Device ms a step of the three flash kernels in the sliding-window
rows (a band of 1024 at S = 16,384, GQA 32/4, D = 128)."""

from chipbench import mellum_reduce


def read(ctx):
    return mellum_reduce.within_ms(ctx, "attn-window", *mellum_reduce.FLASH)
