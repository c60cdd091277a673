"""Device ms a step of the three flash kernels in the full-attention row
(the causal triangle at S = 16,384, GQA 32/4, D = 128)."""

from chipbench import mellum_reduce


def read(ctx):
    return mellum_reduce.within_ms(ctx, "attn-mixer", *mellum_reduce.FLASH)
