"""Device ms a step of the sliding-window attention rows (72 query heads
over 8) from q/k/v to the output projection: everything traced under
``attn-window`` — projections, rotation, the flash kernels under the band
of 512, the gate a head."""

from chipbench import laguna_reduce


def read(ctx):
    return laguna_reduce.within_ms(ctx, "attn-window")
