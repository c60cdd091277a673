"""Device ms a step of the full-attention rows (48 query heads over 8)
from q/k/v to the output projection: everything traced under
``attn-mixer`` — projections, the YaRN rotation of half a head, the flash
kernels under the triangle, the gate a head."""

from chipbench import laguna_reduce


def read(ctx):
    return laguna_reduce.within_ms(ctx, "attn-mixer")
