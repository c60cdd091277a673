"""Device ms a step of the full-attention row from q/k/v to the output
projection: everything traced under ``attn-mixer`` — projections, QK-norm
and the YaRN rotation, the three flash kernels under the triangle."""

from chipbench import mellum_reduce


def read(ctx):
    return mellum_reduce.within_ms(ctx, "attn-mixer")
