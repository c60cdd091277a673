"""Device ms a step of the three sliding-window attention rows from q/k/v
to the output projection: everything traced under ``attn-window`` —
projections, QK-norm and rotation, the three flash kernels under the
band."""

from chipbench import mellum_reduce


def read(ctx):
    return mellum_reduce.within_ms(ctx, "attn-window")
