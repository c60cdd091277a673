"""Device ms a step of the six block-diffusion attention rows from q/k/v
to the output projection: everything traced under ``attn-blockdiff`` —
projections, QK-norm and the rotation at the handed positions, the three
flash kernels under the mask."""

from chipbench import sdar_reduce


def read(ctx):
    return sdar_reduce.within_ms(ctx)
