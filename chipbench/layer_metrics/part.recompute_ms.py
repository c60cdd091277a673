"""Device ms a step of the forward recomputed in backward (``remat``):
``fwd-bwd`` time owned through a ``rematted_computation`` path."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.pass_ms(ctx, "recompute")
