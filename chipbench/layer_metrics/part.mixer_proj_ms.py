"""Device ms a step owned by the mixers' dense matrices
(``mixer-proj``: q/k/v/out, ``in_proj`` / ``out_proj``, CCA's
down-projections and ``W_o``)."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "mixer-proj")
