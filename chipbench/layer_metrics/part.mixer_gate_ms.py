"""Device ms a step owned by the Mamba-2 mixers' float32 side
(``mixer-gate``: ``dt``'s softplus, ``-exp(A_log)``, the casts of ``y``
and the gate, ``y * silu(gate)``, the gated norm)."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "mixer-gate")
