"""Device ms a step owned by the mixers' float32 side (``mixer-gate``: in a
Mamba-2 mixer ``dt``'s softplus, ``-exp(A_log)``, the casts of ``y`` and
the gate, ``y * silu(gate)``, the gated norm; in a delta-rule mixer
``beta``, the decay, the L2 norms and the gated norm; an attention row's
output gate)."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "mixer-gate")
