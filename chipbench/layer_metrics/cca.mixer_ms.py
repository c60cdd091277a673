"""Device ms a step of the compressed-convolutional-attention mixers, from
the down-projections to the output projection: ``cca-mixer`` and the
regions nested in it (both convolutions with the q-k mean and the value's
shift, the norms and the rotation, the three flash kernels in the latent)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "cca-mixer", "cca-conv", "cca-rope", "flash-fwd",
        "flash-bwd-dq", "flash-bwd-dkv")
