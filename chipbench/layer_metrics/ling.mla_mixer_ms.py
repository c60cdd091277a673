"""Device ms a step of the latent-attention row from its query and latent
projections to its output projection: ``mla-mixer`` and what nests in it
— the latent's and the heads' norms and the rotation (``attn-rope``) and
the three flash kernels."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(
        ctx, "mla-mixer", "attn-rope", "flash-fwd", "flash-bwd-dq",
        "flash-bwd-dkv")
