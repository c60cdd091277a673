"""Device ms a step of the five Kimi-Delta-Attention mixers, from the input
projections to the output projection: ``kda-mixer`` and the regions
nested in it (the chunked rule under its per-channel decay, the
convolution's two kernels)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "kda-mixer", "kda-scan", "ssm-conv")
