"""Device ms a step of the Gated DeltaNet mixers' causal depthwise
convolution and its SiLU over the 8,192 query, key and value channels:
``ssm-conv`` (the Mamba-2 cells' kernels)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "ssm-conv")
