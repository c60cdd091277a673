"""Device ms a step of the KDA mixers' causal depthwise convolution and its
SiLU over the 12,288 query, key and value channels: ``ssm-conv`` (the
Mamba-2 cells' kernels; no other row of this cell runs them)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "ssm-conv")
