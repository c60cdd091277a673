"""Device ms a step of the causal depthwise convolution and its SiLU (6144
channels) in the ``nemotron_h`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "ssm-conv")
