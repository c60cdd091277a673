"""Device ms a step of the Mamba-2 mixers of the ``nemotron_h`` cell, from
their input to their output projection, the scan and the convolution
nested in them."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "mamba-mixer", "ssd-scan", "ssm-conv")
