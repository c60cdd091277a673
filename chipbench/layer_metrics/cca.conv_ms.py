"""Device ms a step of the mixers' two causal convolutions (depthwise, then
grouped by head), the q-k mean and the value's shifted half: ``cca-conv``."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "cca-conv")
