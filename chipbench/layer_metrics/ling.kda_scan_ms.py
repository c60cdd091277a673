"""Device ms a step of the chunked delta rule under a decay a key channel,
forward and backward (the forward three times under the two
rematerialisations): ``kda-scan``."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "kda-scan")
