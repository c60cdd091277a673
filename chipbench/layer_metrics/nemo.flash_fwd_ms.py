"""Device ms a step of the flash forward kernel in the ``nemotron_h``
cell (run twice a step under remat)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "flash-fwd")
