"""Device ms a step of the queries' and keys' L2 norms, the keys' learned
scale and the partial rotation: ``cca-rope``."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "cca-rope")
