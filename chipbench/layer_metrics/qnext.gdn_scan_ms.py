"""Device ms a step of the chunked gated delta rule, forward and backward:
``gdn-scan``."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "gdn-scan")
