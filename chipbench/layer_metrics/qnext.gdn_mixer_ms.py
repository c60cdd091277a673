"""Device ms a step of the Gated DeltaNet mixers, from the input projections
to the output projection: ``gdn-mixer`` and the regions nested in it (the
chunked gated delta rule, the convolution's two kernels)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "gdn-mixer", "gdn-scan", "ssm-conv")
