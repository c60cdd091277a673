"""Device ms a step under the ``fwd-bwd`` phase in the ``ling3flash``
cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "fwd-bwd")
