"""Device ms a step under the ``fwd-bwd`` phase in the ``mellum`` cell."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "fwd-bwd")
