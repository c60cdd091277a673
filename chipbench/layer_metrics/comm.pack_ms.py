"""Device ms a step of the ``allreduce`` phase less the ``all-reduce`` ops
themselves: bucket pack, unpack and wire casts."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "allreduce", without_allreduce=True)
