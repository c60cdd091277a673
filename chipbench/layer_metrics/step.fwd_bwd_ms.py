"""Device ms a step under the ``fwd-bwd`` phase of ``make_train_step``."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "fwd-bwd")
