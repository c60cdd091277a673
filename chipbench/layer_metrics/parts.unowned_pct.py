"""Share of ``fwd-bwd`` (by the owner rule) that no region or part
owns."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.unowned_pct(ctx)
