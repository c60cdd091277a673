"""Share of the ``ling3flash`` cell's busy time that joins to no step
phase."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.unattributed_pct(ctx)
