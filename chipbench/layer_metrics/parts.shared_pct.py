"""Share of the busy time spent in fusions that hold ops of a second
owner: what the owner reading bounds and cannot split."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.shared_pct(ctx)
