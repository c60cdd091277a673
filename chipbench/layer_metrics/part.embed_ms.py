"""Device ms a step owned by the embedding (``embed``: the lookup,
positions, scaling; in backward the table's gradient scatter)."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "embed")
