"""Device ms a step owned by the dense FFNs (``ffn``: ``wi`` to ``wo``
with the activation, forward, recomputed forward and backward; a
weight-gradient matmul with AdamW fused in counts with them)."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "ffn")
