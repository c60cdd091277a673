"""Device ms a step of the sigmoid gate a head in the five attention rows
(``mixer-gate`` within ``attn-window`` and ``attn-mixer``: the projection
``x W_g``, the sigmoid, the product with the head's output)."""

from chipbench import laguna_reduce


def read(ctx):
    return laguna_reduce.head_gate_ms(ctx)
