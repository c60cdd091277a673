"""Device ms a step OWNED by the two leading dense SwiGLU FFNs (6144 wide,
every token): the model part ``ffn``, by the owner rule (a fusion counts
under its matrix product)."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "ffn")
