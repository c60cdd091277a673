"""Device ms a step owned by the layers' pre-norms and the final norm
(``norm``)."""

from chipbench import parts_reduce


def read(ctx):
    return parts_reduce.owner_ms(ctx, "norm")
