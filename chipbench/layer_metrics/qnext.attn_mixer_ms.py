"""Device ms a step of the gated attention row from q/k/v to its output
projection: the owner ``attn-mixer`` (projections, gate, transposes) with
what nests in it — the QK-norm and rotation (``attn-rope``) and the three
flash kernels."""

from chipbench import parts_reduce, scope_reduce


def read(ctx):
    nested = scope_reduce.region_ms(
        ctx, "attn-rope", "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
    if nested is None:
        return None
    return nested + (parts_reduce.owner_ms(ctx, "attn-mixer") or 0.0)
