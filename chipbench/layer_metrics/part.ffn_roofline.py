"""What the dense FFNs need (``flops_parts.py``: three passes over two or
three matrices a layer, nothing recomputed, one chip's tokens) over the
bf16 peak, over the time ``ffn`` owns."""

from chipbench import flops, flops_parts, parts_reduce


def read(ctx):
    ms = parts_reduce.owner_ms(ctx, "ffn")
    if not ms:
        return None
    needed = flops_parts.ffn_train_flops(
        ctx["config"], ctx["mix"], len(ctx["devices"]))
    if needed is None:
        return None
    peak = flops.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * (needed / peak) / (ms / 1e3)
