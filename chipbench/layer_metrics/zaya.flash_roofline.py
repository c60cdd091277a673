"""Needed FLOPs and least bytes of causal grouped-query attention forward
+ backward at the latent's width (``flops_zaya.py``: K and V at their own)
over the peaks, over the three flash kernels' device time in the ``zaya``
cell."""

from chipbench import flops_zaya, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
    if not ms:
        return None
    least, bound = flops_zaya.flash_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["flash_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
