"""Needed FLOPs and least bytes of causal grouped-query attention forward
+ backward (``flops_nemotron.py``) over the peaks, over the three flash
kernels' device time in the ``nemotron_h`` cell."""

from chipbench import flops_nemotron, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
    if not ms:
        return None
    least, bound = flops_nemotron.flash_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["flash_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
