"""Needed FLOPs and least bytes of causal grouped-query attention forward
+ backward at 16 heads of 256 (``flops_qwen3next.py``: K and V at their
own width) over the peaks, over the three flash kernels' device time."""

from chipbench import flops_qwen3next, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
    if not ms:
        return None
    least, bound = flops_qwen3next.flash_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["flash_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
