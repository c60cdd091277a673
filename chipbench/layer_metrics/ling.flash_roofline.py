"""Needed FLOPs and least bytes of causal attention forward + backward at
32 heads whose scores are 192 wide and whose values 128, over the
triangle (``flops_ling3.py``: 6 (192 + 128) FLOPs a pair and head, six
passes at each width) over the peaks, over the three flash kernels'
device time."""

from chipbench import flops_ling3, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(
        ctx, "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")
    if not ms:
        return None
    least, bound = flops_ling3.flash_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["ling_flash_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
