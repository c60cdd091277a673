"""What the chunked rule needs (FLOPs and least HBM bytes, forward +
backward, at the published chunk, the decay a float32 number a key
channel: ``flops_ling3.py``, the same work whatever implements it) over
the peaks, over ``kda-scan``'s device time."""

from chipbench import flops_ling3, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(ctx, "kda-scan")
    if not ms:
        return None
    least, bound = flops_ling3.kda_scan_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["kda_scan_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
