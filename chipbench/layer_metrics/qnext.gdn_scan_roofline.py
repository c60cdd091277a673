"""What the chunked gated delta rule needs (FLOPs and least HBM bytes,
forward + backward, at the published chunk: ``flops_qwen3next.py``) over
the peaks, over ``gdn-scan``'s device time."""

from chipbench import flops_qwen3next, scope_reduce


def read(ctx):
    ms = scope_reduce.region_ms(ctx, "gdn-scan")
    if not ms:
        return None
    least, bound = flops_qwen3next.gdn_scan_roofline_seconds(
        ctx["config"], ctx["mix"], ctx["device_kind"])
    ctx.setdefault("notes", {})["gdn_scan_roofline_bound"] = bound
    return 100.0 * least / (ms / 1e3)
