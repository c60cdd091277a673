"""What the chunked gated delta rule needs (FLOPs and least HBM bytes,
forward + backward, at the published chunk: ``flops_qwen3next.py``) over
the peaks, over ``gdn-scan``'s device time."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.roofline_pct(
        ctx, "gdn_scan_roofline_seconds", "gdn-scan")
