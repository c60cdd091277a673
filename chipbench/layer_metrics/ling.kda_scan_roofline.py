"""What the chunked rule needs (FLOPs and least HBM bytes, forward +
backward, at the published chunk, the decay a float32 number a key
channel: ``flops_ling3.py``, the same work whatever implements it) over
the peaks, over ``kda-scan``'s device time."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.roofline_pct(
        ctx, "kda_scan_roofline_seconds", "kda-scan")
