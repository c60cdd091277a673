"""Device ms a step of the four attention rows' QK-norm and rotary
positions (``attn-rope``: plain in the sliding rows, YaRN in the full
one)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.region_ms(ctx, "attn-rope")
