"""Device ms a step under the ``opt-update`` phase.  A fusion counts whole
under the one op the compiler names it after: a weight-gradient matmul with
AdamW fused in counts as ``fwd-bwd``, so this reads the update's own
fusions only (PERF.md section 6, PR 24)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "opt-update")
