"""Device ms a step under the ``opt-update`` phase in the ``ling3flash``
cell (the update's own fusions only, as ``step.opt_update_ms`` reads it)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "opt-update")
