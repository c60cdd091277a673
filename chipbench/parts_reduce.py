"""Device time by OWNER, for the per-layer readers that ask for it
(``part.*``, ``parts.*``).

The owner reading is the program's (``chainermn_tpu.observability.
device_trace.attribute``: within ``fwd-bwd`` every instruction is owned by
the innermost kernel region or model part on the path of its heaviest op,
a partition of the phase; ``shared`` says how much of it sits in fusions
that hold a second owner's ops).  It comes with the same sweep as the
phase and region readings, so these readers go through
``scope_reduce.attribution(ctx)`` — the same table, the same 98% join —
and return ``None`` where that returns nothing or its result carries no
``"owner"`` (a parent commit's ``device_trace``).  An owner that owns no
instruction of the step (``residual`` where the compiler fused every
residual add into a neighbour) reads 0, which is a reading.
"""

from chipbench import scope_reduce


def rows(ctx):
    """One ``attribute`` result a device, or ``None``."""
    got = scope_reduce.attribution(ctx)
    if got is None or any("owner" not in g for g in got["all"]):
        return None
    return got["all"]


def owner_ms(ctx, *names):
    """Device ms a step owned by the named regions or parts together."""
    got = rows(ctx)
    if got is None:
        return None
    return scope_reduce._ms_per_step(ctx, (
        sum(g["owner"].get(n, 0.0) for n in names) for g in got))


def pass_ms(ctx, which):
    """Device ms a step of one pass of ``fwd-bwd`` (``forward``,
    ``recompute``, ``backward``), every owner."""
    got = rows(ctx)
    if got is None:
        return None
    return scope_reduce._ms_per_step(ctx, (
        sum(g["pass"][which].values()) for g in got))


def unowned_pct(ctx):
    """``fwd-bwd`` time whose owning path names no region or part, over
    the phase's time by the owner rule."""
    got = rows(ctx)
    if got is None:
        return None
    phase = sum(sum(g["owner"].values()) for g in got)
    if not phase:
        return None
    none = scope_reduce._device_trace().NO_OWNER
    return 100.0 * sum(g["owner"].get(none, 0.0) for g in got) / phase


def shared_pct(ctx):
    """Time in fusions that hold ops of a second owner, over busy time:
    what the owner reading bounds and cannot split."""
    got = rows(ctx)
    if got is None:
        return None
    return 100.0 * (sum(sum(g["shared"].values()) for g in got)
                    / sum(g["busy"] for g in got))
