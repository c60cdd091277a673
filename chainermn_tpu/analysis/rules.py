"""The lint ruleset, R001–R006.

Each rule is a function over a :class:`~chainermn_tpu.analysis.core.
LintContext` registered via ``register_rule``; future parallelism PRs
(pipeline, ulysses, MoE) add rules the same way.  Severities are all
``error``: every rule here catches a program that is silently wrong,
hung, or measurably wasteful at scale — docs/static_analysis.md is the
user-facing catalog, with the suppression story for intentional cases.
"""

from __future__ import annotations

from typing import List

import numpy as np

from chainermn_tpu.analysis import dataflow
from chainermn_tpu.analysis.core import (
    Finding,
    LintContext,
    SEVERITY_ERROR,
    collective_events,
    collective_fingerprint,
    iter_eqns_with_path,
    register_rule,
)
from chainermn_tpu.observability.hlo_audit import (
    REDUCTION_PRIMITIVES,
    _eqn_axes,
)

#: dtypes whose reduction accumulates in reduced precision on the wire.
NARROW_DTYPES = ("bfloat16", "float16")

#: quantized wire dtypes produced by ``comm_dtype=`` — legitimate ONLY
#: inside the blessed scale→cast→reduce→cast→unscale pattern, whose
#: tell is the per-bucket amax ``pmax`` exchange over the same axes.
QUANT_WIRE_DTYPES = ("int8", "float8_e4m3fn", "float8_e4m3")

#: below this leaf count the per-leaf and bucketed lowerings coincide,
#: so R004 cannot (and need not) distinguish them.
_R004_MIN_LEAVES = 4


def _signature(events):
    return tuple((e.name, e.axes, e.dtype, e.shape) for e in events)


@register_rule(
    "R001", "collective-order-divergence",
    "collective sequence differs across cond branches or across ranks — "
    "deadlock risk at dispatch",
)
def check_collective_divergence(ctx: LintContext) -> List[Finding]:
    findings: List[Finding] = []
    # Static half: a `cond` whose branches trace different collective
    # sequences executes different collectives depending on a runtime
    # value.  When that value is rank-dependent (axis_index, host id),
    # some ranks enter the collective and others never do — the classic
    # SPMD deadlock.  Branch-invariant conds are exactly the ones whose
    # branch signatures agree, so signature equality is the precise
    # static criterion.
    for path, eqn in iter_eqns_with_path(ctx.jaxpr):
        if eqn.primitive.name != "cond":
            continue
        branch_events = [
            collective_events(br) for br in eqn.params.get("branches", ())
        ]
        sigs = [_signature(evs) for evs in branch_events]
        if len(set(sigs)) <= 1:
            continue
        axes = tuple(sorted(
            {a for evs in branch_events for e in evs for a in e.axes}
        ))
        nbytes = max(
            (e.bytes for evs in branch_events for e in evs), default=0
        )
        counts = "/".join(str(len(s)) for s in sigs)
        findings.append(Finding(
            rule="R001", severity=SEVERITY_ERROR,
            message=(
                f"cond branches trace different collective sequences "
                f"({counts} collectives per branch): if the predicate is "
                "rank-dependent, ranks will dispatch mismatched "
                "collectives and deadlock"
            ),
            eqn_path=path, axes=axes, bytes=nbytes,
            fix_hint=(
                "hoist the collective out of the cond, or make both "
                "branches issue the identical collective sequence "
                "(e.g. psum a zero contribution on the idle branch)"
            ),
        ))
    # Cross-rank half: canonicalize this rank's whole collective
    # sequence and compare it over the communicator's object plane.  A
    # mismatch means the ranks *already* traced divergent programs —
    # e.g. a data-dependent architecture choice — and the first step
    # will hang.
    if ctx.comm is not None and getattr(ctx.comm, "size", 1) > 1:
        fp = collective_fingerprint(ctx.jaxpr)
        fps = ctx.comm.allgather_obj(fp)
        if len(set(fps)) > 1:
            findings.append(Finding(
                rule="R001", severity=SEVERITY_ERROR,
                message=(
                    "collective fingerprint differs across ranks "
                    f"({len(set(fps))} distinct of {len(fps)}): the step "
                    "programs are not SPMD and will deadlock at the "
                    "first mismatched collective"
                ),
                fix_hint=(
                    "remove rank-dependent branching from the step "
                    "construction (model config, loss selection, "
                    "communicator choice must match on every process)"
                ),
            ))
    return findings


@register_rule(
    "R002", "unreduced-gradient",
    "a gradient computed under the data-parallel axis reaches the "
    "optimizer update with no psum/allreduce on that axis",
    requires=("jaxpr", "args"),
)
def check_unreduced_gradient(ctx: LintContext) -> List[Finding]:
    dp = frozenset(ctx.dp_axes)
    if not dp or not ctx.arg_leaf_avals:
        return []
    jaxpr = ctx.jaxpr
    counts = [len(a) for a in ctx.arg_leaf_avals]
    if sum(counts) + ctx.n_kwarg_leaves != len(jaxpr.invars):
        return []  # flattening didn't line up with invars; stay silent
    batch = ctx.batch_argnum % len(counts)
    in_taints, offset = [], 0
    for i, n in enumerate(counts):
        in_taints.extend([dp if i == batch else dataflow.EMPTY] * n)
        offset += n
    in_taints.extend([dataflow.EMPTY] * ctx.n_kwarg_leaves)

    out_taints = dataflow.propagate(ctx.closed_jaxpr, in_taints)

    # Only outputs shaped like a (non-scalar) parameter matter: those
    # are the updated params / optimizer moments — batch-derived values
    # reaching them unreduced means each device trains on its own shard
    # and the replicas silently diverge.  Losses and aux outputs may
    # legitimately stay local.
    param_sigs = {
        sig for sig in ctx.arg_leaf_avals[0] if sig[0]  # non-scalar
    }
    hit_axes, n_hits = set(), 0
    for v, taint in zip(jaxpr.outvars, out_taints):
        t = taint & dp
        if not t:
            continue
        sig = (tuple(getattr(v.aval, "shape", ())),
               str(getattr(v.aval, "dtype", "?")))
        if sig in param_sigs:
            n_hits += 1
            hit_axes |= t
    if not n_hits:
        return []
    axes = tuple(sorted(hit_axes))
    return [Finding(
        rule="R002", severity=SEVERITY_ERROR,
        message=(
            f"{n_hits} parameter-shaped step output(s) still carry "
            f"un-reduced per-device gradient content on data-parallel "
            f"axes {axes}: replicas will silently diverge"
        ),
        axes=axes,
        fix_hint=(
            "average gradients before the optimizer update — "
            "communicator.allreduce_grad(grads), or lax.psum/pmean over "
            "the data-parallel axes"
        ),
    )]


def _pmax_axes(ctx: LintContext) -> set:
    """Axis tuples over which the program exchanges a ``pmax``.

    ``pmax`` is not a :data:`COLLECTIVE_PRIMITIVES` member (it never
    carries gradient payload), so it is invisible to ``ctx.events()``;
    the amax exchange of the scaled-quantization pattern has to be
    found by walking the jaxpr directly.
    """
    axes = set()
    for _, eqn in iter_eqns_with_path(ctx.jaxpr):
        if eqn.primitive.name == "pmax":
            axes.add(tuple(str(a) for a in _eqn_axes(eqn)))
    return axes


@register_rule(
    "R003", "narrow-dtype-reduction",
    "psum/psum_scatter accumulates a bf16/fp16 or bare int8/fp8 payload "
    "without an explicit opt-in or the scaled-quantization pattern",
)
def check_narrow_dtype_reduction(ctx: LintContext) -> List[Finding]:
    # An explicit allreduce_grad_dtype is the sanctioned way to trade
    # wire precision for bandwidth (the reference pure_nccl's fp16
    # mode); with it set, narrow reductions are intent, not accident.
    if ctx.comm is not None and \
            getattr(ctx.comm, "allreduce_grad_dtype", None) is not None:
        return []
    # Likewise a resolved comm_dtype (ctor / env) declares the
    # quantized wire intentionally: the communicator itself emits the
    # blessed scale→cast→reduce→cast→unscale sequence.
    comm_quant = None
    if ctx.comm is not None:
        try:
            resolve = getattr(ctx.comm, "resolve_comm_dtype", None)
            comm_quant = resolve() if callable(resolve) else None
        except Exception:
            comm_quant = None
    pmax_axes = None  # computed lazily — most programs have no quant wire
    findings = []
    for e in ctx.events():
        if e.name not in REDUCTION_PRIMITIVES:
            continue
        if e.dtype in QUANT_WIRE_DTYPES:
            # Quantized wire.  Blessed when the communicator opted in,
            # or when the same program exchanges a pmax over the same
            # axes — the per-bucket amax agreement that makes the
            # narrow sum exact-mean-preserving.  A bare int8/fp8
            # reduction with neither is an unscaled sum: it wraps
            # (int8) or saturates (fp8) as the world grows.
            if comm_quant is not None:
                continue
            if pmax_axes is None:
                pmax_axes = _pmax_axes(ctx)
            # The scale is sound when amax agreement covers at least
            # the axes being reduced (hierarchical/2D lowerings reduce
            # over sub-axes of the pmax'd data-parallel axes).
            if any(set(e.axes) <= set(p) for p in pmax_axes):
                continue
            findings.append(Finding(
                rule="R003", severity=SEVERITY_ERROR,
                message=(
                    f"{e.name} reduces a bare {e.dtype} payload of "
                    f"shape {list(e.shape)} with no amax scale "
                    "exchange: an unscaled narrow sum wraps or "
                    "saturates as the world grows"
                ),
                eqn_path=e.path, axes=e.axes, bytes=e.bytes,
                fix_hint=(
                    "use comm_dtype= on the communicator (or "
                    "CHAINERMN_TPU_COMM_DTYPE) so the reduction is "
                    "wrapped in the scaled pattern: pmax the bucket "
                    "amax, divide by the per-rank budget, reduce, "
                    "rescale"
                ),
            ))
            continue
        if e.dtype not in NARROW_DTYPES:
            continue
        findings.append(Finding(
            rule="R003", severity=SEVERITY_ERROR,
            message=(
                f"{e.name} reduces a {e.dtype} payload of shape "
                f"{list(e.shape)}: the accumulation itself runs in "
                f"{e.dtype}, silently losing gradient precision as the "
                "world grows"
            ),
            eqn_path=e.path, axes=e.axes, bytes=e.bytes,
            fix_hint=(
                "keep gradients float32 through the collective, or opt "
                "in explicitly with allreduce_grad_dtype= on the "
                "communicator (which also suppresses this rule)"
            ),
        ))
    return findings


@register_rule(
    "R004", "bucketing-regression",
    "reduction-collective count scales with parameter leaf count "
    "instead of bucket count",
    requires=("audit",),
)
def check_bucketing_regression(ctx: LintContext) -> List[Finding]:
    n_leaves = ctx.n_leaves
    if n_leaves is None and ctx.arg_leaf_avals:
        n_leaves = len(ctx.arg_leaf_avals[0])
    if not n_leaves or n_leaves < _R004_MIN_LEAVES:
        return []
    audit = ctx.get_audit()
    red = audit.reduction_collectives()
    # The golden-census invariant, as a rule: a bucketed lowering emits
    # O(n_buckets) reductions (+1 for the loss pmean); one-or-more
    # reduction *per leaf* is the unbucketed per-leaf lowering leaking
    # back in — each collective re-pays the dispatch latency the fused
    # flat-buffer path exists to amortize.  Compiled-HLO audits arrive
    # in the paired-async representation (``all-reduce-start``/``-done``
    # per bucket under the overlapped schedule); reduction_collectives()
    # folds each pair to ONE logical reduction, so overlap cannot be
    # misread as a bucketing regression (fixture: overlap_async_pairs).
    if red < n_leaves:
        return []
    return [Finding(
        rule="R004", severity=SEVERITY_ERROR,
        message=(
            f"{red} reduction collectives for a {n_leaves}-leaf "
            "parameter tree: the gradient allreduce is scaling with "
            "leaf count, not bucket count"
        ),
        bytes=sum(audit.bytes_per_primitive.get(p, 0)
                  for p in REDUCTION_PRIMITIVES),
        fix_hint=(
            "re-enable gradient bucketing: bucket_bytes>0 on the "
            "communicator (and check CHAINERMN_TPU_BUCKET_BYTES is not "
            "set to 0)"
        ),
    )]


@register_rule(
    "R005", "donation-audit",
    "train step compiled without donating params/opt-state buffers",
)
def check_donation(ctx: LintContext) -> List[Finding]:
    # Two detection paths, matching the two trace paths: the jit AOT
    # surface hands us donate_argnums directly; a make_jaxpr trace
    # through a jitted callable leaves the declaration on the inlined
    # pjit eqn's donated_invars param.
    if ctx.donate_argnums:
        return []
    pjits = [
        (path, eqn) for path, eqn in iter_eqns_with_path(ctx.jaxpr)
        if eqn.primitive.name == "pjit"
    ]
    if any(any(eqn.params.get("donated_invars", ()))
           for _, eqn in pjits):
        return []
    if not pjits and ctx.donate_argnums is None:
        return []  # never went through jit — nothing to donate
    jaxpr = ctx.jaxpr
    in_sigs = {
        (tuple(v.aval.shape), str(v.aval.dtype))
        for v in jaxpr.invars
        if hasattr(v.aval, "shape") and v.aval.shape
    }
    matched_bytes = 0
    n_matched = 0
    for v in jaxpr.outvars:
        aval = getattr(v, "aval", None)
        shape = tuple(getattr(aval, "shape", ()))
        if not shape:
            continue
        if (shape, str(aval.dtype)) in in_sigs:
            n_matched += 1
            matched_bytes += (
                int(np.prod(shape)) * np.dtype(aval.dtype).itemsize
            )
    if not n_matched:
        return []
    return [Finding(
        rule="R005", severity=SEVERITY_ERROR,
        message=(
            f"step updates {n_matched} input-shaped buffer(s) "
            f"(~{matched_bytes} bytes) but donates nothing: XLA must "
            "keep both old and new params/opt-state live, doubling "
            "their memory"
        ),
        bytes=matched_bytes,
        eqn_path=pjits[0][0] if pjits else "",
        fix_hint=(
            "build the step with donate=True (make_train_step default) "
            "or pass donate_argnums to jax.jit for the updated "
            "arguments"
        ),
    )]


@register_rule(
    "R006", "sharding-plan-coverage",
    "a sharding plan leaves parameter leaves unmatched or resolves a "
    "leaf to a spec that cannot apply",
    requires=("plan",),
)
def check_plan_coverage(ctx: LintContext) -> List[Finding]:
    # Plan targets carry no jaxpr at all — the "program" under lint is
    # the rule table itself.  validate() does the tree walk; this rule
    # turns its two error classes into findings (shadowed rules stay
    # advisory: resolution is still well-defined, so they surface via
    # validate()/the shardplan CLI, not as lint errors).
    from chainermn_tpu.sharding import validate

    v = validate(ctx.plan, ctx.plan_params)
    findings: List[Finding] = []
    for path in v.unmatched:
        findings.append(Finding(
            rule="R006", severity=SEVERITY_ERROR,
            message=(
                f"plan {ctx.plan.name!r} has no rule matching parameter "
                f"leaf '{path}': resolution raises and the layout is "
                "undefined for this model"
            ),
            eqn_path=path,
            fix_hint=(
                "add a rule whose regex matches this path, or end the "
                "plan with a terminal catch-all "
                "PlanRule('replicate', r'.*', P())"
            ),
        ))
    for c in v.conflicts:
        findings.append(Finding(
            rule="R006", severity=SEVERITY_ERROR,
            message=(
                f"plan {ctx.plan.name!r} rule {c['rule']!r} resolves "
                f"leaf '{c['path']}' to a conflicting spec: {c['reason']}"
            ),
            eqn_path=c["path"],
            fix_hint=(
                "fix the rule's PartitionSpec (one mesh axis per entry, "
                "no more entries than the leaf has dims, axes that "
                "exist on the target mesh)"
            ),
        ))
    return findings
