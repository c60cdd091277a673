"""Collective audit — jaxpr- and HLO-level census of a step's wire cost.

For any traceable function (a jitted train step, a communicator's
``allreduce_grad``), count the collective primitives it lowers to and
charge each collective's per-device operand bytes to the mesh axes it
runs over.  The result is environment-independent evidence of an
algorithm's wire structure — readable on one chip, or on the virtual
CPU mesh, long before a v4-32 is available — and the input the
two_dimensional backend's bandwidth claim is verified against (its
inter-axis bytes must be the flat backend's divided by ``intra_size``).

``benchmarks/allreduce_bench.py``'s ``allreduce_static_bytes_per_leg``
table consumes THIS module (one source of truth for the bytes-per-leg
metric); examples call :func:`audit_fn` on their real train step and
log the result as an ``hlo_audit`` row in the step-event log.

Two census sources, one :class:`CollectiveAudit` shape:

* :func:`audit_jaxpr` (and the ``audit_*`` wrappers) — the traced
  program, where collectives are single primitives (``psum``, …).
* :func:`audit_hlo_text` — compiled HLO, where the TPU compiler's
  async-collective machinery may have SPLIT a collective into an
  ``all-reduce-start``/``all-reduce-done`` pair (likewise
  ``collective-permute-start/done``, ``all-gather-start/done``) so the
  latency-hiding scheduler can place independent backward compute
  between the two halves — the lowering the backward-overlapped bucket
  schedule (:mod:`chainermn_tpu.communicators.overlap`) exists to
  trigger.  The HLO parser folds each start/done pair into ONE logical
  collective under its jaxpr-primitive name (so
  ``reduction_collectives()`` and ``census()`` never double-count) and
  reports ``overlap_fraction``: the fraction of async pairs with real
  compute scheduled strictly between start and done.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

# lax.psum → psum, lax.psum_scatter → reduce_scatter, lax.all_gather →
# all_gather, lax.ppermute → ppermute, lax.all_to_all → all_to_all.
COLLECTIVE_PRIMITIVES = (
    "psum", "reduce_scatter", "all_gather", "ppermute", "all_to_all",
)

#: The primitives that perform a reduction (the ones gradient bucketing
#: promises to make leaf-count-independent; all_gather/ppermute only move).
REDUCTION_PRIMITIVES = ("psum", "reduce_scatter")

# The four the gradient-allreduce census reports (all_to_all never appears
# in an allreduce lowering; kept out for byte-identical bench output).
ALLREDUCE_CENSUS_KEYS = ("psum", "reduce_scatter", "all_gather", "ppermute")

#: HLO opcode → jaxpr primitive name, the vocabulary bridge that lets an
#: HLO-text census reuse every count consumer (``census()``,
#: ``reduction_collectives()``, lint R004) unchanged.
HLO_COLLECTIVE_OPS = {
    "all-reduce": "psum",
    "reduce-scatter": "reduce_scatter",
    "all-gather": "all_gather",
    "collective-permute": "ppermute",
    "all-to-all": "all_to_all",
}

_ASYNC_START = "-start"
_ASYNC_DONE = "-done"


def fold_async_counts(counts: Dict[str, int]) -> Dict[str, int]:
    """Fold a counts dict that may contain RAW HLO opcodes — including
    unpaired ``*-start``/``*-done`` entries — into jaxpr-primitive
    counts, one logical collective per async pair.

    ``-start`` carries the count (each pair has exactly one), ``-done``
    is dropped, synchronous HLO opcodes map through
    :data:`HLO_COLLECTIVE_OPS`, and names already in jaxpr vocabulary
    pass unchanged.  This is the defensive normalization lint R004 runs
    before comparing collective counts to leaf counts, so a census fed
    from compiled HLO can never make split collectives look like a
    bucketing regression.
    """
    out: Dict[str, int] = {}
    for name, n in counts.items():
        base = name
        if base.endswith(_ASYNC_DONE):
            continue
        if base.endswith(_ASYNC_START):
            base = base[: -len(_ASYNC_START)]
        base = HLO_COLLECTIVE_OPS.get(base, base)
        out[base] = out.get(base, 0) + int(n)
    return out


def _eqn_axes(eqn):
    """Mesh-axis names a collective eqn runs over, as a tuple."""
    for key in ("axes", "axis_name"):
        if key in eqn.params:
            ax = eqn.params[key]
            if isinstance(ax, (tuple, list)):
                out = []
                for a in ax:
                    out.extend(a) if isinstance(a, (tuple, list)) \
                        else out.append(a)
                return tuple(out)
            return (ax,)
    return ()


def _operand_bytes(eqn) -> int:
    """Per-device operand bytes of one eqn (sum over array invars)."""
    return sum(
        int(np.prod(v.aval.shape)) * np.dtype(v.aval.dtype).itemsize
        for v in eqn.invars
        if hasattr(v.aval, "shape")
    )


def iter_eqns(jaxpr):
    """Depth-first walk over every eqn, recursing into inner jaxprs
    (pjit/shard_map/scan/cond bodies) — collectives live inside the
    shard_map eqn, never at top level."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            # Inner jaxprs appear as raw Jaxpr (has .eqns) or ClosedJaxpr
            # (has .jaxpr) param values; `branches` holds a tuple of them.
            if isinstance(val, (tuple, list)):
                for v in val:
                    if hasattr(v, "eqns"):
                        yield from iter_eqns(v)
                    elif hasattr(v, "jaxpr"):
                        yield from iter_eqns(v.jaxpr)
            elif hasattr(val, "eqns"):
                yield from iter_eqns(val)
            elif hasattr(val, "jaxpr"):
                yield from iter_eqns(val.jaxpr)


@dataclasses.dataclass
class CollectiveAudit:
    """Census of one traced program's collectives.

    ``counts`` — occurrences per collective primitive name.
    ``bytes_per_axis`` — per-device operand bytes charged to each mesh
    axis a collective runs over (an op over both axes charges both),
    ``str(axis) → bytes``.
    ``bytes_per_primitive`` — per-device operand bytes per primitive.
    ``op_bytes`` — per-device operand bytes of each individual occurrence,
    in trace order per primitive: with gradient bucketing this IS the
    per-bucket byte profile of the allreduce.
    ``async_pairs`` — start/done pairs folded into the counts (HLO-text
    audits only; a jaxpr audit never sees the split representation).
    ``overlap_fraction`` — fraction of those pairs with at least one
    real compute instruction scheduled strictly between start and done:
    the audit's measure of how much of the collective actually hides
    under backward compute (0.0 when there are no async pairs).
    """

    counts: Dict[str, int]
    bytes_per_axis: Dict[str, int]
    bytes_per_primitive: Dict[str, int]
    op_bytes: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    async_pairs: int = 0
    overlap_fraction: float = 0.0

    def census(self, keys=ALLREDUCE_CENSUS_KEYS) -> Dict[str, int]:
        """Fixed-key count view (zeros included) — the allreduce-bench
        ``hlo_collectives`` record shape.  Counts are normalized through
        :func:`fold_async_counts`, so an audit built from raw HLO
        opcodes (async pairs included) reports one logical collective
        per pair."""
        folded = fold_async_counts(self.counts)
        return {k: folded.get(k, 0) for k in keys}

    def reduction_collectives(self) -> int:
        """Total reduction-collective occurrences (psum + reduce_scatter)
        — the count bucketing makes O(n_buckets) instead of O(n_leaves).
        An ``all-reduce-start``/``all-reduce-done`` pair is ONE
        occurrence (:func:`fold_async_counts`)."""
        folded = fold_async_counts(self.counts)
        return sum(folded.get(k, 0) for k in REDUCTION_PRIMITIVES)

    def summary(self) -> dict:
        return {
            "counts": dict(self.counts),
            "bytes_per_axis": dict(self.bytes_per_axis),
            "bytes_per_primitive": dict(self.bytes_per_primitive),
            "op_bytes": {k: list(v) for k, v in self.op_bytes.items()},
            "reduction_collectives": self.reduction_collectives(),
            "async_pairs": self.async_pairs,
            "overlap_fraction": self.overlap_fraction,
        }


def audit_jaxpr(jaxpr) -> CollectiveAudit:
    """Audit an already-traced (Closed)Jaxpr."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    counts: Dict[str, int] = {}
    per_axis: Dict[str, int] = {}
    per_prim: Dict[str, int] = {}
    op_bytes: Dict[str, List[int]] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMITIVES:
            continue
        counts[name] = counts.get(name, 0) + 1
        nbytes = _operand_bytes(eqn)
        per_prim[name] = per_prim.get(name, 0) + nbytes
        op_bytes.setdefault(name, []).append(nbytes)
        for ax in _eqn_axes(eqn):
            per_axis[str(ax)] = per_axis.get(str(ax), 0) + nbytes
    return CollectiveAudit(counts, per_axis, per_prim, op_bytes)


# ---------------------------------------------------------------------------
# HLO-text census — the post-compilation view, where async collectives
# appear as start/done pairs the jaxpr never contains.
# ---------------------------------------------------------------------------

#: HLO element type → itemsize, for payload bytes parsed out of HLO text.
_HLO_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

#: Instructions that are pure plumbing — NOT evidence of compute between
#: an async start and its done (the scheduler moving a tuple or a
#: parameter between the halves hides nothing).
_HLO_NONCOMPUTE = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "copy", "copy-start", "copy-done", "after-all",
    "partition-id", "replica-id", "opt-barrier", "domain",
))

_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.+)$"
)
_HLO_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
# ``%fused_computation.3 (p0: f32[8]) -> f32[8] {`` / ``ENTRY %main.7 (...``
_HLO_COMPUTATION_RE = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


class HloInstr(NamedTuple):
    """One instruction line of HLO text.  ``op_name`` is the name-stack
    path of its ``metadata={op_name="jit(..)/fwd-bwd/..."}`` (a fusion
    carries one op's of those it fused), ``""`` where the compiler kept
    none; ``operands`` holds every ``%name`` after the opcode, the
    computations it ``calls=`` among them; ``computation`` is the one
    the line stands in."""

    index: int
    name: str
    opcode: str
    operands: Tuple[str, ...]
    nbytes: int
    op_name: str
    computation: str = ""


def _hlo_shape_bytes(type_str: str) -> int:
    """Payload bytes of the FIRST array shape in an HLO type string —
    for a collective's result type this is the buffer it moves (async
    start tuples repeat the same buffer shape)."""
    m = _HLO_SHAPE_RE.search(type_str)
    if not m:
        return 0
    itemsize = _HLO_ITEMSIZE.get(m.group(1))
    if itemsize is None:
        return 0
    dims = m.group(2)
    elems = 1
    for d in dims.split(","):
        if d.strip():
            elems *= int(d)
    return elems * itemsize


def _parse_hlo_instr(index: int, line: str) -> Optional[HloInstr]:
    m = _HLO_INSTR_RE.match(line)
    if m is None:
        return None
    rest = m.group("rest").lstrip()
    # Skip the result type: either one balanced-paren tuple type or a
    # single array/scalar token; the opcode follows immediately.
    type_str = rest
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    type_str, rest = rest[: i + 1], rest[i + 1:].lstrip()
                    break
        else:
            return None
    else:
        parts = rest.split(None, 1)
        if len(parts) < 2:
            return None
        type_str, rest = parts[0], parts[1]
    om = re.match(r"([a-zA-Z][\w\-]*)\s*\(", rest)
    if om is None:
        return None
    # The op's own metadata comes last on the line (a custom call's
    # backend config may hold an empty ``metadata={}`` before it).
    op_names = _HLO_OP_NAME_RE.findall(rest)
    return HloInstr(
        index=index,
        name=m.group("name"),
        opcode=om.group(1),
        operands=tuple(re.findall(r"%([\w.\-]+)", rest)),
        nbytes=_hlo_shape_bytes(type_str),
        op_name=op_names[-1] if op_names else "",
    )


def hlo_instructions(hlo_text: str) -> List[HloInstr]:
    """Every instruction of every computation of an HLO module's text
    (``compiled.as_text()``), ``while``/``conditional`` bodies and fused
    computations included, in text order.  The one HLO-text parser: the
    collective census below and
    :func:`chainermn_tpu.observability.device_trace.scope_table` both
    read through it."""
    out = []
    computation = ""
    for i, line in enumerate(hlo_text.splitlines()):
        head = _HLO_COMPUTATION_RE.match(line)
        if head is not None:
            computation = head.group(1)
            continue
        ins = _parse_hlo_instr(i, line)
        if ins is not None:
            out.append(ins._replace(computation=computation))
    return out


def hlo_module_name(hlo_text: str) -> str:
    """``jit_train_step`` from ``HloModule jit_train_step, ...``."""
    m = _HLO_MODULE_RE.match(hlo_text.lstrip())
    return m.group(1) if m else ""


def audit_hlo_text(hlo_text: str) -> CollectiveAudit:
    """Census of compiled HLO text (``jitted.lower(...).compile()
    .as_text()``), the representation where the TPU compiler's async
    machinery splits collectives into start/done pairs.

    Folding rule: an ``X-start``/``X-done`` pair is ONE logical ``X``
    (counted under the jaxpr-primitive name via
    :data:`HLO_COLLECTIVE_OPS`), with the pair tallied in
    ``async_pairs``; an unmatched ``-start`` still counts once (the
    collective exists) and an unmatched ``-done`` never does.
    ``overlap_fraction`` is the fraction of matched pairs with at least
    one non-plumbing instruction scheduled strictly between start and
    done — the post-scheduler evidence that gradient collectives hide
    under backward compute.  ``bytes_per_axis`` stays empty (HLO has
    replica groups, not mesh-axis names); per-collective payload bytes
    land in ``op_bytes``/``bytes_per_primitive`` as usual.
    """
    instrs = hlo_instructions(hlo_text)
    by_name: Dict[str, HloInstr] = {ins.name: ins for ins in instrs}

    counts: Dict[str, int] = {}
    per_prim: Dict[str, int] = {}
    op_bytes: Dict[str, List[int]] = {}
    async_pairs = 0
    overlapped = 0
    consumed_dones = set()

    def _tally(prim: str, nbytes: int) -> None:
        counts[prim] = counts.get(prim, 0) + 1
        per_prim[prim] = per_prim.get(prim, 0) + nbytes
        op_bytes.setdefault(prim, []).append(nbytes)

    # Pair dones with their starts first (done references the start's
    # result by name), so the start-side walk knows which are paired.
    start_to_done: Dict[str, HloInstr] = {}
    for ins in instrs:
        if not ins.opcode.endswith(_ASYNC_DONE):
            continue
        base = ins.opcode[: -len(_ASYNC_DONE)]
        if base not in HLO_COLLECTIVE_OPS:
            continue
        for operand in ins.operands:
            src = by_name.get(operand)
            if src is not None and src.opcode == base + _ASYNC_START:
                start_to_done[src.name] = ins
                consumed_dones.add(ins.name)
                break

    for ins in instrs:
        op = ins.opcode
        if op in HLO_COLLECTIVE_OPS:
            _tally(HLO_COLLECTIVE_OPS[op], ins.nbytes)
            continue
        if op.endswith(_ASYNC_START):
            base = op[: -len(_ASYNC_START)]
            if base not in HLO_COLLECTIVE_OPS:
                continue
            _tally(HLO_COLLECTIVE_OPS[base], ins.nbytes)
            done = start_to_done.get(ins.name)
            if done is None:
                continue
            async_pairs += 1
            between = (
                other for other in instrs
                if ins.index < other.index < done.index
            )
            if any(
                o.opcode not in _HLO_NONCOMPUTE
                and o.opcode not in HLO_COLLECTIVE_OPS
                and not o.opcode.endswith((_ASYNC_START, _ASYNC_DONE))
                for o in between
            ):
                overlapped += 1
    return CollectiveAudit(
        counts=counts,
        bytes_per_axis={},
        bytes_per_primitive=per_prim,
        op_bytes=op_bytes,
        async_pairs=async_pairs,
        overlap_fraction=(overlapped / async_pairs) if async_pairs else 0.0,
    )


def audit_compiled(fn, *args, **kwargs) -> CollectiveAudit:
    """Compile ``fn(*args, **kwargs)`` (jitted or plain) and audit the
    OPTIMIZED HLO — the only level where async start/done pairs and the
    latency-hiding schedule are visible.  Args may be real arrays or
    ``jax.ShapeDtypeStruct``s; nothing executes."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    return audit_hlo_text(compiled.as_text())


class TracedStep(NamedTuple):
    """One abstract trace of a step function — the shared entry point the
    audit AND the collective linter (:mod:`chainermn_tpu.analysis`) build
    on, so a step is traced exactly once however it is wrapped.

    ``donate_argnums`` carries the jit wrapper's donation declaration when
    the AOT ``trace`` path supplied it; ``None`` means "unknown — look for
    ``pjit`` eqn ``donated_invars`` inside the jaxpr instead".
    """

    closed_jaxpr: Any
    donate_argnums: Optional[Tuple[int, ...]]


def trace_step(fn, *args, **kwargs) -> TracedStep:
    """Trace ``fn(*args, **kwargs)`` without executing it.

    Accepts plain callables AND already-``jax.jit``-wrapped ones: a
    callable with jit's AOT ``.trace`` surface is traced through it (one
    trace, reusing jit's cached machinery — no re-wrap double-trace),
    which also exposes its ``donate_argnums``, and an error from that
    trace is the caller's to see: re-tracing with ``make_jaxpr`` would
    lose the donation declaration and let the R005 audit pass on
    ``None``.  Everything else goes through ``jax.make_jaxpr``, kwargs
    included.  Args may be real arrays or ``jax.ShapeDtypeStruct``s."""
    import jax

    tracer = getattr(fn, "trace", None)
    if callable(tracer):
        tr = tracer(*args, **kwargs)
        return TracedStep(tr.jaxpr, tuple(tr.donate_argnums))
    return TracedStep(jax.make_jaxpr(fn)(*args, **kwargs), None)


def audit_fn(fn, *args, **kwargs) -> CollectiveAudit:
    """Trace ``fn(*args, **kwargs)`` (jitted or plain) and audit the
    resulting program.  Args may be real arrays or
    ``jax.ShapeDtypeStruct``s; nothing executes.  Delegates the tracing
    to :func:`trace_step` — the entry point shared with the collective
    linter — so jitted callables and kwargs take the single-trace path."""
    return audit_jaxpr(trace_step(fn, *args, **kwargs).closed_jaxpr)


def _allreduce_jaxpr(comm, nbytes: int, dtype):
    """The traced ``allreduce_grad`` lowering every per-communicator
    census is computed on: a rank-stacked (device_size, elems) buffer
    through the communicator's characteristic collective pattern."""
    import jax
    import jax.numpy as jnp

    n = comm.device_size
    elems = max(1, nbytes // np.dtype(dtype).itemsize)
    spec = comm._world_spec

    def body(tree):
        sq = jax.tree.map(lambda x: jnp.squeeze(x, 0), tree)
        out = comm.allreduce_grad(sq)
        return jax.tree.map(lambda x: x[None], out)

    return jax.make_jaxpr(comm.shard_map(
        body, in_specs=({"g": spec},), out_specs={"g": spec}
    ))({"g": jnp.ones((n, elems), dtype)})


def audit_allreduce(comm, nbytes: int, dtype=np.float32) -> CollectiveAudit:
    """Audit one communicator's gradient-allreduce path at a given
    per-device payload — the library home of
    ``benchmarks/allreduce_bench.py``'s
    ``allreduce_static_bytes_per_leg`` numbers."""
    return audit_jaxpr(_allreduce_jaxpr(comm, nbytes, dtype))


def audit_allreduce_tree(comm, tree) -> CollectiveAudit:
    """Audit ``allreduce_grad`` over a FULL gradient pytree.

    ``tree`` carries per-device leaf shapes (no leading rank axis) —
    arrays or ``jax.ShapeDtypeStruct``s; nothing executes.  This is the
    many-leaf generalization of :func:`audit_allreduce`: with bucketing
    on, ``reduction_collectives()`` is O(n_buckets) and ``op_bytes``
    holds each bucket's wire size; with ``bucket_bytes=0`` it shows the
    legacy per-leaf lowering for comparison.
    """
    import jax
    import jax.numpy as jnp

    n = comm.device_size
    spec = comm._world_spec
    stacked = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n,) + tuple(l.shape), l.dtype), tree
    )
    specs = jax.tree.map(lambda _: spec, stacked)

    def body(t):
        sq = jax.tree.map(lambda x: jnp.squeeze(x, 0), t)
        out = comm.allreduce_grad(sq)
        return jax.tree.map(lambda x: x[None], out)

    return audit_jaxpr(jax.make_jaxpr(comm.shard_map(
        body, in_specs=(specs,), out_specs=specs
    ))(stacked))


def assert_two_dimensional_inter_savings(profiles: dict,
                                         intra_size: int) -> None:
    """``profiles``: {communicator_name: bytes_per_axis dict}.  Asserts
    the 2D claim when both sides are present: two_dimensional's
    inter-axis operand bytes == flat's / intra_size (SURVEY §2.1
    two-dimensional row — the reference's rationale for the 2D algorithm
    on >1 GbE clusters)."""
    flat = next(
        (profiles[k] for k in ("flat", "xla_ici", "pure_nccl")
         if k in profiles), None,
    )
    td = profiles.get("two_dimensional")
    if flat is None or td is None:
        return
    flat_inter = flat.get("inter", 0)
    td_inter = td.get("inter", 0)
    assert flat_inter > 0 and td_inter > 0, (profiles,)
    assert td_inter * intra_size == flat_inter, (
        f"two_dimensional inter-axis bytes {td_inter} x intra "
        f"{intra_size} != flat's {flat_inter} — the 2D bandwidth claim "
        "does not hold in the traced lowering"
    )
