"""Device time by program scope — the program's one reader of a profiler
capture.

A capture's op events say *which instruction* ran and when; the compiled
program says *which scope* each instruction was traced under
(``metadata={op_name="jit(train_step)/fwd-bwd/jvp(TransformerLM)/..."}``
in ``compiled.as_text()``; a fusion carries its root's).  Instruction
names are unique within a program and the capture's op events are named
by them, so the join needs nothing but public API:

    scope_table(compiled)        {instruction name: op_name path}, and
                                 which path OWNS each instruction
    attribute(ops, table)        device seconds by phase, by region and,
                                 within ``fwd-bwd``, by owner
    idle_by_host_span(ops, host) idle seconds by ``chainermn:`` host span
                                 or compile stage of JAX's
    capture({name: jitted})      profile a caller's k steps, return one
                                 report (and hand it to the sinks)

Three readings of one step (the vocabulary is ``observability/spans.py``):

* **phase** — the outermost of ``fwd-bwd`` / ``allreduce`` /
  ``opt-update`` on an op's path.  Phases partition the busy time: with
  the time no phase claims (``unattributed``) they add up to ``busy``.
* **region** — the innermost kernel or allreduce-stage name
  (``flash-fwd``, ``fused-ce``, ``grad-pack``, ...): a finer cut of
  *part* of the busy time.  ``region_tiles`` puts beside a flash
  region's time the block geometry its kernel was built with and the
  tiles it finds live, visits and copies a head row
  (``spans.tiles_scope``, a component of the same paths).

* **owner** — within ``fwd-bwd``, the innermost name of the kernel
  regions AND the model's parts (``spans.MODEL_PARTS``: ``ffn``,
  ``norm``, ``mixer-proj``, ...) on the path that owns the instruction,
  ``"(none)"`` where that path names neither.  Owners partition the
  phase.  ``pass`` cuts the same seconds by forward / recompute /
  backward (the path's ``transpose(...)`` wrappers and
  ``rematted_computation``), ``layer`` by the path's ``layer_<i>``.
  ``within`` nests them: under every scope name on the owning path, the
  seconds by owner — ``within["moe-layer"]`` is an expert layer by its
  parts, ``within["attn-window"]["flash-fwd"]`` the forward kernel's time
  in the windowed rows and ``within["attn-mixer"]["flash-fwd"]`` in the
  full ones.  Not a partition: an op counts under each name it is in.

A fusion carries the ``op_name`` of ONE of the ops the compiler fused
into it, and the phase and region readings take that one: a
weight-gradient matmul with the AdamW update fused in counts whole as
``fwd-bwd`` (the matmul's) on the TPU compiler of this toolchain.  The
owner reading decides for itself: a fusion is owned by its heaviest op —
its ``convolution`` / ``dot`` (the largest where it holds several), else
the instruction with the largest result — and an instruction the
compiler gave no ``op_name`` (``copy``, ``bitcast``) by its one
consumer, else by its first operand's producer (``inherited``).  What
no reading can split is said beside it: ``shared`` is, of each owner's
seconds, those spent in fusions that hold ops of a second (phase,
region) — a bound, not a split — and ``mixed`` its phase-level case,
the busy time of fusions that hold ops of a second phase.
Container ops (``while``, ``conditional``, ``call``) span the ops of
their bodies and are left out; where two ops overlap, the time goes to
the one that started last.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

from chainermn_tpu.observability import hlo_audit, spans

CONTAINERS = ("while", "conditional", "call")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: A capture is refused (``attribute`` still returns, readers must not
#: report) when less than this share of the busy time joins to the table.
MIN_JOINED_SHARE = 0.98

#: An op is its program run's while it ends no later than the run's own
#: event by this much: both ends are ``(start_ns + duration_ns) * 1e-9``
#: of different events, and a program's LAST op ends with the program to
#: the rounding (a kernel under study that ends its program was dropped
#: in one call of three, PERF.md section 6, PR 48).
END_TOLERANCE_S = 1e-6

#: The owner of ``fwd-bwd`` time whose owning path names no region.
NO_OWNER = "(none)"
PASSES = ("forward", "recompute", "backward")

_WRAPPER = re.compile(r"^[\w\-.]+\((.*)\)$")
_MODULE_EVENT = re.compile(r"^([\w.\-]+?)(?:\(\d+\))?$")
_LAYER = re.compile(r"(?:^|/)layer_(\d+)(?:/|$)")
_NUMBERED = re.compile(r"\.\d+$")

Triple = Tuple[str, float, float]


class ScopeTable(dict):
    """``{instruction name: op_name path}`` of one compiled program.
    ``containers`` holds the names of its ``while`` / ``conditional`` /
    ``call`` instructions, ``mixed`` those of the fusions that hold ops
    of another step phase than the one the fusion itself is named under,
    ``program`` the module's name (``jit_train_step``), ``tiles`` the
    distinct block geometries its kernels were built with, by region
    (``spans.tiles_scope``).  For the owner reading: ``owner_paths`` the
    path that owns an instruction where that is not its own
    (:meth:`owner_path`), ``owners_in`` the distinct ``owner(path)`` among
    the ops of each fusion, ``inherited`` the instructions whose own path
    names no phase and that took a neighbour's.  ``tiles_within`` is
    ``tiles`` by the other scope names on the kernel's path (``{outer:
    {region: [geometry]}}``): the census of the flash calls of a
    windowed row apart from a full row's."""

    def __init__(self, paths=(), containers=(), program="", mixed=(),
                 owner_paths=(), owners_in=(), inherited=()):
        super().__init__(paths)
        self.containers = frozenset(containers)
        self.mixed = frozenset(mixed)
        self.program = program
        self.owner_paths = dict(owner_paths)
        self.owners_in = dict(owners_in)
        self.inherited = frozenset(inherited)
        self.tiles: Dict[str, List[dict]] = {}
        self.tiles_within: Dict[str, Dict[str, List[dict]]] = {}
        for path in self.values():
            found = [t for t in map(spans.parse_tiles,
                                    scope_components(path)) if t]
            region = classify(path)[1]
            if not found or region is None:
                continue
            by_region = [self.tiles] + [
                self.tiles_within.setdefault(outer, {})
                for outer in scopes_on(path) if outer != region]
            for tiles in by_region:
                seen = tiles.setdefault(region, [])
                if found[-1] not in seen:
                    seen.append(found[-1])

    def owner_path(self, name: str) -> str:
        """The path that owns an instruction: its heaviest op's for a
        fusion, a neighbour's where it has none, else its own."""
        return self.owner_paths.get(name) or self.get(name, "")


def _heaviest(work) -> str:
    """The path of the op a fusion is owned by: its ``convolution`` /
    ``dot`` where it holds one, else the instruction with the largest
    result (the one nearest the root among equals)."""
    pool = [i for i in work if i.opcode in ("convolution", "dot")] or work
    if not pool:
        return ""
    return max(pool, key=lambda i: (i.nbytes, i.index)).op_name


def scope_table(compiled) -> ScopeTable:
    """The table of a compiled program (``jitted.lower(...).compile()``)
    or of its ``as_text()``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    instructions = hlo_audit.hlo_instructions(text)
    paths, containers = {}, set()
    by_name, inside, users = {}, {}, {}
    for ins in instructions:
        paths[ins.name] = ins.op_name
        by_name[ins.name] = ins
        inside.setdefault(ins.computation, []).append(ins)
        if ins.opcode in CONTAINERS:
            containers.add(ins.name)

    def producers(ins):
        """The operands of ``ins`` that are instructions beside it (a
        computation's parameters are its inputs, not work)."""
        for name in ins.operands:
            made = by_name.get(name)
            if (made is not None and made.computation == ins.computation
                    and made.opcode != "parameter"):
                yield made

    for ins in instructions:
        for made in producers(ins):
            users.setdefault(made.name, []).append(ins)

    mixed, owner_paths, owners_in, fused = set(), {}, {}, set()

    def fused_into(ins, nested=False):
        """``(instruction, nested)`` over a fusion's computation (the
        last computation it names: ``calls=``) and the computations of
        the fusions nested in it."""
        for called in [c for c in ins.operands if c in inside][-1:]:
            fused.add(called)
            for i in inside[called]:
                yield i, nested
                if i.opcode == "fusion":
                    yield from fused_into(i, True)

    # One loop over the fused computations: who is in each fusion (its
    # phase-level case is ``mixed``, read off the fusion's own
    # computation as it always was), and which of them owns it.  A
    # constant is no work and, shared between fusions, keeps the path of
    # any one use: it owns nothing.  Nor does an op whose path names no
    # step phase (traced inside a jitted helper, it may start there).
    for ins in instructions:
        if ins.opcode != "fusion":
            continue
        body = list(fused_into(ins))
        phases = {classify(i.op_name)[0] for i, nested in body
                  if not nested} - {None}
        if phases - {classify(ins.op_name)[0]}:
            mixed.add(ins.name)
        work = [i for i, _ in body
                if i.opcode not in ("parameter", "constant")
                and owner(i.op_name)[0] is not None]
        owners_in[ins.name] = frozenset(owner(i.op_name) for i in work)
        heavy = _heaviest(work)
        if heavy and heavy != ins.op_name:
            owner_paths[ins.name] = heavy

    # An instruction whose own path names no phase (none at all on a
    # ``copy`` the compiler inserted, an argument's name on a prefetch)
    # takes its one consumer's owner, else its first operand's
    # producer's, else its first consumer's.
    inherited = set()

    def phased(path):
        return owner(path)[0] is not None

    def resolve(name, seen):
        path = owner_paths.get(name) or paths[name]
        if phased(path) or name in seen:
            return path
        seen.add(name)
        after = users.get(name, ())
        first = next(producers(by_name[name]), None)
        for near in (after[0] if len(after) == 1 else None, first,
                     after[0] if after else None):
            found = resolve(near.name, seen) if near is not None else ""
            if phased(found):
                owner_paths[name] = found
                inherited.add(name)
                return found
        return path

    for ins in instructions:
        if (ins.computation not in fused and ins.name not in containers
                and ins.opcode != "parameter"):
            resolve(ins.name, set())
    return ScopeTable(paths, containers, hlo_audit.hlo_module_name(text),
                      mixed, owner_paths, owners_in, inherited)


def instruction_name(text: str) -> str:
    """The instruction an op event is named by.  On this toolchain the
    event's name is the whole instruction text (``%fusion.12 = f32[..]
    fusion(...)``); a bare name passes through."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.strip().removeprefix("ROOT ").lstrip("%")


def scope_components(path: str) -> List[str]:
    """The components of an ``op_name`` path with the transformation
    wrappers taken off: ``transpose(jvp(fwd-bwd))`` -> ``fwd-bwd``,
    ``jvp()`` -> nothing."""
    out = []
    for part in path.split("/"):
        while True:
            m = _WRAPPER.match(part)
            if m is None:
                break
            part = m.group(1)
        if part:
            out.append(part)
    return out


def _outermost_phase_innermost(path: str, named):
    phase = name = None
    for part in scope_components(path):
        if phase is None and part in spans.STEP_PHASES:
            phase = part
        elif part not in spans.STEP_PHASES and named(part):
            name = part
    return phase, name


@functools.lru_cache(maxsize=1 << 16)
def classify(path: str) -> Tuple[Optional[str], Optional[str]]:
    """``(phase, region)`` of a path: the outermost step phase and the
    innermost kernel / allreduce-stage name, ``None`` where it has none."""
    return _outermost_phase_innermost(path, spans.is_region)


@functools.lru_cache(maxsize=1 << 16)
def owner(path: str) -> Tuple[Optional[str], Optional[str]]:
    """``(phase, owner)`` of a path: as :func:`classify`, the innermost
    name taken of the kernel regions and the model's parts together."""
    return _outermost_phase_innermost(path, spans.is_scope)


@functools.lru_cache(maxsize=1 << 16)
def scopes_on(path: str) -> Tuple[str, ...]:
    """The kernel regions and model parts a path passes through,
    outermost first (the last is its :func:`owner`)."""
    return tuple(part for part in scope_components(path)
                 if part not in spans.STEP_PHASES and spans.is_scope(part))


@functools.lru_cache(maxsize=1 << 16)
def pass_of(path: str) -> str:
    """Which pass a ``fwd-bwd`` path belongs to: ``recompute`` under
    ``jax.checkpoint``'s ``rematted_computation``, ``backward`` under a
    ``transpose(...)`` wrapper, else ``forward``."""
    parts = path.split("/")
    if "rematted_computation" in parts:
        return "recompute"
    if any(part.startswith("transpose(") for part in parts):
        return "backward"
    return "forward"


@functools.lru_cache(maxsize=1 << 16)
def layer_of(path: str) -> Optional[str]:
    """``"3"`` of a path through ``layer_3`` (``TransformerLM`` names its
    blocks so), else None."""
    m = _LAYER.search(path)
    return m.group(1) if m else None


def attribute(ops: Iterable[Triple], table: ScopeTable) -> dict:
    """Device seconds of one device's ops by scope.

    ``ops`` are ``(name, start, end)`` triples (seconds; the name an
    instruction's, or its whole text).  Returns ``{"phase": {name: s},
    "region": {name: s}, "unattributed": s, "joined": s, "busy": s}``:
    ``busy`` is the union of the op intervals, ``joined`` the part of it
    whose instruction the table holds, ``unattributed`` the part whose
    instruction it does not hold or whose path names no phase,
    ``mixed`` the part spent in fusions that also hold ops of another
    phase (the compiler names a fusion after one of the ops it fused, so
    a weight-gradient matmul with the optimizer update fused in counts
    whole under one of the two: ``mixed`` bounds what the phase reading
    cannot split).

    Beside them, over the ops whose OWNING path (``table.owner_path``)
    is in ``fwd-bwd``: ``"owner": {name or "(none)": s}``, a partition of
    those seconds; ``"shared": {name: s}``, of each owner's seconds those
    spent in fusions that hold ops of a second (phase, region), and
    ``"shared_fusions": {instruction: s}`` the same by fusion;
    ``"inherited"``, those of instructions that took a neighbour's path;
    ``"pass": {"forward" | "recompute" | "backward": {name: s}}`` and
    ``"layer": {"<i>": s}``; ``"within": {scope: {owner: s}}``, each
    owner's seconds under every scope name on its owning path (its own
    among them).  ``"unowned"`` is the busy time no phase
    owns by that rule (what ``unattributed`` is by the fusion's own
    name).
    """
    live = []
    for name, start, end in ops:
        key = instruction_name(name)
        if key in table.containers or end <= start:
            continue
        live.append((start, end, key))
    live.sort()
    out = {"phase": {}, "region": {}, "unattributed": 0.0, "joined": 0.0,
           "mixed": 0.0, "busy": 0.0,
           "owner": {}, "shared": {}, "shared_fusions": {},
           "inherited": 0.0, "unowned": 0.0,
           "pass": {name: {} for name in PASSES}, "layer": {},
           "within": {}}

    def add(to, name, seconds):
        to[name] = to.get(name, 0.0) + seconds

    def credit_owner(key, seconds):
        path = table.owner_path(key)
        phase, name = owner(path)
        if phase is None:
            out["unowned"] += seconds
        if phase != "fwd-bwd":
            return
        name = name or NO_OWNER
        add(out["owner"], name, seconds)
        add(out["pass"][pass_of(path)], name, seconds)
        for outer in set(scopes_on(path)):
            add(out["within"].setdefault(outer, {}), name, seconds)
        if len(table.owners_in.get(key, ())) > 1:
            add(out["shared"], name, seconds)
            add(out["shared_fusions"], key, seconds)
        if key in table.inherited:
            out["inherited"] += seconds
        layer = layer_of(path)
        if layer is not None:
            add(out["layer"], layer, seconds)

    def credit(key, seconds):
        out["busy"] += seconds
        credit_owner(key, seconds)
        if key in table.mixed:
            out["mixed"] += seconds
        path = table.get(key)
        if path is None:
            out["unattributed"] += seconds
            return
        out["joined"] += seconds
        phase, region = classify(path)
        if phase is None:
            out["unattributed"] += seconds
        else:
            add(out["phase"], phase, seconds)
        if region is not None:
            add(out["region"], region, seconds)

    # Sweep in start order; at every instant the time goes to the running
    # op that started last, so that the readings partition the union.
    stack: list = []
    now = 0.0
    for start, end, key in live + [(float("inf"), float("inf"), None)]:
        while stack and now < start:
            top_end, top_key = stack[-1]
            if top_end <= now:
                stack.pop()
                continue
            until = min(top_end, start)
            credit(top_key, until - now)
            now = until
        now = start
        stack.append((end, key))
    return out


def _merged(ops: Iterable[Triple]) -> List[List[float]]:
    """The op intervals merged into disjoint busy intervals, in order."""
    busy: list = []
    for _, start, end in sorted(ops, key=lambda o: o[1]):
        if busy and start <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], end)
        else:
            busy.append([start, end])
    return busy


def idle_by_host_span(ops: Iterable[Triple],
                      host_events: Iterable[Triple]) -> Dict[str, float]:
    """Idle seconds of one device between its first and last op, each gap
    put down to a host event on the capture's one clock
    (``spans.host_label``: a ``chainermn:`` annotation by its name, a
    compile-stage event of JAX's as ``"compile"`` / ``"lower"``): the
    innermost of those that cover more than half of it — a recompilation
    inside a step reads ``compile``, not ``chainermn:train_step`` — else
    the one that covers most of it, ``"unannotated"`` where none does."""
    busy = _merged(ops)
    host = [(label, start, end) for name, start, end in host_events
            if (label := spans.host_label(name)) is not None]
    out: Dict[str, float] = {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        best, label, inner = 0.0, "unannotated", None
        for name, start, end in host:
            cover = min(gap_end, end) - max(gap_start, start)
            if cover > best:
                best, label = cover, name
            if cover > (gap_end - gap_start) / 2 and (
                    inner is None or end - start < inner[0]):
                inner = (end - start, name)
        if inner is not None:
            label = inner[1]
        out[label] = out.get(label, 0.0) + (gap_end - gap_start)
    return out


def _mean(values) -> float:
    values = list(values)
    return sum(values) / len(values) if values else 0.0


def _abstract(tree):
    import jax

    def leaf(x):
        if not isinstance(x, jax.Array):
            return x
        # An array left where ``jnp.asarray`` put it is not committed to
        # that device: the program places it, so no sharding is noted.
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)

    return jax.tree.map(leaf, tree)


def read_capture(path: str):
    """``(devices, host)`` of an ``.xplane.pb`` file (``.gz`` accepted),
    through ``jax.profiler.ProfileData``: per device plane its op and
    program (module) events as ``(name, start, end)`` triples in seconds,
    and the host threads' ``chainermn:`` annotations and compile-stage
    events (``spans.host_label``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)

    def triples(line):
        return [(ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events]

    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append({
                    "name": plane.name,
                    "ops": triples(lines[OPS_LINE]),
                    "modules": (triples(lines[MODULES_LINE])
                                if MODULES_LINE in lines else []),
                })
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.extend(t for t in triples(ln)
                            if spans.host_label(t[0]) is not None)
    return devices, host


def report_from(devices, host, tables: Dict[str, ScopeTable]) -> dict:
    """One report from what :func:`read_capture` returns and the scope
    table of each named program.  Per program: how often it ran and, a
    call, its device ms by phase and by region (mean over devices).  Ops
    of programs that were not named count as busy time of ``"other"``."""
    by_module = {t.program: name for name, t in tables.items()}
    programs: Dict[str, dict] = {}
    idle: Dict[str, float] = {}
    window = busy = 0.0
    for dev in devices:
        ops = sorted(dev["ops"], key=lambda o: o[1])
        # Programs run one after another on a device: an op belongs to
        # the last program run that started before it.
        runs = []
        for mod_name, start, end in sorted(dev["modules"],
                                           key=lambda m: m[1]):
            m = _MODULE_EVENT.match(mod_name)
            runs.append((start, end, by_module.get(
                m.group(1) if m else mod_name, "other")))
        starts = [r[0] for r in runs]
        inside: Dict[str, list] = {}
        for op in ops:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[2] <= runs[i][1] + END_TOLERANCE_S:
                inside.setdefault(runs[i][2], []).append(op)
        for name, picked in inside.items():
            got = attribute(picked, tables.get(name, ScopeTable()))
            got["calls"] = sum(1 for r in runs if r[2] == name)
            programs.setdefault(name, []).append(got)
        merged = _merged(ops)
        if merged:
            busy += sum(end - start for start, end in merged)
            window += merged[-1][1] - merged[0][0]
        for label, seconds in idle_by_host_span(ops, host).items():
            idle[label] = idle.get(label, 0.0) + seconds
    n = max(len(devices), 1)
    out = {"devices": len(devices), "window_s": window / n,
           "busy_s": busy / n,
           "idle_share": 1.0 - busy / window if window else None,
           "idle_by_host_span_ms": {k: v / n * 1e3
                                    for k, v in sorted(idle.items())},
           "programs": {}}
    for name, per_dev in sorted(programs.items()):
        calls = _mean(g["calls"] for g in per_dev)
        table = tables.get(name, ScopeTable())

        def ms(field, key=None, rows=per_dev):
            values = (g[field] if key is None else g[field].get(key, 0.0)
                      for g in rows)
            return _mean(values) / max(calls, 1) * 1e3

        def ms_by_name(field, rows=per_dev):
            names = sorted({k for g in rows for k in g[field]})
            return {k: ms(field, k, rows) for k in names}

        total = _mean(g["busy"] for g in per_dev)
        out["programs"][name] = {
            "calls": calls,
            "busy_ms": ms("busy"),
            "unattributed_ms": ms("unattributed"),
            "mixed_ms": ms("mixed"),
            "joined_share": (_mean(g["joined"] for g in per_dev) / total
                             if total else None),
            "phase_ms": ms_by_name("phase"),
            "region_ms": ms_by_name("region"),
            "region_tiles": table.tiles,
            "region_tiles_within": table.tiles_within,
            "owner_ms": ms_by_name("owner"),
            "shared_ms": ms_by_name("shared"),
            "inherited_ms": ms("inherited"),
            "unowned_ms": ms("unowned"),
            "pass_ms": {p: ms_by_name(p, [g["pass"] for g in per_dev])
                        for p in PASSES},
            "layer_ms": dict(sorted(ms_by_name("layer").items(),
                                    key=lambda kv: int(kv[0]))),
            "within_ms": {
                outer: ms_by_name(outer, [
                    {outer: g["within"].get(outer, {})} for g in per_dev])
                for outer in sorted({k for g in per_dev
                                     for k in g["within"]})},
            "shared_fusions": _largest_shared(
                ms_by_name("shared_fusions"), table),
        }
    return out


def _largest_shared(ms_by_fusion: Dict[str, float], table: ScopeTable,
                    keep: int = 10) -> List[dict]:
    """The fusions with most time among those with two or more owners,
    the instances of one fusion (``convolution_add_fusion.3``, ``.7``:
    one a layer) with the same owners added up; each with its owner and
    with every (phase, region) found in it."""
    kinds: Dict[tuple, dict] = {}
    for name, value in ms_by_fusion.items():
        owners = sorted("/".join(part or NO_OWNER for part in pair)
                        for pair in table.owners_in[name])
        owned_by = owner(table.owner_path(name))[1] or NO_OWNER
        kind = (_NUMBERED.sub("", name), owned_by, tuple(owners))
        row = kinds.setdefault(kind, {
            "fusion": kind[0], "owner": owned_by, "owners": owners,
            "count": 0, "ms": 0.0})
        row["count"] += 1
        row["ms"] += value
    return sorted(kinds.values(), key=lambda r: -r["ms"])[:keep]


class Capture:
    """A profiler session around a caller's few steps.

    ``programs`` maps a name to a jitted callable (anything with
    ``lower``: ``jax.jit`` results, the steps ``make_train_step``
    builds).  Call them through ``cap[name]`` inside the session: that
    notes the abstract arguments (shapes, dtypes, shardings — taken
    before the call, donated buffers are gone after it), from which
    :meth:`stop` lowers and compiles each program once more for its
    scope table (a compilation-cache hit).

        with device_trace.capture({"train_step": step}) as cap:
            for _ in range(4):
                params, state, loss = cap["train_step"](params, state, batch)
            jax.block_until_ready(loss)
        cap.report["programs"]["train_step"]["phase_ms"]

    End the block with the device drained, or the last steps are cut.
    The Python tracer is off (it slows the host by a few per cent and
    nothing here reads it).  On exit the report goes to the sinks that
    are installed: one ``device_profile`` row of the current
    ``StepRecorder``, ``device/<program>/<scope>_ms`` and
    ``device/<program>/owner/<name>_ms`` scalars of the current
    ``Reporter``.
    """

    def __init__(self, programs: dict, logdir: Optional[str] = None):
        self.programs = dict(programs)
        self.logdir = logdir
        self.report: Optional[dict] = None
        self._args: Dict[str, tuple] = {}
        self._dir: Optional[str] = None

    def __getitem__(self, name):
        fn = self.programs[name]

        def noted(*args, **kwargs):
            self._args[name] = (_abstract(args), _abstract(kwargs))
            return fn(*args, **kwargs)

        return noted

    def start(self) -> "Capture":
        import jax

        self._dir = self.logdir or tempfile.mkdtemp(
            prefix="chainermn_tpu_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=options)
        return self

    def _end_session(self, read: bool):
        import jax

        jax.profiler.stop_trace()
        try:
            if not read:
                return None
            found = glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError(
                    f"the profiler wrote no trace to {self._dir}")
            return read_capture(max(found, key=os.path.getmtime))
        finally:
            if self.logdir is None:
                shutil.rmtree(self._dir, ignore_errors=True)

    def stop(self) -> dict:
        devices, host = self._end_session(read=True)
        tables = {}
        for name, (args, kwargs) in self._args.items():
            compiled = self.programs[name].lower(*args, **kwargs).compile()
            tables[name] = scope_table(compiled)
        self.report = report_from(devices, host, tables)
        publish(self.report)
        return self.report

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.stop()
        else:
            self._end_session(read=False)
        return False


#: ``with capture({"train_step": step}) as cap: ...`` — see :class:`Capture`.
capture = Capture


def publish(report: dict) -> None:
    """Hand a report to the installed sinks (none installed: nothing)."""
    from chainermn_tpu.observability import reporter as _reporter
    from chainermn_tpu.observability import step_log as _step_log

    rec = _step_log.current_recorder()
    if rec is not None:
        rec.record("device_profile", **report)
    rep = _reporter.get_reporter()
    if rep is not None:
        for program, row in report["programs"].items():
            for kind in ("phase_ms", "region_ms"):
                for scope, value in row[kind].items():
                    rep.observe(f"device/{program}/{scope}_ms", value)
            for scope, value in row["owner_ms"].items():
                rep.observe(f"device/{program}/owner/{scope}_ms", value)
            rep.observe(f"device/{program}/unattributed_ms",
                        row["unattributed_ms"])
            rep.observe(f"device/{program}/busy_ms", row["busy_ms"])
