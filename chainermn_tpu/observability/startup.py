"""The start-up ledger — where a process's time goes before its first
timed step, recorded where the work happens.

One ledger a process, always on, in memory.  It is fed from four sides
and read from three:

* the package stamps its own import (``chainermn_tpu/__init__.py``:
  first statement to last, ``import jax`` nested in it) and the ledger
  asks the OS when the process began (``/proc/self/stat``);
* the library's boundaries leave **marks** — a timestamp the first time
  each is crossed (:func:`mark`: ``setup_compilation_cache``,
  ``build_mesh``, ``create_communicator``,
  ``create_multi_node_optimizer``, ``make_train_step`` and its
  ``.return``) — and **calls**: start and end of the first
  :data:`Ledger.CALLS` calls of ``train_step`` and ``global_batch``
  (:func:`open_call` / :func:`close`; from then on the wrapper pays one
  set lookup);
* ``jax.monitoring`` — this module is the program's ONE bridge to it,
  registered once when the package's import ends (:func:`finish_import`):
  a time-span listener for the three compile stages (``trace``,
  ``lower``, ``compile``, each with JAX's ``fun_name`` as ``program``),
  a duration listener for the cache's retrieval and saved seconds and an
  event listener for the cache's requests, hits and misses, which are
  attached to the ``compile`` span they fire inside.  The listeners fire
  inside the compile path: they append under a lock, do no IO and never
  raise;
* an entry point's own :func:`phase` — a span that is also an
  ``annotate(name)``, so a profiler capture of start-up has the phases on
  its clock beside JAX's own compile-stage host events.

Readers: :meth:`Ledger.summary` (the operator's report: phases, marks,
calls, a row a program, recompilations, self times);
:class:`~chainermn_tpu.observability.step_log.StepRecorder`, which drains
the compile-stage spans into its ``compile`` rows (:meth:`Ledger.since`);
and the benchmark's ``setup.*`` readers (``chipbench/setup_reduce.py``).

One clock: ``time.perf_counter``.  JAX stamps its spans with
``time.time()``; they are brought over by one offset sampled when this
module is imported.  A span is ``(id, kind, name, start, end, program,
thread, parent)``: ``parent`` is the id of the phase or call that was
open on the span's thread when it began.  What is kept is bounded.  JAX
reports a ``trace`` for every jitted function a program's tracing enters
(``matmul``, ``_where``, a layer's kernels' wrappers: 15,000 events in a
run of an eight-layer cell): a ``trace`` span that lies inside another of
its thread is folded into a count and a sum by name, not kept, so a
program costs three spans.  Of the kept spans the first
:data:`Ledger.HEAD` stay (start-up), after them the newest
:data:`Ledger.TAIL` (what a recorder has yet to drain); the import, the
phases (the first :data:`Ledger.PHASES`) and the calls are held apart
and never fall out.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import re
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

_WALL_TO_PERF = time.perf_counter() - time.time()

#: The compile stages by the event JAX records for each
#: (``jax/_src/dispatch.py``).
STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
STAGES = tuple(STAGE_EVENTS.values())
EVENT_OF_STAGE = {stage: event for event, stage in STAGE_EVENTS.items()}

#: The persistent cache's events (``jax/_src/compiler.py``,
#: ``compilation_cache.py``), fired inside a ``compile`` span.  A request
#: is a compilation that asked the cache; a hit loaded its executable; a
#: miss compiled and was long enough to be written (so a request that is
#: neither was too quick to keep, and will never hit).
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_CACHE_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


class Span:
    """One interval of the ledger, on ``time.perf_counter``'s clock."""

    __slots__ = ("id", "kind", "name", "start", "end", "program", "thread",
                 "parent", "index", "cache")

    def __init__(self, id, kind, name, start, end=None, program=None,
                 thread=None, parent=None, index=None, cache=None):
        self.id, self.kind, self.name = id, kind, name
        self.start, self.end = start, end
        self.program, self.thread, self.parent = program, thread, parent
        self.index = index    # of a call: 0 is the first
        self.cache = cache    # of a compile: {"state": ..., counts, seconds}

    @property
    def cache_state(self) -> Optional[str]:
        return self.cache["state"] if self.cache else None


_MODULE_NAME = re.compile(r"^(?:jit|pmap)(?:\((.*)\)|_(.*))$")


def program_name(fun_name) -> Optional[str]:
    """JAX's ``fun_name`` as the program's name: the traced function's
    for ``trace``, the module's (``jit(<name>)``, ``jit_<name>``) for
    ``lower`` and ``compile`` — one name for the three."""
    if fun_name is None:
        return None
    m = _MODULE_NAME.match(str(fun_name))
    return (m.group(1) or m.group(2)) if m else str(fun_name)


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Seconds covered by at least one of the intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def _os_process_start() -> Optional[float]:
    """When the OS started this process, on ``perf_counter``'s clock:
    ``/proc/self/stat`` field 22 (clock ticks after boot) against the
    boot clock now.  ``None`` where it cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            after_comm = f.read().rsplit(")", 1)[1].split()
        started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        since_boot = time.clock_gettime(time.CLOCK_BOOTTIME)
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - (since_boot - started)


class Ledger:
    """The spans, marks and calls of one process (module docstring)."""

    HEAD = 4096
    TAIL = 2048
    CALLS = 16
    PHASES = 256

    def __init__(self, imported: Optional[dict] = None):
        self._lock = threading.Lock()
        self._head: List[Span] = []
        self._tail: collections.deque = collections.deque(maxlen=self.TAIL)
        self._closed = 0          # spans ever kept: the drains' cursor
        self._ids = itertools.count(1)
        self._phases: List[Span] = []       # import and phases, open ones too
        self._calls: Dict[str, List[Span]] = {}
        self._calls_full: set = set()       # names whose record is full
        self._local = threading.local()
        # Per thread, the ``trace`` spans that nothing has yet been seen
        # to contain, as (start, end, fun_name, parent); and what was
        # folded: name -> [count, seconds].
        self._pending_traces: Dict[int, list] = {}
        self._nested: Dict[str, list] = {}
        self.marks: Dict[str, float] = {}
        self.listener_s = 0.0
        self.listener_calls = 0
        self.process_start: Optional[float] = None
        self.imported: Optional[dict] = None
        if imported or _IMPORTED:
            self.record_import(imported or _IMPORTED)

    # -- feeding -------------------------------------------------------
    def record_import(self, imported: dict) -> None:
        """The package's import (``first`` statement to ``last``, with
        ``jax``'s own inside it) and, before it, the process's start."""
        first = imported["first"]
        began = _os_process_start()
        if began is None or began > first:
            began = first
        self.imported, self.process_start = imported, began
        top = Span(next(self._ids), "import", "import", first,
                   imported["last"])
        self.add(Span(next(self._ids), "import", "import_jax",
                      *imported["jax"], parent=top.id))
        self.add(top)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _keep(self, span: Span) -> None:
        if len(self._head) < self.HEAD:
            self._head.append(span)
        else:
            self._tail.append(span)
        self._closed += 1

    def add(self, span: Span) -> None:
        """A closed span joins the record (one made by hand too: a call
        among the calls, a phase among the phases)."""
        with self._lock:
            if span.kind == "call":
                held = self._calls.setdefault(span.name, [])
            else:
                held = self._phases if span.kind != "stage" else None
            if held is not None and span not in held and (
                    len(held) < self.PHASES):
                held.append(span)
            self._keep(span)

    def mark(self, name: str) -> None:
        """A boundary's timestamp, the first time it is crossed."""
        if name not in self.marks:
            self.marks.setdefault(name, time.perf_counter())

    def begin(self, kind: str, name: str) -> Optional[Span]:
        """Open a span of ``kind`` ``"phase"`` or ``"call"`` on this
        thread (``None``: a call past the record)."""
        stack = self._stack()
        span = Span(next(self._ids), kind, name, time.perf_counter(),
                    thread=threading.get_ident(),
                    parent=stack[-1] if stack else None)
        with self._lock:
            if kind == "call":
                calls = self._calls.setdefault(name, [])
                if len(calls) >= self.CALLS:
                    return None
                span.index = len(calls)
                calls.append(span)
                if len(calls) >= self.CALLS:
                    self._calls_full.add(name)
            elif len(self._phases) < self.PHASES:
                self._phases.append(span)
        stack.append(span.id)
        return span

    def close(self, span: Span) -> None:
        span.end = time.perf_counter()
        stack = self._stack()
        if span.id in stack:
            del stack[stack.index(span.id):]
        self.add(span)

    def open_call(self, name: str) -> Optional[Span]:
        """The span of one of the first :data:`CALLS` calls of ``name``,
        ``None`` from then on."""
        if name in self._calls_full:
            return None
        return self.begin("call", name)

    # -- jax.monitoring ------------------------------------------------
    def account(self, seconds: float) -> None:
        """What one listener call cost (the ledger's own overhead)."""
        with self._lock:
            self.listener_calls += 1
            self.listener_s += seconds

    def _pending(self) -> dict:
        pending = getattr(self._local, "cache", None)
        if pending is None:
            pending = self._local.cache = {}
        return pending

    def on_time_span(self, event, start_time, end_time, **kwargs) -> None:
        stage = STAGE_EVENTS.get(event)
        if stage is None:
            return
        start = float(start_time) + _WALL_TO_PERF
        end = float(end_time) + _WALL_TO_PERF
        thread = threading.get_ident()
        stack = getattr(self._local, "stack", None)
        parent = stack[-1] if stack else None
        fun_name = kwargs.get("fun_name")
        if stage == "trace":
            with self._lock:
                pending = self._pending_traces.get(thread)
                if pending is None:
                    pending = self._pending_traces[thread] = []
                # A function traced inside this one ended before it and
                # began after it: fold it.
                while pending and pending[-1][0] >= start:
                    s0, e0, name0, _ = pending.pop()
                    folded = self._nested.get(name0)
                    if folded is None:
                        self._nested[name0] = [1, e0 - s0]
                    else:
                        folded[0] += 1
                        folded[1] += e0 - s0
                pending.append((start, end, fun_name, parent))
            return
        span = Span(next(self._ids), "stage", stage, start, end,
                    program=program_name(fun_name), thread=thread,
                    parent=parent)
        if stage == "compile":
            seen, self._local.cache = self._pending(), {}
            state = ("hit" if seen.get("hits") else
                     "miss" if seen.get("misses") else "uncached")
            span.cache = dict(seen, state=state)
        with self._lock:
            self._settle(thread)
            self._keep(span)

    def _settle(self, thread=None) -> None:
        """The pending ``trace`` spans of ``thread`` (of every thread)
        are kept: a ``lower`` has followed, or someone reads.  Caller
        holds the lock."""
        for key in ([thread] if thread is not None
                    else list(self._pending_traces)):
            for start, end, fun_name, parent in self._pending_traces.pop(
                    key, ()):
                self._keep(Span(next(self._ids), "stage", "trace", start,
                                end, program=program_name(fun_name),
                                thread=key, parent=parent))

    def on_duration(self, event, duration_secs, **kwargs) -> None:
        key = _CACHE_DURATIONS.get(event)
        if key is not None:
            pending = self._pending()
            pending[key] = pending.get(key, 0.0) + float(duration_secs)

    def on_event(self, event, **kwargs) -> None:
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            pending = self._pending()
            pending[key] = pending.get(key, 0) + 1

    # -- reading -------------------------------------------------------
    def cursor(self) -> int:
        with self._lock:
            self._settle()
            return self._closed

    def since(self, cursor: int = 0) -> Tuple[int, List[Span]]:
        """``(new cursor, spans closed since cursor)``, oldest first;
        what the bound has dropped meanwhile is not among them."""
        with self._lock:
            self._settle()
            out = self._head[cursor:]
            # The tail holds the kept spans numbered from here on.
            tail_from = self._closed - len(self._tail)
            out += list(self._tail)[max(cursor - tail_from, 0):]
            return self._closed, out

    def spans(self) -> List[Span]:
        """Every kept span, the phases and the calls (open ones too), by
        start."""
        with self._lock:
            self._settle()
            found = {s.id: s for group in (
                self._head, self._tail, self._phases,
                *self._calls.values()) for s in group}
        return sorted(found.values(), key=lambda s: s.start)

    def calls(self, name: str) -> List[Span]:
        with self._lock:
            return list(self._calls.get(name, ()))

    def nested_traces(self) -> List[dict]:
        """What was folded, largest first: the functions traced inside a
        program's tracing, by name."""
        with self._lock:
            self._settle()
            rows = [{"program": program_name(k), "count": v[0], "s": v[1]}
                    for k, v in self._nested.items()]
        return sorted(rows, key=lambda r: -r["s"])

    def program_rows(self, spans: Optional[List[Span]] = None) -> List[dict]:
        """A row a program name: seconds by stage (each stage's own
        spans, added), how the cache answered its compilations, and when
        it was first seen."""
        rows: Dict[str, dict] = {}
        origin = self.process_start or 0.0
        for s in self.spans() if spans is None else spans:
            if s.kind != "stage":
                continue
            row = rows.setdefault(s.program or "?", {
                "program": s.program or "?", "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "cache": None, "compiles": 0, "hits": 0,
                "misses": 0, "first_seen_s": s.start - origin})
            row[s.name + "_s"] += s.end - s.start
            if s.name == "compile":
                row["compiles"] += 1
                row["hits"] += s.cache_state == "hit"
                row["misses"] += s.cache_state == "miss"
                if row["cache"] is None:
                    row["cache"] = s.cache_state
        return sorted(rows.values(), key=lambda r: r["first_seen_s"])

    def recompiles(self) -> List[dict]:
        """Compilations of a step program (``spans.PROGRAM_NAMES``) that
        began after the first ``train_step`` call had returned, each with
        the recorded call it fell into (``None`` outside them)."""
        from chainermn_tpu.observability.spans import PROGRAM_NAMES

        calls = [c for c in self.calls("train_step") if c.end is not None]
        if not calls:
            return []
        origin = self.process_start or 0.0
        out = []
        for s in self.spans():
            if (s.kind == "stage" and s.name == "compile"
                    and s.program in PROGRAM_NAMES
                    and s.start >= calls[0].end):
                inside = [c.index for c in calls
                          if c.start <= s.start and s.end <= c.end
                          and c.thread == s.thread]
                out.append({
                    "program": s.program,
                    "call": inside[0] if inside else None,
                    "cache": s.cache_state,
                    "compile_s": s.end - s.start,
                    "at_s": s.start - origin})
        return out

    def summary(self) -> dict:
        """The operator's report; seconds, times as seconds after the
        process began."""
        spans = self.spans()
        origin = self.process_start
        if origin is None:
            origin = spans[0].start if spans else time.perf_counter()
        now = time.perf_counter()
        children: Dict[int, list] = {}
        for s in spans:
            if s.parent is not None:
                children.setdefault(s.parent, []).append(
                    (s.start, s.end if s.end is not None else now))

        names = {s.id: s.name for s in spans}

        def row(s):
            end = s.end if s.end is not None else now
            got = {"name": s.name, "start_s": s.start - origin,
                   "s": end - s.start,
                   "self_s": end - s.start - union_seconds(
                       (max(a, s.start), min(b, end))
                       for a, b in children.get(s.id, ())),
                   "parent": names.get(s.parent)}
            if s.end is None:
                got["open"] = True
            if s.index is not None:
                got["index"] = s.index
            return got

        stages = [s for s in spans if s.kind == "stage"]
        compiles = [s for s in stages if s.name == "compile"]

        def counted(key):
            return sum(s.cache.get(key, 0) for s in compiles)

        return {
            "process_start_from_os": bool(
                self.imported
                and self.process_start < self.imported["first"]),
            "phases": [row(s) for s in spans
                       if s.kind in ("import", "phase")],
            "marks": {k: v - origin
                      for k, v in sorted(self.marks.items(),
                                         key=lambda kv: kv[1])},
            "calls": {name: [row(s) for s in self.calls(name)]
                      for name in sorted(self._calls)},
            "programs": self.program_rows(spans),
            "recompiles": self.recompiles(),
            "nested_traces": self.nested_traces()[:8],
            "totals": {
                "trace_lower_s": union_seconds(
                    (s.start, s.end) for s in stages
                    if s.name != "compile"),
                "compile_s": union_seconds(
                    (s.start, s.end) for s in compiles),
                "requests": counted("requests"), "hits": counted("hits"),
                "misses": counted("misses"),
                "retrieval_s": counted("retrieval_s"),
                "saved_s": counted("saved_s"),
                "spans": len(spans),
                "nested_traces": sum(v[0] for v in self._nested.values()),
                "nested_trace_s": sum(v[1] for v in self._nested.values()),
                "dropped": self._closed - len(self._head) - len(self._tail),
                "listener_calls": self.listener_calls,
                "listener_s": self.listener_s,
            },
        }


# ---------------------------------------------------------------------------
# The process's ledger and its bridge
# ---------------------------------------------------------------------------
_IMPORTED: Optional[dict] = None
_current = Ledger()
_registered = False
_listener_errors = 0


def current() -> Ledger:
    return _current


@contextlib.contextmanager
def use(ledger: Ledger):
    """Make ``ledger`` the process's inside the block.  The listeners
    stay registered and feed whichever ledger is current, so a test can
    read its own job off a fresh one whatever the process did before."""
    global _current
    previous, _current = _current, ledger
    try:
        yield ledger
    finally:
        _current = previous


def _listener(method: str):
    def listen(event, *args, **kwargs):
        global _listener_errors
        t0 = time.perf_counter()
        ledger = _current
        try:
            getattr(ledger, method)(event, *args, **kwargs)
        except Exception:   # inside JAX's compile path: never raise
            _listener_errors += 1
        ledger.account(time.perf_counter() - t0)

    listen.__name__ = method
    return listen


_LISTENERS = (
    ("register_event_time_span_listener", _listener("on_time_span")),
    ("register_event_duration_secs_listener", _listener("on_duration")),
    ("register_event_listener", _listener("on_event")),
)


def register() -> bool:
    """Register the three listeners with ``jax.monitoring``, once a
    process; whether this call did."""
    global _registered
    if _registered:
        return False
    from jax import monitoring

    for name, listener in _LISTENERS:
        getattr(monitoring, name)(listener)
    _registered = True
    return True


def listener_errors() -> int:
    return _listener_errors


def finish_import(first: float, jax_span: Tuple[float, float]) -> None:
    """Called by the package's last statement: stamp the import into the
    process's ledger and register the listeners."""
    global _IMPORTED
    if _IMPORTED is None:
        _IMPORTED = {"first": first, "jax": jax_span,
                     "last": time.perf_counter()}
        _current.record_import(_IMPORTED)
    register()


def mark(name: str) -> None:
    _current.mark(name)


def open_call(name: str) -> Optional[Span]:
    return _current.open_call(name)


def close(span: Optional[Span]) -> None:
    """Close a span of :func:`open_call` (``None``: past the record) on
    the ledger it was opened on."""
    if span is not None:
        _current.close(span)


@contextlib.contextmanager
def phase(name: str):
    """A span of the process's ledger that is also ``annotate(name)``
    (``chainermn:<name>`` on the profiler's clock): what an entry point
    wraps its own stretches of start-up in — ``backend`` around
    ``jax.devices()``, ``weights`` around the making of the weights."""
    from chainermn_tpu.observability.spans import annotate

    ledger = _current
    span = ledger.begin("phase", name)
    try:
        with annotate(name):
            yield span
    finally:
        ledger.close(span)


def summary() -> dict:
    return _current.summary()


def publish(reporter, spans: Iterable[Span]) -> None:
    """Hand a drain to a Reporter: ``startup/<phase>_s`` gauges for the
    phases among ``spans`` and ``compile/{requests,hits,misses}`` counters
    for its compilations."""
    for s in spans:
        if s.kind in ("phase", "import") and s.end is not None:
            reporter.gauge(f"startup/{s.name}_s", s.end - s.start)
        elif s.kind == "stage" and s.name == "compile":
            for key in _CACHE_EVENTS.values():
                if s.cache.get(key):
                    reporter.count(f"compile/{key}", s.cache[key])


def report_lines(report: Optional[dict] = None, top: int = 12) -> List[str]:
    """:func:`summary` as a few lines of text: the phases, the marks, the
    totals, the ``top`` programs by seconds and every recompilation."""
    r = summary() if report is None else report
    lines = ["start-up, seconds after the process began "
             f"(from the OS: {r['process_start_from_os']})"]
    for p in r["phases"]:
        lines.append(
            f"  phase {p['name']:<28} +{p['start_s']:8.3f}  {p['s']:8.3f} s"
            f"  (self {p['self_s']:.3f})")
    lines.append("  marks " + "  ".join(
        f"{k}=+{v:.3f}" for k, v in r["marks"].items()))
    for name, calls in r["calls"].items():
        lines.append(f"  calls {name}: " + "  ".join(
            f"#{c['index']} +{c['start_s']:.3f} {c['s']:.3f}s"
            for c in calls[:4]))
    t = r["totals"]
    lines.append(
        f"  trace+lower {t['trace_lower_s']:.3f} s  compile "
        f"{t['compile_s']:.3f} s  cache requests {t['requests']} hits "
        f"{t['hits']} misses {t['misses']} (retrieval "
        f"{t['retrieval_s']:.3f} s, saved {t['saved_s']:.3f} s)  "
        f"listeners {t['listener_calls']} calls {t['listener_s']:.4f} s")
    rows = sorted(r["programs"], reverse=True, key=lambda p: (
        p["trace_s"] + p["lower_s"] + p["compile_s"]))
    for p in rows[:top]:
        lines.append(
            f"  program {p['program']:<28} trace {p['trace_s']:7.3f}  "
            f"lower {p['lower_s']:7.3f}  compile {p['compile_s']:7.3f}  "
            f"{p['cache']} x{p['compiles']}  first +{p['first_seen_s']:.3f}")
    if len(rows) > top:
        rest = rows[top:]
        lines.append(f"  ... and {len(rest)} programs more, " + "  ".join(
            f"{k} {sum(p[k + '_s'] for p in rest):.3f}" for k in STAGES))
    for c in r["recompiles"]:
        lines.append(
            f"  RECOMPILED {c['program']} in call {c['call']} at "
            f"+{c['at_s']:.3f}: {c['compile_s']:.3f} s, cache {c['cache']}")
    return lines
