"""Set-up from the inside, for the ``setup.*`` per-layer readers: the
program's own start-up ledger (``chainermn_tpu.observability.startup``,
read in process as ``qnext.gmm_tile_fill_pct`` reads the program's count)
cut where the runner cuts ``setup_s``.

Set-up ends at the window: every runner drives ``mix["reference_steps"]``
first steps by the window's own call and feed and opens its window with
the next ``comm.global_batch``.  The ledger has the start and end of the
first calls of ``global_batch`` and ``train_step``, so the window is the
start of the first of either after the ``reference_steps``-th
``train_step`` call returned.  Five phases partition process start ->
window:

    import_s       process start -> end of ``import chainermn_tpu``
    backend_s      -> the program's first call, ``setup_compilation_cache``
                   (``run.py`` brings the backend up before it)
    build_s        -> the first ``train_step`` call (mesh, communicator,
                   model, optimizer, seeded weights and state)
    first_call_s   the first ``train_step`` call, host side (trace, lower,
                   compile or load, dispatch)
    first_steps_s  its return -> the window (the first steps on the
                   device, what ``correct`` reads back)

and three readings cut across them: the union of the ``trace`` and
``lower`` spans, the union of the ``compile`` spans (cache retrieval
included) and the compilations the cache missed (asked, not found,
compiled and written: 0 on a truly warm run), each inside set-up.

Every function returns ``None`` where its source is not there: a program
from before the ledger (no ``observability.startup`` to import), a ledger
without the marks, a run that made fewer calls than ``reference_steps``.
"""

from chipbench import harness

PHASES = ("import_s", "backend_s", "build_s", "first_call_s",
          "first_steps_s")
FIRST_CALL = "setup_compilation_cache"
TABLE_ROWS = 12


def ledger(ctx):
    """The program's ledger (a test hands its own as
    ``ctx["startup_ledger"]``)."""
    if "startup_ledger" in ctx:
        return ctx["startup_ledger"]
    try:
        from chainermn_tpu.observability import startup
    except ImportError:
        return None
    return startup.current()


def cuts(led, reference_steps):
    """The six instants the five phases lie between, on the ledger's
    clock, or ``None``."""
    if led is None or led.process_start is None or not led.imported:
        return None
    first_call = led.marks.get(FIRST_CALL)
    steps = [c for c in led.calls("train_step") if c.end is not None]
    if first_call is None or len(steps) < max(reference_steps, 1):
        return None
    followed = steps[reference_steps - 1].end
    after = [c.start for name in ("global_batch", "train_step")
             for c in led.calls(name) if c.start >= followed]
    if not after:
        return None
    return (led.process_start, led.imported["last"], first_call,
            steps[0].start, steps[0].end, min(after))


def _union(intervals):
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total, reach = total + end - start, end
        elif end > reach:
            total, reach = total + end - reach, end
    return total


def reduce_ledger(led, reference_steps):
    """The eight readings and the per-program table, or ``None``."""
    at = cuts(led, reference_steps)
    if at is None:
        return None
    began, window = at[0], at[-1]
    out = {name: after - before
           for name, before, after in zip(PHASES, at, at[1:])}
    stages = [s for s in led.spans()
              if s.kind == "stage" and s.start < window]

    def clipped(names):
        return _union((max(s.start, began), min(s.end, window))
                      for s in stages if s.name in names)

    out["trace_lower_s"] = clipped(("trace", "lower"))
    out["compile_s"] = clipped(("compile",))
    compiles = [s for s in stages if s.name == "compile"]
    for key, state in (("cache_hits", "hit"), ("cache_misses", "miss"),
                       ("cache_too_quick_to_keep", "uncached")):
        out[key] = sum(1 for s in compiles if s.cache_state == state)
    for key in ("retrieval_s", "saved_s"):
        out["cache_" + key] = sum(s.cache.get(key, 0.0) for s in compiles)
    out["process_start_to_window_s"] = window - began
    out["listener_calls"] = led.listener_calls
    out["listener_s"] = led.listener_s
    out["programs"] = led.program_rows(stages)
    return out


def reading(ctx, name):
    """One of the eight, from the run's one reduction (kept in
    ``ctx["notes"]``; the per-program table is printed with the first)."""
    notes = ctx.setdefault("notes", {})
    if "setup" not in notes:
        notes["setup"] = reduce_ledger(
            ledger(ctx), int(ctx["mix"]["reference_steps"]))
        if notes["setup"] is not None:
            say(notes["setup"])
    return None if notes["setup"] is None else notes["setup"][name]


def say(got):
    harness.say(
        "setup: " + " ".join(f"{k}={got[k]:.4f}" for k in PHASES)
        + f" sum={got['process_start_to_window_s']:.4f}"
        f" trace_lower_s={got['trace_lower_s']:.4f}"
        f" compile_s={got['compile_s']:.4f}"
        f" cache_misses={got['cache_misses']}"
        f" cache_hits={got['cache_hits']}"
        f" too_quick_to_keep={got['cache_too_quick_to_keep']}"
        f" cache_retrieval_s={got['cache_retrieval_s']:.4f}"
        f" cache_saved_s={got['cache_saved_s']:.4f}"
        f" ledger_listener_calls={got['listener_calls']}"
        f" ledger_listener_s={got['listener_s']:.6f}")
    rows = sorted(got["programs"], reverse=True, key=lambda r: (
        r["trace_s"] + r["lower_s"] + r["compile_s"]))
    for r in rows[:TABLE_ROWS]:
        harness.say(
            f"setup program {r['program']}: trace_s={r['trace_s']:.3f} "
            f"lower_s={r['lower_s']:.3f} compile_s={r['compile_s']:.3f} "
            f"cache={r['cache']} compiles={r['compiles']} hits={r['hits']} "
            f"misses={r['misses']} first_seen_s={r['first_seen_s']:.2f}")
    rest = rows[TABLE_ROWS:]
    if rest:
        harness.say(
            f"setup programs, {len(rest)} more: " + " ".join(
                f"{k}={sum(r[k] for r in rest):.3f}"
                for k in ("trace_s", "lower_s", "compile_s")))
