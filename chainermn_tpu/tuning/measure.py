"""Compile-and-time harness for candidate kernel configs.

One contract: the caller supplies ``build_run(config) -> run`` where
``run(n)`` executes ``n`` chained iterations ending in one hard
:func:`~chainermn_tpu.utils.profiling.sync`, and this module times every
candidate with the same median-of-k slope method ``bench.py`` uses (the
slope between two run lengths cancels the per-run dispatch + readback
constant; the median absorbs run-to-run noise).

A candidate that fails anywhere — Mosaic compile error, VMEM OOM, a
shape the estimate misjudged — is recorded with its error and skipped,
never fatal: an autotune sweep must survive the edges of its own search
space.
"""

from __future__ import annotations

from typing import Callable, Iterable, List, Optional

from chainermn_tpu.utils.profiling import median_slope


def measure_candidates(
    build_run: Callable[[dict], Callable[[int], float]],
    candidates: Iterable[dict],
    n1: int = 3,
    repeats: int = 3,
    log: Optional[Callable[[str], None]] = None,
) -> List[dict]:
    """Time every candidate; returns one record per candidate:
    ``{"config", "seconds", "error"}`` with ``seconds`` None for skipped
    (failed) configs.  ``run(1)`` is called once first so compile time
    never leaks into the slope samples and compile failures are caught
    per-candidate."""
    results = []
    for cfg in candidates:
        rec = {"config": dict(cfg), "seconds": None, "error": None}
        try:
            run = build_run(dict(cfg))
            run(1)  # compile + warm; candidate-killing errors land here
            t, samples = median_slope(run, n1, repeats=repeats)
            rec["seconds"] = float(t)
            rec["samples"] = [float(s) for s in samples]
        except Exception as e:  # invalid config: skip, keep sweeping
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        if log is not None:
            log(
                f"  {rec['config']}: "
                + (f"{rec['seconds'] * 1e6:.1f} us/iter"
                   if rec["seconds"] is not None
                   else f"skipped ({rec['error']})")
            )
        results.append(rec)
    return results


def best_config(results: List[dict]) -> Optional[dict]:
    """The measured argmin record, or None when every candidate failed."""
    timed = [r for r in results if r["seconds"] is not None]
    if not timed:
        return None
    return min(timed, key=lambda r: r["seconds"])
