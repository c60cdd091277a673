#!/usr/bin/env python
"""What the start-up ledger costs a step, on the HOST's clock: the time
a call of ``optimizers._instrument_step``'s wrapper and of
``CommunicatorBase.global_batch``'s record adds once the record of the
first ``Ledger.CALLS`` calls is full — the state every step of a run but
its first sixteen is in.

The wrapper is timed around a step that does nothing, beside the wrapper
as it was before the ledger (the same annotation, no ``open_call`` /
``close``), ``--calls`` times each (10^6), the two interleaved in
``--rounds`` rounds so that the machine's drift falls on both; the
listeners' side (a few dozen appends a process, none in a warm step) is
the ledger's own ``listener_s``, which ``chipbench/setup_reduce.py``
prints with every traced run.

    chiprun -- env PYTHONPATH=. python benchmarks/startup_ledger_cost.py

No device work: JAX is imported for ``TraceAnnotation`` only, and the
numbers are host nanoseconds (PERF.md section 6, PR 49).
"""

import argparse
import functools
import json
import statistics
import time

from chainermn_tpu import optimizers
from chainermn_tpu.observability import spans, startup


def before_the_ledger(step_fn):
    """``_instrument_step``'s telemetry-off path as PR 48 had it."""

    @functools.wraps(step_fn)
    def instrumented(*args, **kwargs):
        if not spans.telemetry_active():
            with spans.annotate("train_step"):
                return step_fn(*args, **kwargs)
        raise AssertionError("no sink is installed in this probe")

    return instrumented


def seconds(fn, calls):
    t0 = time.perf_counter()
    for _ in range(calls):
        fn()
    return time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=1_000_000)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args()

    def step():
        return None

    step.lower = step.trace = step.eval_shape = None   # the AOT surface
    with startup.use(startup.Ledger()) as ledger:
        now = optimizers._instrument_step(step)
        was = before_the_ledger(step)
        for _ in range(startup.Ledger.CALLS):
            now()
        assert ledger.open_call("train_step") is None    # the record is full
        rows = []
        for _ in range(args.rounds):
            rows.append({
                "bare_ns": seconds(step, args.calls) / args.calls * 1e9,
                "before_ns": seconds(was, args.calls) / args.calls * 1e9,
                "ledger_ns": seconds(now, args.calls) / args.calls * 1e9,
                "open_close_ns": seconds(
                    lambda: startup.close(startup.open_call("train_step")),
                    args.calls) / args.calls * 1e9,
            })
        assert len(ledger.calls("train_step")) == startup.Ledger.CALLS
    out = {k: statistics.median(r[k] for r in rows) for k in rows[0]}
    out["added_ns_a_call"] = out["ledger_ns"] - out["before_ns"]
    out["calls"], out["rounds"] = args.calls, rows
    print(json.dumps(out))


if __name__ == "__main__":
    main()
