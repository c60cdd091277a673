#!/usr/bin/env python
"""What the gradient exchange costs the core, op by op: one traced run of
a training cell (``chipbench/run.py``'s own, its arguments passed
through) with one more reading of the traced slice written to ``$DUMP``
as JSON — device ms a step by (phase of the step, scopes on the op's
path, kind of op), the ``all-reduce`` and ``collective-permute`` ops'
time and the exposed part of it.  Since PR 46 the manifest's
``comm.exchange_ms`` (``comm.allreduce_ms`` before) and
``comm.exposed_ms`` match ``all-reduce`` and ``collective-permute`` ops
alike (``scope_reduce.EXCHANGE``, the pattern below) and
``comm.pack_ms`` is the ``allreduce`` scope less those ops: the ring's
piece cuts, adds and write-backs.  This tool is the finer split behind
them — by scope and kind of op — on which PERF.md §5 (dp4) rests.

    chiprun --chips 4 -- env DUMP=chiprun_out/exchange_split.json python \
        benchmarks/exchange_split.py --workload cgpt-train-dp4 \
        --seed 2450700041 --seconds 20 --trace 1

from the root of a checkout (about 100 s warm on four chips)."""
import json, os, re, sys, traceback

sys.path.insert(0, os.getcwd())
from chipbench import scope_reduce, trace_reduce  # noqa: E402
import chipbench.run as bench_run  # noqa: E402

DUMP = os.environ["DUMP"]
EXCHANGE = r"\s(all-reduce|collective-permute)(-start|-done)?\("
_orig = scope_reduce.attribution


def dump(ctx):
    from chainermn_tpu.observability import device_trace as dt

    trace, table = ctx["trace"], ctx["scope_table"]
    steps, k = ctx["trace_steps"], len(trace.devices)
    rows, by_opcode = {}, {}
    for d in trace.devices:
        for o in d["ops"]:
            code = trace_reduce.opcode(o.name)
            if code in trace_reduce.CONTAINERS:
                continue
            key = dt.instruction_name(o.name)
            path = table.get(key) or ""
            own = table.owner_path(key) or ""
            phase = dt.classify(path)[0] if path else None
            flags = "+".join(s for s in ("fwd-bwd", "allreduce", "opt-update",
                                         "grad-unpack")
                             if s in path or s in own)
            r = (str(phase), flags, trace_reduce.short_name(o.name))
            rows[r] = rows.get(r, 0.0) + o.dur
            by_opcode[code] = by_opcode.get(code, 0.0) + o.dur
    ms = lambda s: s / k / steps * 1e3  # noqa: E731
    out = {
        "trace_steps": steps, "devices": k,
        "exchange_ms": ms(k * (trace.seconds(EXCHANGE) or 0.0)),
        "exchange_exposed_ms": ms(k * (trace.exposed_seconds(EXCHANGE) or 0.0)),
        "by_opcode_ms": {c: ms(s) for c, s in sorted(
            by_opcode.items(), key=lambda kv: -kv[1])},
        "rows_ms": [[*r, ms(s)] for r, s in sorted(
            rows.items(), key=lambda kv: -kv[1])[:150]],
        "by_phase_ms": {},
        "by_phase_flags_ms": {},
    }
    for (phase, flags, _), s in rows.items():
        out["by_phase_ms"][phase] = out["by_phase_ms"].get(phase, 0.0) + ms(s)
        kf = f"{phase}|{flags}"
        out["by_phase_flags_ms"][kf] = out["by_phase_flags_ms"].get(kf, 0.0) + ms(s)
    counts = {}
    for o in trace.devices[0]["ops"]:
        c = trace_reduce.opcode(o.name)
        if c.startswith(("collective-permute", "all-reduce")):
            counts[c] = counts.get(c, 0) + 1
    out["ops_on_device0_in_slice"] = counts
    with open(DUMP, "w") as f:
        json.dump(out, f, indent=1)


def attribution(ctx):
    got = _orig(ctx)
    if not ctx.get("_dumped") and ctx.get("trace") is not None \
            and ctx.get("scope_table") is not None:
        ctx["_dumped"] = True
        try:
            dump(ctx)
        except Exception:
            with open(DUMP + ".err", "w") as f:
                traceback.print_exc(file=f)
    return got


scope_reduce.attribution = attribution
bench_run.main()
