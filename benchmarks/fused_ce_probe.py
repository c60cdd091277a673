#!/usr/bin/env python
"""The loss head's gradient alone, on the chip: device time a call and by
compiled op, for both gradient rules of ``ops.fused_ce``.

``grad_in_forward`` is ``fused_cross_entropy`` (one scan, three matmuls a
chunk), ``recompute`` is ``fused_cross_entropy_with_lse`` (two scans,
four).  Each is compiled at ``--rows x --d`` against every ``--vocab``,
run ``--calls`` times inside one profiler capture, and read by DEVICE
time (``observability.device_trace``): the program's time a call and its
ops' (the loops' bodies summed over their chunks), largest first.

    chiprun -- env PYTHONPATH=. python benchmarks/fused_ce_probe.py \
        --vocab 50257,25088 --out chiprun_out/fused_ce_probe.json

About a minute on one chip.  Off the chip the capture has no device
plane: rows without times.  PERF.md §6 (PR 27) rests on this table.
"""

import argparse
import collections
import glob
import json
import os
import tempfile

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np

from chainermn_tpu.observability import device_trace
from chainermn_tpu.ops import fused_ce


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--vocab", default="50257")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rules = {
        "grad_in_forward": lambda h, e, lab: fused_ce.fused_cross_entropy(
            h, e, lab, chunk=args.chunk),
        "recompute": lambda h, e, lab: fused_ce.fused_cross_entropy_with_lse(
            h, e, lab, chunk=args.chunk)[0],
    }
    rng = np.random.RandomState(0)
    rows = []
    for vocab in (int(v) for v in args.vocab.split(",")):
        operands = (
            jnp.asarray(rng.randn(args.rows, args.d), jnp.bfloat16),
            jnp.asarray(rng.randn(vocab, args.d) * 0.02, jnp.float32),
            jnp.asarray(rng.randint(0, vocab, args.rows), jnp.int32))
        compiled = {}
        for rule, loss in rules.items():
            c = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
                *operands).compile()
            jax.block_until_ready(c(*operands))
            compiled[rule] = c
        logdir = tempfile.mkdtemp(prefix="fused_ce_probe_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        for c in compiled.values():
            for _ in range(args.calls):
                out = c(*operands)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(
            logdir, "plugins", "profile", "*", "*.xplane.pb"))
        devices, _ = device_trace.read_capture(
            max(found, key=os.path.getmtime))
        # One device, programs in the order they ran, ``calls`` each.
        programs = sorted(devices[0]["modules"], key=lambda t: t[1]) \
            if devices else []
        for i, (rule, c) in enumerate(compiled.items()):
            row = {"vocab": vocab, "rule": rule, "temp_mb": round(
                c.memory_analysis().temp_size_in_bytes / 1e6, 1)}
            runs = programs[i * args.calls:(i + 1) * args.calls]
            if len(programs) == args.calls * len(compiled):
                by_op = collections.Counter()
                for name, start, end in devices[0]["ops"]:
                    inside = any(s <= start and end <= t for _, s, t in runs)
                    op = device_trace.instruction_name(name)
                    if inside and not op.startswith("while"):
                        by_op[op] += (end - start) * 1e3 / args.calls
                row["ms"] = [round((t - s) * 1e3, 3) for _, s, t in runs]
                row["ops_ms"] = [
                    [op, round(ms, 3)] for op, ms in by_op.most_common(8)]
            rows.append(row)
            print(json.dumps(row))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
