#!/usr/bin/env python
"""Block-geometry sweep of the three flash kernels, alone, on the chip.

For every ``block_q x block_k`` of ``--blocks`` it compiles the forward
call and the backward (one pass under ``flash-bwd-dkv`` where the rows
fit its footprint, else dq and dk/dv: ``_flash_bh_bwd``'s rule;
``flash_bwd_probe.py`` times the two sides against each other) at one
shape, runs each a few times inside one profiler capture and reads the
DEVICE time of each kernel by its scope (``observability.device_trace``)
— no host clock, so a dispatch constant cannot hide a short grid.  A geometry that does not
compile is reported as such and skipped.

    chiprun -- env PYTHONPATH=. python benchmarks/flash_sweep.py \
        --batch 8 --heads 16 --seq 2048 --out chiprun_out/flash_sweep.json

The default static geometry (``auto_block_size``) rests on this table
(PERF.md §6, PR 25).
"""

import argparse
import glob
import itertools
import json
import os
import tempfile

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np

from chainermn_tpu.observability import device_trace
from chainermn_tpu.ops.flash_attention import (
    _flash_bh_bwd,
    _flash_bh_fwd,
    default_interpret,
)

KERNELS = ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")


def build(which, bq, bk, scale, causal):
    if which == "fwd":
        def fn(q, k, v):
            return _flash_bh_fwd(q, k, v, scale=scale, causal=causal,
                                 block_q=bq, block_k=bk,
                                 interpret=default_interpret())
    else:
        def fn(q, k, v, o, lse, do):
            return _flash_bh_bwd(q, k, v, o, lse, do, scale=scale,
                                 causal=causal, block_q=bq, block_k=bk,
                                 interpret=default_interpret())
    # The capture tells programs apart by their module name.
    fn.__name__ = f"{which}_{bq}x{bk}"
    return jax.jit(fn)


def device_report(programs, calls):
    """``(report, failed)`` of ``{name: (jitted, operands)}``: every
    program compiled, run ``calls`` times inside ONE profiler capture and
    read by device time under each scope (``report["programs"][name]
    ["region_ms"]``; off the chip the capture has no device plane and the
    report no programs).  A program Mosaic refuses is left out and named
    in ``failed`` with its error."""
    compiled, tables, failed = {}, {}, {}
    for name, (fn, operands) in programs.items():
        try:
            c = fn.lower(*operands).compile()
            jax.block_until_ready(c(*operands))
        except Exception as e:  # a geometry Mosaic refuses: report, go on
            failed[name] = f"{type(e).__name__}: {e}"[:300]
            continue
        compiled[name] = (c, operands)
        tables[name] = device_trace.scope_table(c)

    logdir = tempfile.mkdtemp(prefix="flash_sweep_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    for c, operands in compiled.values():
        for _ in range(calls):
            out = c(*operands)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    return device_trace.report_from(
        *device_trace.read_capture(max(found, key=os.path.getmtime)),
        tables), failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--d-head", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--no-causal", dest="causal", action="store_false")
    ap.add_argument("--blocks", default="128,256,512,1024,2048")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    BH, S, D = args.batch * args.heads, args.seq, args.d_head
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.randn(BH, S, D), dtype) / D**0.25
                   for _ in range(4))
    scale = 1.0 / D**0.5
    edges = [int(b) for b in args.blocks.split(",") if S % int(b) == 0]
    o, lse = build("fwd", min(edges), min(edges), scale, args.causal)(q, k, v)

    programs = {}
    for which, (bq, bk) in itertools.product(
            ("fwd", "bwd"), itertools.product(edges, edges)):
        operands = (q, k, v) if which == "fwd" else (q, k, v, o, lse, do)
        programs[f"{which}_{bq}x{bk}"] = (
            build(which, bq, bk, scale, args.causal), operands)
    report, failed = device_report(programs, args.calls)

    # Needed FLOPs (what the roofline counts): 2 matmuls forward, 5 in the
    # backward (what the one pass runs; the two kernels run 7: 2 in dq +
    # its s, 3 in dk/dv + its s), causal half.
    mm = 2 * S * S * D * BH * (0.5 if args.causal else 1.0)
    rows = []
    for bq, bk in itertools.product(edges, edges):
        row = {"block_q": bq, "block_k": bk}
        for which in ("fwd", "bwd"):
            name = f"{which}_{bq}x{bk}"
            if name in failed:
                row[which + "_error"] = failed[name]
                continue
            # Off the chip the capture has no device plane: no times.
            region = report["programs"].get(name, {}).get("region_ms", {})
            for kern in KERNELS:
                if kern in region:
                    row[kern + "_ms"] = region[kern]
        if "flash-fwd_ms" in row:
            row["fwd_tflops"] = 2 * mm / row["flash-fwd_ms"] / 1e9
        if "flash-bwd-dkv_ms" in row:
            row["bwd_ms"] = (row.get("flash-bwd-dq_ms", 0.0)
                             + row["flash-bwd-dkv_ms"])
            row["bwd_tflops"] = 5 * mm / row["bwd_ms"] / 1e9
        rows.append(row)
        print(json.dumps(row))
    result = {
        "device": jax.devices()[0].device_kind,
        "shape": {"BH": BH, "S": S, "D": D, "dtype": str(dtype),
                  "causal": args.causal},
        "rows": rows,
    }
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
