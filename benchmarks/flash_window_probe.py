#!/usr/bin/env python
"""The three flash kernels alone under a sliding window, on the chip:
device time a call of each at a given (head rows, KV head rows, S, D,
window) over a list of ``block_q x block_k``, beside the tile census of
each call (``ops.flash_attention.tile_census``: tiles live, grid steps
visited, blocks copied a head row).

Every geometry of ``--blocks-q x --blocks-k`` is compiled for the forward
call and for the backward (one pass under ``flash-bwd-dkv`` on dq's grid
where the rows fit its footprint — every shape here — else dq and
dk/dv), run ``--calls`` times inside one profiler capture and read by
DEVICE time under each kernel's scope (``flash_sweep.device_report``).  A geometry Mosaic refuses is reported
and skipped.  ``--window 0`` probes the causal triangle at the same
shape (the price of a live tile with no window mask).

    chiprun -- env PYTHONPATH=. python benchmarks/flash_window_probe.py \
        --out chiprun_out/flash_window_probe.json

The defaults are ``mellum2-train-1chip``'s sliding row (32 query and 4 KV
head rows of 128 at S = 16,384 under a window of 1024, bfloat16);
``--seq 8192 --window 4096`` is the Mistral-style row of PERF.md §6,
PR 40, whose table ``auto_block_size``'s window rule rests on.  About a
minute and a half on one chip for nine geometries.  Off the chip the
kernels run interpreted and the capture has no device plane: rows with a
census and no times (use ``--seq 512 --window 128 --blocks-q 128,256``
there).  It imports nothing a checkout from before the band grid lacks,
so ``PYTHONPATH=<older checkout>`` times that checkout's kernels.
"""

import argparse
import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from flash_sweep import device_report  # (beside this file; sets the cache)

from chainermn_tpu.ops.flash_attention import (
    _flash_bh_bwd,
    _flash_bh_fwd,
    default_interpret,
    tile_census,
)

try:    # (a checkout from before the one-pass backward has no rule)
    from chainermn_tpu.ops.flash_attention import bwd_fused_vmem_bytes
except ImportError:
    def bwd_fused_vmem_bytes(*shape):
        return None


def kernels_of(fused):
    """``{pass: {scope: census key}}``: the one-pass backward runs under
    ``flash-bwd-dkv`` on dq's grid."""
    return {"fwd": {"flash-fwd": "fwd"},
            "bwd": {"flash-bwd-dkv": "dq"} if fused else {
                "flash-bwd-dq": "dq", "flash-bwd-dkv": "dkv"}}


def build(which, bq, bk, scale, window):
    common = dict(scale=scale, causal=True, block_q=bq, block_k=bk,
                  interpret=default_interpret(), window=window)
    if which == "fwd":
        def fn(q, k, v):
            return _flash_bh_fwd(q, k, v, **common)
    else:
        def fn(q, k, v, o, lse, do):
            return _flash_bh_bwd(q, k, v, o, lse, do, **common)
    # The capture tells programs apart by their module name.
    fn.__name__ = f"{which}_{bq}x{bk}"
    return jax.jit(fn)


def attended_pairs(S, window):
    """(query, key) pairs a head row attends: the triangle, cut to the
    band ``0 <= q_pos - k_pos < window``."""
    w = S if window is None else min(window, S)
    return w * (w + 1) // 2 + (S - w) * w


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--head-rows", type=int, default=32,
                    help="batch x query heads")
    ap.add_argument("--kv-head-rows", type=int, default=4,
                    help="batch x KV heads")
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--d-head", type=int, default=128)
    ap.add_argument("--window", type=int, default=1024,
                    help="0: no window (the causal triangle)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--blocks-q", default="256,512,1024")
    ap.add_argument("--blocks-k", default=None,
                    help="default: the same list as --blocks-q")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    BH, BHk, S, D = args.head_rows, args.kv_head_rows, args.seq, args.d_head
    window = args.window or None
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    q, do = (jnp.asarray(rng.randn(BH, S, D), dtype) / D**0.25
             for _ in range(2))
    k, v = (jnp.asarray(rng.randn(BHk, S, D), dtype) / D**0.25
            for _ in range(2))
    scale = 1.0 / D**0.5

    def edges(text):
        return [int(b) for b in text.split(",") if S % int(b) == 0]

    pairs = list(itertools.product(
        edges(args.blocks_q), edges(args.blocks_k or args.blocks_q)))
    o, lse = build("fwd", *pairs[0], scale, window)(q, k, v)

    programs = {}
    for which, (bq, bk) in itertools.product(("fwd", "bwd"), pairs):
        operands = (q, k, v) if which == "fwd" else (q, k, v, o, lse, do)
        programs[f"{which}_{bq}x{bk}"] = (
            build(which, bq, bk, scale, window), operands)
    report, failed = device_report(programs, args.calls)

    rows = []
    for bq, bk in pairs:
        census = tile_census(S, S, bq, bk, True, window)
        row = {"block_q": bq, "block_k": bk,
               "fill_pct": 100.0 * attended_pairs(S, window)
               / (census["fwd"]["live"] * bq * bk)}
        kernels = kernels_of(bwd_fused_vmem_bytes(
            S, bq, bk, D, dtype.itemsize) is not None)
        for which, scopes in kernels.items():
            name = f"{which}_{bq}x{bk}"
            if name in failed:
                row[which + "_error"] = failed[name]
                continue
            # Off the chip the capture has no device plane: no times.
            region = report["programs"].get(name, {}).get("region_ms", {})
            for kern, grid in scopes.items():
                row[kern] = dict(
                    {f: census[grid][f]
                     for f in ("live", "visited", "copied")},
                    **({"ms": region[kern]} if kern in region else {}))
        times = [row.get(kern, {}).get("ms")
                 for scopes in kernels.values() for kern in scopes]
        if None not in times:
            row["all_ms"] = sum(times)
        rows.append(row)
        print(json.dumps(row))
    result = {
        "device": jax.devices()[0].device_kind,
        "shape": {"BH": BH, "BHk": BHk, "S": S, "D": D,
                  "dtype": str(dtype), "window": window},
        "rows": rows,
    }
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
