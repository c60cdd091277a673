#!/usr/bin/env python
"""The flash backward alone, one pass against two, on the chip: device
time a call of ``_flash_bwd_fused`` (one Mosaic kernel: ``s``, ``p`` and
``dp`` once a tile, ``dk`` / ``dv`` summed into rows resident in VMEM)
and of ``_flash_bwd_pair`` (``flash-bwd-dq`` + ``flash-bwd-dkv``, each
computing them for itself) at the backward geometries of the benchmark's
cells, beside the live tiles of each call, the µs a live tile, the
fused pass's footprint (``flash_vmem_bytes(which="bwd_fused")``) and how
far the two sides' ``dq``, ``dk``, ``dv`` lie apart on the device.

    chiprun -- env PYTHONPATH=. python benchmarks/flash_bwd_probe.py \
        --out chiprun_out/flash_bwd_probe.json

Every program is compiled, run ``--calls`` times inside one profiler
capture and read by DEVICE time under its kernels' scopes
(``flash_sweep.device_report``); the fused pass runs under the scope
``flash-bwd-dkv``.  About three minutes on one chip for the nine
geometries (and the sliding row once more at 1024-edge tiles); ``--cells sdar,cgpt`` for the two PERF.md §6 (PR 48) rests
on.  Off the chip the kernels run interpreted and the capture has no
device plane: rows without times (use ``--shrink 64`` there, which
divides every length and tile).
"""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from flash_sweep import device_report  # (beside this file; sets the cache)

from chainermn_tpu.ops.flash_attention import (
    _flash_bh_fwd,
    _flash_bwd_fused,
    _flash_bwd_pair,
    default_interpret,
    flash_vmem_bytes,
    tile_census,
)

#: cell -> head rows, KV head rows, S, D, D_v, backward tile, window,
#: block-diffusion (L, B): what each cell's runner builds (ROADMAP S4).
CELLS = {
    "cgpt": (128, 128, 2048, 128, 128, 1024, None, None),
    "granite": (64, 16, 8192, 64, 64, 1024, None, None),
    "nemo": (64, 4, 8192, 128, 128, 1024, None, None),
    "zaya": (16, 4, 8192, 128, 128, 1024, None, None),
    "qwen3next": (32, 4, 8192, 256, 256, 512, None, None),
    "mellum-full": (32, 4, 16384, 128, 128, 1024, None, None),
    "mellum-window": (32, 4, 16384, 128, 128, 512, 1024, None),
    # (the same row at the window's width, which the rule passes over)
    "mellum-window-1024": (32, 4, 16384, 128, 128, 1024, 1024, None),
    "ling": (32, 32, 16384, 192, 128, 1024, None, None),
    "sdar": (32, 4, 16384, 128, 128, 1024, None, (8192, 4)),
}
SIDES = {"fused": (_flash_bwd_fused, ("flash-bwd-dkv",)),
         "pair": (_flash_bwd_pair, ("flash-bwd-dq", "flash-bwd-dkv"))}


def build(side, cell, geometry):
    def fn(q, k, v, o, lse, do):
        grads = SIDES[side][0](q, k, v, o, lse, do, **geometry)
        # (an op behind the kernel: the capture's join keeps an op only
        # if it ends inside its module's event, and a kernel that is the
        # module's LAST op ends with it — to the rounding, so that one
        # call's kernel in three was dropped: PERF.md §6, PR 48)
        return grads, sum(g[0, 0, 0].astype(jnp.float32) for g in grads)
    # The capture tells programs apart by their module name.
    fn.__name__ = f"{side}_{cell}".replace("-", "_")
    return jax.jit(fn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--shrink", type=int, default=1,
                    help="divide every length, tile, window and block")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    programs, rows = {}, {}
    for cell in args.cells.split(","):
        BH, BHk, S, D, Dv, b, window, blockdiff = CELLS[cell]
        S, b = S // args.shrink, b // args.shrink
        window = window and window // args.shrink
        if blockdiff is not None:
            blockdiff = (blockdiff[0] // args.shrink, blockdiff[1])
        q = jnp.asarray(rng.randn(BH, S, D), dtype) / D**0.25
        k = jnp.asarray(rng.randn(BHk, S, D), dtype) / D**0.25
        v = jnp.asarray(rng.randn(BHk, S, Dv), dtype) / D**0.25
        do = jnp.asarray(rng.randn(BH, S, Dv), dtype) / D**0.25
        geometry = dict(scale=1.0 / D**0.5, causal=True, block_q=b,
                        block_k=b, interpret=default_interpret(),
                        window=window, blockdiff=blockdiff)
        o, lse = _flash_bh_fwd(q, k, v, **geometry)
        operands = (q, k, v, o, lse, do)
        for side in SIDES:
            programs[f"{side}_{cell}".replace("-", "_")] = (
                build(side, cell, geometry), operands)
        live = tile_census(S, S, b, b, True, window, blockdiff)["dq"]["live"]
        rows[cell] = {
            "cell": cell, "BH": BH, "BHk": BHk, "S": S, "D": D, "D_v": Dv,
            "block": b, "window": window, "blockdiff": blockdiff,
            "live_tiles": live * BH,
            "fused_vmem_bytes": flash_vmem_bytes(
                b, b, D, dtype.itemsize, "bwd_fused", False, Dv, rows=S)}
    report, failed = device_report(programs, args.calls)

    for cell, row in rows.items():
        outs = {}
        for side, (_, kernels) in SIDES.items():
            name = f"{side}_{cell}".replace("-", "_")
            if name in failed:
                row[side + "_error"] = failed[name]
                continue
            fn, operands = programs[name]
            outs[side], _ = fn(*operands)
            # Off the chip the capture has no device plane: no times.
            region = report["programs"].get(name, {}).get("region_ms", {})
            if all(kern in region for kern in kernels):
                row[side + "_kernels_ms"] = {
                    kern: region[kern] for kern in kernels}
                row[side + "_ms"] = sum(region[kern] for kern in kernels)
                row[side + "_us_a_tile"] = (
                    1e3 * row[side + "_ms"] / row["live_tiles"])
        if len(outs) == 2:
            row["max_abs_diff"] = {
                name: float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for name, a, b in zip(("dq", "dk", "dv"), outs["fused"],
                                      outs["pair"])}
        if "fused_ms" in row and "pair_ms" in row:
            row["fused_over_pair"] = row["fused_ms"] / row["pair_ms"]
        print(json.dumps(row))
    result = {"device": jax.devices()[0].device_kind, "dtype": str(dtype),
              "rows": list(rows.values())}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
