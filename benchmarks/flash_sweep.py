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

``--kinds`` times a live tile BY KIND instead (PR 50): an interior
tile, a diagonal tile (the backward's by halves, and whole with the
halves bypassed from here), the band's far tile, a tile both edges cut,
a step entered and skipped and a resident block's opening and closing,
forward and backward, at ``--kind-widths`` (D = 64 / 128 / 256 and
scores 192 / values 128 by default) — each read off whole calls of a few
tiles a head row, by differences (:func:`tile_kind_times`).  About four
minutes on one chip.
"""

import argparse
import glob
import importlib
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

# (the module: ``chainermn_tpu.ops.flash_attention`` names the function)
fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


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


class _Whole:
    """A jitted function that lowers with the backward's halves bypassed
    from outside, as ``tests/test_flash_attention.py`` does (there is no
    switch in the module; a checkout from before PR 50 has nothing to
    bypass)."""

    def __init__(self, fn):
        self.fn = fn

    def lower(self, *operands):
        halves = getattr(fa, "_by_halves", None)
        if halves is not None:
            fa._by_halves = lambda *a, **kw: False
        jax.clear_caches()
        try:
            return self.fn.lower(*operands)
        finally:
            if halves is not None:
                fa._by_halves = halves
            jax.clear_caches()


#: name -> (causal, window in tile edges, tiles a side, halves): the
#: calls :func:`tile_kind_times` solves the kinds from.  A head row of
#: ``n`` tiles a side runs, non-causal, n^2 interior tiles; causal, n
#: diagonal and n (n - 1) / 2 interior tiles and as many steps entered
#: and skipped; under a window of one edge n diagonal and n - 1 far
#: tiles and one skipped step; under half an edge n tiles both edges
#: cut and n - 1 far ones.
_KIND_CALLS = {
    "full-2": (False, None, 2, True), "full-4": (False, None, 4, True),
    "causal-2": (True, None, 2, True), "causal-4": (True, None, 4, True),
    "causal-2-whole": (True, None, 2, False),
    "causal-4-whole": (True, None, 4, False),
    "band-4": (True, 1.0, 4, True), "both-4": (True, 0.5, 4, True),
}


def tile_kind_times(D, Dv, dtype, head_rows=32, calls=3,
                    passes=("fwd", "bwd"), blocks=None):
    """``[row]``, one a pass: the µs a tile of each kind at this width,
    at the tile the rule gives the pass (``blocks``: ``{pass: edge}`` to
    pin it), solved from the DEVICE times of :data:`_KIND_CALLS` a head
    row: ``interior`` and ``row`` (a resident block's opening and
    closing steps' own work) from the two non-causal calls, ``skipped``
    and ``diagonal`` from the two causal ones, ``far`` from the band,
    ``both`` from the narrow band; ``diagonal-whole`` and
    ``skipped-whole`` the causal calls with the backward's halves
    bypassed.  Off the chip: rows without times."""
    dtype = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    programs, edges = {}, {}
    for which in passes:
        b = (blocks or {}).get(which) or fa.auto_block_size(
            16384, D, dtype, which, D_v=None if Dv == D else Dv)
        edges[which] = b
        for name, (causal, window, n, halves) in _KIND_CALLS.items():
            S = n * b
            q = jnp.asarray(rng.randn(head_rows, S, D), dtype) / D**0.25
            k = jnp.asarray(rng.randn(head_rows, S, D), dtype) / D**0.25
            v, do = (jnp.asarray(rng.randn(head_rows, S, Dv), dtype)
                     / D**0.25 for _ in range(2))
            geometry = dict(scale=1.0 / D**0.5, causal=causal, block_q=b,
                            block_k=b, interpret=default_interpret(),
                            window=window and int(window * b))

            def fn(*operands, geometry=geometry, which=which):
                if which == "fwd":
                    out = _flash_bh_fwd(*operands, **geometry)
                else:
                    out = _flash_bh_bwd(*operands, **geometry)
                # (an op behind the kernel: see flash_bwd_probe.build)
                return out, sum(x[0, 0, 0].astype(jnp.float32) for x in out)

            fn.__name__ = f"{which}_{name}_d{D}x{Dv}".replace("-", "_")
            operands = (q, k, v)
            if which == "bwd":
                o, lse = _flash_bh_fwd(q, k, v, **geometry)
                operands = (q, k, v, o, lse, do)
            jitted = jax.jit(fn)
            programs[fn.__name__] = (
                jitted if halves else _Whole(jitted), operands)
    report, failed = device_report(programs, calls)

    rows = []
    for which in passes:
        kernels = ("flash-fwd",) if which == "fwd" else (
            "flash-bwd-dq", "flash-bwd-dkv")
        row = {"pass": which, "D": D, "D_v": Dv, "block": edges[which],
               "head_rows": head_rows}
        us = {}
        for name in _KIND_CALLS:
            full = f"{which}_{name}_d{D}x{Dv}".replace("-", "_")
            if full in failed:
                row.setdefault("errors", {})[name] = failed[full]
                continue
            region = report["programs"].get(full, {}).get("region_ms", {})
            if any(kern in region for kern in kernels):
                us[name] = 1e3 * sum(
                    region.get(kern, 0.0) for kern in kernels) / head_rows
        for name in _KIND_CALLS:
            # (a pass that halves nothing lowers its "-whole" calls to the
            # very program of the plain ones, and the capture, which tells
            # programs apart by module, reads one of the two)
            twin = name[:-6] if name.endswith("-whole") else name + "-whole"
            if name not in us and twin in us:
                us[name] = us[twin]
        if len(us) == len(_KIND_CALLS):
            t = {"interior": (us["full-4"] - 2 * us["full-2"]) / 8}
            t["row"] = (us["full-2"] - 4 * t["interior"]) / 2
            for tag in ("", "-whole"):
                c2, c4 = us["causal-2" + tag], us["causal-4" + tag]
                skipped = (c4 - 2 * c2) / 4 - t["interior"]
                t["skipped" + tag] = skipped
                t["diagonal" + tag] = (
                    c2 - t["interior"] - skipped - 2 * t["row"]) / 2
            t["far"] = (us["band-4"] - 4 * t["diagonal"] - t["skipped"]
                        - 4 * t["row"]) / 3
            t["both"] = (us["both-4"] - 3 * t["far"] - t["skipped"]
                         - 4 * t["row"]) / 4
            row["us_a_tile"] = t
        row["us_a_head_row"] = us
        rows.append(row)
        print(json.dumps(row))
    return rows


def write_json(path, result):
    """``result`` to ``path`` (None: nowhere), its directory made."""
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kinds", action="store_true",
                    help="the µs a tile by kind, and nothing else")
    ap.add_argument("--kind-widths", default="64,128,256,192/128")
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

    if args.kinds:
        rows = []
        for width in args.kind_widths.split(","):
            D, _, Dv = width.partition("/")
            rows += tile_kind_times(int(D), int(Dv or D), args.dtype,
                                    args.batch * args.heads // 4, args.calls)
        write_json(args.out, {"device": jax.devices()[0].device_kind,
                              "rows": rows})
        return
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
    write_json(args.out, result)


if __name__ == "__main__":
    main()
