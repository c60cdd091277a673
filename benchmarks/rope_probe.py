#!/usr/bin/env python
"""Rotary positions alone, on the chip: device time a call, forward +
backward, of ``models.transformer.rotate_partial`` (a head turned whole:
``x cos + (x P) sin`` against full-width tables, the partner lanes
fetched by one product with a signed permutation) beside the form it
replaced, kept here only: the head cut into ``rotary_dim / 2``-wide
halves that the chip pads to 128 lanes each, a float32 copy of the
operand made by the caller, three parts concatenated.

Both are compiled as a caller runs them — the operand in the type the
caller holds it, the result cast to the model's bfloat16, the cotangent
arriving in it — at the three cells' shapes (``--cells``): mellum's q
(1 x 16,384 x 32 heads of 128, bfloat16, the whole head turned),
qwen3next's q (2 x 8,192 x 16 of 256, bfloat16, a quarter turned) and
zaya's q (2 x 8,192 x 8 of 128, float32 from CCA's unit norm, half
turned).  The activations' layouts are left to the compiler, as they are
inside a step.  Each program runs ``--calls`` times inside one profiler
capture and is read by DEVICE time (``observability.device_trace``),
with its largest ops, the bytes the compiler says it accesses, and how
far the two forms' results lie apart on this device.

    chiprun -- env PYTHONPATH=. python benchmarks/rope_probe.py \
        --out chiprun_out/rope_probe.json

Half a minute on one chip.  Off the chip the capture has no device plane:
rows without times (``--seq 256`` rehearses it).  PERF.md §6 (PR 41)
rests on this table.
"""

import argparse
import json
import os

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding
from ssm_conv_probe import device_ms  # (beside this file)

from chainermn_tpu.models.block_table import rotary_frequencies
from chainermn_tpu.models.transformer import rotate_partial

#: cell: (batch, seq, heads, d_head, rotary_dim, theta, operand dtype)
CELLS = {
    "mellum": (1, 16384, 32, 128, 128, 5e5, jnp.bfloat16),
    "qwen3next": (2, 8192, 16, 256, 64, 1e7, jnp.bfloat16),
    "zaya": (2, 8192, 8, 128, 64, 5e6, jnp.float32),
}


def rotate_halves(x, positions, rotary_dim, theta):
    """The form before PR 41, for a float32 ``x``."""
    half = rotary_dim // 2
    freq, _ = rotary_frequencies(rotary_dim, theta)
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)                                   # (S, half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b, rest = (x[..., :half], x[..., half:rotary_dim],
                  x[..., rotary_dim:])
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def both_passes(rotate):
    """``rotate`` as a caller runs it: the result cast to the model's
    bfloat16, the cotangent arriving in it."""
    def run(x, g):
        y, back = jax.vjp(lambda x: rotate(x).astype(jnp.bfloat16), x)
        return y, back(g)[0]

    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--seq", type=int, default=None,
                    help="another sequence length for every cell")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    here = SingleDeviceSharding(jax.devices()[0])
    free = Format(Layout.AUTO, here)
    rng = np.random.RandomState(0)
    programs, shapes = {}, {}
    for cell in args.cells.split(","):
        b, S, h, d, rotary_dim, theta, dtype = CELLS[cell]
        S = args.seq or S
        x = jnp.asarray(rng.randn(b, S, h, d), dtype)
        g = jnp.asarray(rng.randn(b, S, h, d), jnp.bfloat16)
        pos = jnp.arange(S)

        forms = {
            "halves": both_passes(lambda x: rotate_halves(
                x.astype(jnp.float32), pos, rotary_dim, theta)),
            "whole": both_passes(lambda x: rotate_partial(
                x, pos, rotary_dim, theta))}
        for form, fn in forms.items():
            c = jax.jit(fn, in_shardings=(free, free),
                        out_shardings=(free, free)).lower(x, g).compile()
            programs[f"{cell}.{form}"] = (c, tuple(
                jax.device_put(a, f)
                for a, f in zip((x, g), c.input_formats[0])))
            shapes[f"{cell}.{form}"] = [b, S, h, d, rotary_dim,
                                        jnp.dtype(dtype).name]
    rows = []
    for name, timed in device_ms(programs, args.calls).items():
        c = programs[name][0]
        row = {"program": name, "shape": shapes[name],
               "gb_accessed": round(
                   c.cost_analysis()["bytes accessed"] / 1e9, 3), **timed}
        rows.append(row)
        print(json.dumps(row))
    # the two forms' results and cotangents on this device (on the host:
    # the results keep the layouts the compiler chose)
    gaps = {}
    for cell in args.cells.split(","):
        outs = [[np.asarray(a, np.float32) for a in c(*operands)]
                for c, operands in (programs[f"{cell}.halves"],
                                    programs[f"{cell}.whole"])]
        gaps[cell] = [float(np.abs(new - old).max())
                      for old, new in zip(*outs)]
    print(json.dumps({"largest_gap_y_dx": gaps}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "rows": rows, "largest_gap_y_dx": gaps}, f, indent=1)


if __name__ == "__main__":
    main()
