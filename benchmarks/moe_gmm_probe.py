#!/usr/bin/env python
"""The held experts' two grouped matmuls alone, on the chip: device time a
call of ``relu(x W_up[g])^2 W_down[g]`` forward + backward over ragged row
groups, in the forms that were weighed for ``ops/grouped_matmul.py``.

The rows are laid out as ``parallel.moe_dropless.dispatch`` lays them:
``--pairs`` rows (the cell's 6,144 a layer in expectation) fall into
``--experts`` groups with a skew, every group starts on a tile, and the
buffer has room for ``--bound`` rows.  Forms:

* ``pallas_t<rows>`` — the repo's kernels (``grouped_relu2_mlp``) at a row
  tile of 128, 256 and 512: work on live tiles only;
* ``ragged_dot`` — ``lax.ragged_dot`` over the same buffer with the
  groups' tile-padded sizes, its backward by autodiff;
* ``per_tile_einsum`` — plain XLA: every tile's weights gathered and a
  batched matmul over ALL the buffer's tiles, live or not.

Each is compiled once, run ``--calls`` times inside one profiler capture
and read by DEVICE time, with its largest ops (``benchmarks/
ssm_conv_probe.py``'s ``device_ms``).

    chiprun -- env PYTHONPATH=. python benchmarks/moe_gmm_probe.py \
        --out chiprun_out/moe_gmm_probe.json

About a minute on one chip.  Off the chip the capture has no device plane:
rows without times.  PERF.md §6 (PR 30) rests on this table.
"""

import argparse
import json
import os

import jax

from benchmarks.ssm_conv_probe import device_ms

import jax.numpy as jnp
import numpy as np
from jax import lax

from chainermn_tpu.ops.grouped_matmul import grouped_relu2_mlp


def layout(sizes, tile, bound):
    """``(tile_group, n_live, row_live)`` of groups of ``sizes`` rows."""
    tiles = np.maximum(1, -(-sizes // tile))
    n_tiles = -(-bound // tile) + len(sizes)
    tile_group = np.minimum(
        np.searchsorted(np.cumsum(tiles), np.arange(n_tiles), side="right"),
        len(sizes) - 1)
    live = np.zeros(n_tiles * tile, bool)
    at = 0
    for n, t in zip(sizes, tiles):
        live[at:at + n] = True
        at += t * tile
    return (jnp.asarray(tile_group, jnp.int32),
            jnp.asarray([tiles.sum()], jnp.int32), live,
            jnp.asarray(tiles * tile, jnp.int32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=6144)
    ap.add_argument("--bound", type=int, default=24576)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--d", type=int, default=2688)
    ap.add_argument("--f", type=int, default=1856)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    share = 1.0 / np.arange(1, args.experts + 1)          # a skewed load
    sizes = rng.multinomial(args.pairs, share / share.sum())
    w_up = jnp.asarray(rng.normal(size=(args.experts, args.f, args.d))
                       * 0.02, jnp.float32)      # output-major, as held
    w_down = jnp.asarray(rng.normal(size=(args.experts, args.f, args.d))
                         * 0.02, jnp.float32)

    def grad_of(mlp):
        def loss(x, w_up, w_down, dy, *plan):
            return jnp.sum(mlp(x, w_up, w_down, *plan).astype(jnp.float32)
                           * dy)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def ragged(x, w_up, w_down, tile_group, n_live, padded):
        del tile_group, n_live
        up = lax.ragged_dot(x, jnp.swapaxes(w_up, 1, 2).astype(x.dtype),
                            padded)
        return lax.ragged_dot(jnp.square(jax.nn.relu(up)),
                              w_down.astype(x.dtype), padded)

    def per_tile(x, w_up, w_down, tile_group, n_live, padded):
        del n_live, padded
        tiles = x.reshape(tile_group.shape[0], -1, x.shape[-1])
        up = jnp.einsum("tmd,tfd->tmf", tiles,
                        w_up.astype(x.dtype)[tile_group])
        out = jnp.einsum("tmf,tfd->tmd", jnp.square(jax.nn.relu(up)),
                         w_down.astype(x.dtype)[tile_group])
        return out.reshape(x.shape[0], -1)

    def pallas(x, w_up, w_down, tile_group, n_live, padded):
        del padded
        return grouped_relu2_mlp(x, w_up, w_down, tile_group, n_live)

    base_x = rng.normal(size=(args.pairs, args.d))
    base_dy = rng.normal(size=(args.pairs, args.d))

    def spread(base, live):
        """The same rows in every layout: a group's rows from its first
        tile's first row, zeros elsewhere."""
        out = np.zeros((live.shape[0], base.shape[1]))
        out[live] = base
        return out

    programs, lives = {}, {}
    for name, fn, tile in (("pallas_t256", pallas, 256),
                           ("pallas_t128", pallas, 128),
                           ("pallas_t512", pallas, 512),
                           ("ragged_dot", ragged, 256),
                           ("per_tile_einsum", per_tile, 256)):
        tile_group, n_live, live, padded = layout(sizes, tile, args.bound)
        x = jnp.asarray(spread(base_x, live), jnp.bfloat16)
        dy = jnp.asarray(spread(base_dy, live), jnp.float32)
        operands = (x, w_up, w_down, dy, tile_group, n_live, padded)
        programs[name] = (grad_of(fn).lower(*operands).compile(), operands)
        lives[name] = live
    rows = []
    for name, timed in device_ms(programs, args.calls).items():
        c = programs[name][0]
        row = {"program": name, "sizes": sizes.tolist(),
               "buffer_rows": int(lives[name].shape[0]),
               "temp_mb": round(
                   c.memory_analysis().temp_size_in_bytes / 1e6, 1), **timed}
        rows.append(row)
        print(json.dumps(row))
    want = None
    gaps = {}
    for name, (c, operands) in programs.items():
        dx, dup, ddown = c(*operands)
        got = [np.asarray(jnp.where(lives[name][:, None], dx, 0),
                          np.float32)[lives[name]],
               np.asarray(dup), np.asarray(ddown)]
        if want is None:
            want = got
        gaps[name] = [float(np.abs(g - w).max() / np.abs(w).max())
                      for g, w in zip(got, want)]
    print(json.dumps({"gap_to_first_dx_dup_ddown": gaps}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind, "rows": rows,
                       "gap_to_first_dx_dup_ddown": gaps}, f, indent=1)


if __name__ == "__main__":
    main()
