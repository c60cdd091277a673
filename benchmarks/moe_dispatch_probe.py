#!/usr/bin/env python
"""The dropless dispatch alone, on the chip: device time a call of
``combine(gather_rows(x, plan), weight, plan)`` forward + backward (two
row gathers and two row scatter-adds: a layer-step runs one gather more),
in the forms that were weighed for ``parallel/moe_dropless.py``.

The plan is ``moe_dropless.dispatch``'s own, from a seeded choice of
experts, at three shapes: ``nemo`` (16,384 tokens, top-6 of 128 with 8
held, d 2688: a buffer of 104 tiles of which ~28 are live), ``zaya``
(top-1 of 16 with 8 held, d 2048: 72 tiles, ~38 live) and ``full`` (the
zaya shape with every token on a held expert: every tile live but the
spare ones).  Forms:

* ``xla_all_rows`` — what the package did until PR 33: ``x[plan.token]``
  and ``zeros.at[plan.token].add(rows)`` over every row of the buffer,
  the selects and products around them elementwise, autodiff's backward;
* ``package`` — the kept form (``moe_dropless.DISPATCH_FORM``): gathers
  both ways.  ``take_rows`` gathers a tile's rows a step of one XLA
  ``while`` over the plan's live tiles; ``add_rows`` gathers every token's
  first row and scatter-adds, a tile's worth a step, only the further
  rows of tokens that have several (none at top-1);
* ``live_tile_loop`` — that loop for both: a tile gathered or
  scatter-added a step (``--tiles-a-step`` sweeps more tiles a step,
  ``_sorted`` tells the gather and the scatter that a tile's tokens ascend
  and are distinct);
* ``prefix_ladder`` — the same whole-buffer ops over ``rows[:n]`` for
  ``--rungs`` static prefixes, the rung chosen from ``n_live`` by
  ``lax.switch``;
* ``top1_inverse`` (top-1 shapes only) — ``add_rows`` as the exact gather
  ``out[t] = rows[inv[t]]`` and nothing else, the take side as the
  loop's: what the package's form comes to at top-1;
* ``plan`` — ``moe_dropless.dispatch`` alone (the sort, the layout, and
  the tokens' side of it that the package's ``add_rows`` reads);
* ``mosaic_take`` against ``xla_take`` and ``loop_take`` (the gather
  ALONE, a float32 source): a Mosaic kernel that walks the live tiles
  with ``token`` in SMEM and copies a row a DMA, HBM to HBM.  Mosaic
  (jax 0.9.0) refuses a one-row slice of an array tiled (8, 128) — "Slice
  shape along dimension 0 must be aligned to tiling (8)", (16 for
  bfloat16) — in HBM and in VMEM alike, so the source and the result are
  (T, 1, d): a row is then a tile, and XLA pays a copy of the whole
  source on the way in and of the whole buffer on the way out.

Each is compiled once, run ``--calls`` times inside one profiler capture
and read by DEVICE time, with its largest ops (``benchmarks/
ssm_conv_probe.py``'s ``device_ms``).

    chiprun -- env PYTHONPATH=. python benchmarks/moe_dispatch_probe.py \
        --out chiprun_out/moe_dispatch_probe.json

About two minutes on one chip.  Off the chip the capture has no device
plane: rows without times.  PERF.md §6 (PR 33) rests on this table.
"""

import argparse
import functools
import json
import os

import jax

from benchmarks.ssm_conv_probe import device_ms

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import default_interpret
from chainermn_tpu.parallel import moe_dropless as moe

SHAPES = {
    # name: (tokens, d, experts, held, top_k, every token on a held expert)
    "nemo": (16384, 2688, 128, 8, 6, False),
    "zaya": (16384, 2048, 16, 8, 1, False),
    "full": (16384, 2048, 16, 8, 1, True),
}


def choice(rng, tokens, experts, held, top_k, full):
    """(tokens, top_k) distinct experts a token, uniform over all the
    experts — or over the held ones alone."""
    scores = rng.random((tokens, held if full else experts))
    return np.argsort(-scores, axis=1)[:, :top_k].astype(np.int32)


# ---------------------------------------------------------------- the forms

def all_rows_take(x, plan, scale=None, y=None):
    took = jnp.where(plan.valid[:, None], x[plan.token], 0)
    if y is None:
        return took
    dots = jnp.sum(jnp.where(plan.valid[:, None], y.astype(jnp.float32), 0.0)
                   * took, axis=-1)
    return (took * scale[:, None]).astype(y.dtype), dots


def all_rows_add(rows, plan, n_tokens, scale=None):
    add = jnp.where(plan.valid[:, None], rows.astype(jnp.float32), 0.0)
    if scale is not None:
        add = add * scale[:, None]
    out = jnp.zeros((n_tokens, rows.shape[1]), jnp.float32).at[
        plan.token].add(add)
    return jnp.where(plan.past_bound > 0, jnp.nan, out)


def loop_forms(tiles_a_step, hints):
    """The live-tile loop, ``tiles_a_step`` tiles a step (a divisor of the
    buffer's tiles); with ``hints`` the tokens of a step are declared
    sorted and distinct (one tile a step only: two groups may share a
    token)."""

    def over(plan, body, init):
        n_tiles = plan.tile_group.shape[0]
        step_rows = plan.token.shape[0] // n_tiles * tiles_a_step
        steps = -(-jnp.minimum(plan.n_live[0], n_tiles) // tiles_a_step)

        def step(t, carry):
            at = t * step_rows
            return body(at, lambda a: lax.dynamic_slice_in_dim(
                a, at, step_rows), carry)

        return lax.fori_loop(0, steps, step, init)

    def tokens(tile, plan, n_tokens):
        token = tile(plan.token)
        if not hints:
            return token, {}
        # rows without a pair follow a tile's pairs: past the last token,
        # ascending, dropped
        return jnp.where(tile(plan.valid), token, n_tokens + jnp.arange(
            token.shape[0])), dict(indices_are_sorted=True,
                                   unique_indices=True)

    def take(x, plan, scale=None, y=None):
        rows, d = plan.token.shape[0], x.shape[1]

        def body(at, tile, carry):
            ok = tile(plan.valid)[:, None]
            token, said = tokens(tile, plan, x.shape[0])
            took = jnp.where(ok, x.at[token].get(
                mode="fill", fill_value=0, **said), 0)
            if y is None:
                return lax.dynamic_update_slice_in_dim(carry, took, at, 0)
            out, dots = carry
            dot = jnp.sum(jnp.where(ok, tile(y).astype(jnp.float32), 0.0)
                          * took, axis=-1)
            return (lax.dynamic_update_slice_in_dim(
                        out, (took * tile(scale)[:, None]).astype(out.dtype),
                        at, 0),
                    lax.dynamic_update_slice_in_dim(dots, dot, at, 0))

        init = jnp.zeros((rows, d), x.dtype) if y is None else (
            jnp.zeros((rows, d), y.dtype), jnp.zeros((rows,), jnp.float32))
        return over(plan, body, init)

    def add(rows, plan, n_tokens, scale=None):
        def body(at, tile, out):
            add = tile(rows).astype(jnp.float32)
            if scale is not None:
                add = add * tile(scale)[:, None]
            token, said = tokens(tile, plan, n_tokens)
            return out.at[token].add(
                jnp.where(tile(plan.valid)[:, None], add, 0.0),
                mode="drop", **said)

        init = jnp.full((n_tokens, rows.shape[1]), jnp.where(
            plan.past_bound > 0, jnp.nan, 0.0), jnp.float32)
        return over(plan, body, init)

    return take, add


def ladder_forms(rungs):
    """Whole-buffer ops over a static prefix of the rows, the shortest of
    ``rungs`` evenly spaced ones that holds the live tiles."""

    def rung_of(plan):
        n_tiles = plan.tile_group.shape[0]
        tile_rows = plan.token.shape[0] // n_tiles
        ends = sorted({-(-n_tiles * (i + 1) // rungs) for i in range(rungs)})
        return [e * tile_rows for e in ends], jnp.searchsorted(
            jnp.asarray(ends, jnp.int32), plan.n_live[0], side="left")

    def head(plan, n):
        return plan._replace(token=plan.token[:n], pair=plan.pair[:n],
                             valid=plan.valid[:n])

    def padded(a, rows):
        return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (
            a.ndim - 1))

    def take(x, plan, scale=None, y=None):
        rows = plan.token.shape[0]
        ends, rung = rung_of(plan)

        def branch(n):
            def run(_):
                if y is None:
                    return padded(all_rows_take(x, head(plan, n)), rows)
                dy, dots = all_rows_take(x, head(plan, n), scale[:n], y[:n])
                return padded(dy, rows), padded(dots, rows)
            return run

        return lax.switch(rung, [branch(n) for n in ends], None)

    def add(rows, plan, n_tokens, scale=None):
        ends, rung = rung_of(plan)
        return lax.switch(rung, [
            (lambda n: lambda _: all_rows_add(
                rows[:n], head(plan, n), n_tokens,
                None if scale is None else scale[:n]))(n)
            for n in ends], None)

    return take, add


def top1_inverse_forms():
    """``add_rows`` as a gather: at one expert a token a token has one row
    or none."""
    take, _ = loop_forms(1, False)

    def add(rows, plan, n_tokens, scale=None):
        n_rows = plan.token.shape[0]
        inv = jnp.full((n_tokens,), n_rows, jnp.int32).at[
            jnp.where(plan.valid, plan.token, n_tokens)].set(
            jnp.arange(n_rows, dtype=jnp.int32), mode="drop")
        out = rows.at[inv].get(mode="fill", fill_value=0).astype(jnp.float32)
        if scale is not None:
            out = out * scale.at[inv].get(mode="fill", fill_value=0)[:, None]
        return jnp.where(plan.past_bound > 0, jnp.nan, out)

    return take, add


def dispatch_of(take, add):
    """``(gather_rows, combine)`` over one pair of row movers, joined as
    the package joins its own."""

    @jax.custom_vjp
    def gather_rows(x, plan):
        return take(x, plan)

    def gather_fwd(x, plan):
        return take(x, plan), (plan, jnp.zeros((x.shape[0], 0), x.dtype))

    def gather_bwd(saved, drows):
        plan, like_x = saved
        return add(drows, plan, like_x.shape[0]).astype(like_x.dtype), None

    gather_rows.defvjp(gather_fwd, gather_bwd)

    def pair_weights(weight, plan):
        return jnp.where(plan.valid, weight.reshape(-1)[plan.pair], 0.0)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def combine(y, weight, plan, n_tokens):
        return add(y, plan, n_tokens, pair_weights(weight, plan))

    def combine_fwd(y, weight, plan, n_tokens):
        return combine(y, weight, plan, n_tokens), (y, weight, plan)

    def combine_bwd(n_tokens, saved, dout):
        y, weight, plan = saved
        dy, dots = take(dout, plan, pair_weights(weight, plan), y)
        dweight = jnp.zeros((weight.size,), jnp.float32).at[plan.pair].add(
            dots).reshape(weight.shape)
        return dy, dweight, None

    combine.defvjp(combine_fwd, combine_bwd)
    return gather_rows, combine


def plain_dispatch():
    def gather_rows(x, plan):
        return all_rows_take(x, plan)

    def combine(y, weight, plan, n_tokens):
        w = jnp.where(plan.valid, weight.reshape(-1)[plan.pair], 0.0)
        return all_rows_add(y, plan, n_tokens, w)

    return gather_rows, combine


# ------------------------------------------- the Mosaic row copier, take only

def _mosaic_take_kernel(token_ref, n_live_ref, src_ref, out_ref, sem, *,
                        tile_rows):
    t = pl.program_id(0)

    @pl.when(t < n_live_ref[0])
    def _():
        def copy(r):
            return pltpu.make_async_copy(
                src_ref.at[token_ref[t * tile_rows + r]],
                out_ref.at[t * tile_rows + r], sem)

        lax.fori_loop(0, tile_rows, lambda r, c: (copy(r).start(), c)[1], 0)
        lax.fori_loop(0, tile_rows, lambda r, c: (copy(r).wait(), c)[1], 0)


def mosaic_take(x, plan):
    """Rows of a float32 ``x`` by DMA, HBM to HBM; a row without a pair
    copies token 0's (the caller selects it away)."""
    n_tiles = plan.tile_group.shape[0]
    rows, d = plan.token.shape[0], x.shape[1]
    out = pl.pallas_call(
        functools.partial(_mosaic_take_kernel, tile_rows=rows // n_tiles),
        out_shape=jax.ShapeDtypeStruct((rows, 1, d), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=default_interpret(), name="moe-take-rows",
    )(plan.token, plan.n_live, x.reshape(x.shape[0], 1, d))
    return jnp.where(plan.valid[:, None], out.reshape(rows, d), 0)


# ------------------------------------------------------------------ the run

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="nemo,zaya,full")
    ap.add_argument("--tokens", type=int, default=None,
                    help="fewer tokens than the cells' (a CPU rehearsal)")
    ap.add_argument("--tiles-a-step", default="1,2,4")
    ap.add_argument("--rungs", type=int, default=8)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rng = np.random.default_rng(args.seed)
    programs, meta, checks = {}, {}, {}
    for shape in args.shapes.split(","):
        tokens, d, experts, held, top_k, full = SHAPES[shape]
        tokens = args.tokens or tokens
        chosen = choice(rng, tokens, experts, held, top_k, full)
        n_rows = moe.rows_bound(tokens * top_k, held, experts)
        plan_of = jax.jit(lambda c: moe.dispatch(c, (0, held), n_rows))
        plan = plan_of(jnp.asarray(chosen))
        n_tiles = int(plan.tile_group.shape[0])
        stats = moe.load_stats(chosen, experts, (0, held))
        x = jnp.asarray(rng.normal(size=(tokens, d)), jnp.bfloat16)
        weight = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
        dout = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)

        forms = {"xla_all_rows": plain_dispatch(),
                 "package": (moe.gather_rows, moe.combine)}
        for c in (int(c) for c in args.tiles_a_step.split(",")):
            if n_tiles % c == 0:
                forms[f"live_tile_loop_{c}"] = dispatch_of(
                    *loop_forms(c, False))
        forms["live_tile_loop_1_sorted"] = dispatch_of(*loop_forms(1, True))
        forms[f"prefix_ladder_{args.rungs}"] = dispatch_of(
            *ladder_forms(args.rungs))
        if top_k == 1:
            forms["top1_inverse"] = dispatch_of(*top1_inverse_forms())

        def grad_of(gather_rows, combine):
            def loss(x, weight, dout, plan):
                # the experts stand between the two in a layer: a product
                # that keeps the compiler from joining them
                rows = gather_rows(x, plan) * jnp.asarray(0.5, x.dtype)
                return jnp.sum(combine(rows, weight, plan, tokens) * dout)
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

        for name, (gather_rows, combine) in forms.items():
            operands = (x, weight, dout, plan)
            key = f"{shape}/{name}"
            programs[key] = (grad_of(gather_rows, combine).lower(
                *operands).compile(), operands)
            meta[key] = {"shape": shape, "form": name, "d": d,
                         "top_k": top_k, **stats}

        # the plan itself: the sort, the layout and the tokens' side
        programs[f"{shape}/plan"] = (
            plan_of.lower(jnp.asarray(chosen)).compile(),
            (jnp.asarray(chosen),))
        meta[f"{shape}/plan"] = {"shape": shape, "form": "plan", "d": d,
                                 "top_k": top_k, **stats}
        x32 = x.astype(jnp.float32)
        takes = {
            "xla_take": lambda x, plan: all_rows_take(x, plan),
            "loop_take": lambda x, plan: loop_forms(1, False)[0](x, plan),
            "mosaic_take": mosaic_take}
        for name, take in takes.items():
            key = f"{shape}/{name}"
            programs[key] = (jax.jit(take).lower(x32, plan).compile(),
                             (x32, plan))
            meta[key] = {"shape": shape, "form": name, "d": d,
                         "top_k": top_k, **stats}
        checks[shape] = (list(forms), list(takes), plan)

    rows = []
    for key, timed in device_ms(programs, args.calls, top=12).items():
        c = programs[key][0]
        row = {"program": key, **meta[key], "temp_mb": round(
            c.memory_analysis().temp_size_in_bytes / 1e6, 1), **timed}
        rows.append(row)
        print(json.dumps(row))
    gaps = {}
    for shape, (forms, takes, plan) in checks.items():
        want = None
        for name in forms:
            c, operands = programs[f"{shape}/{name}"]
            loss, (dx, dweight) = c(*operands)
            got = [np.asarray(loss, np.float64),
                   np.asarray(dx.astype(jnp.float32)), np.asarray(dweight)]
            want = want or got
            gaps[f"{shape}/{name}"] = [
                float(np.abs(g - w).max() / (np.abs(w).max() + 1e-30))
                for g, w in zip(got, want)]
        live = np.repeat(np.arange(plan.tile_group.shape[0])
                         < int(plan.n_live[0]),
                         plan.token.shape[0] // plan.tile_group.shape[0])
        want = None
        for name in takes:
            c, operands = programs[f"{shape}/{name}"]
            got = np.asarray(c(*operands))[live]
            want = got if want is None else want
            gaps[f"{shape}/{name}"] = [float(np.abs(got - want).max())]
    print(json.dumps({"gap_to_first_loss_dx_dweight": gaps}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind, "rows": rows,
                       "gap_to_first_loss_dx_dweight": gaps}, f, indent=1)


if __name__ == "__main__":
    main()
