#!/usr/bin/env python
"""The state-space scan alone, on the chip: device time a call of its
forward and of its backward at both hybrid cells' geometries, for the two
Mosaic kernels (``ops.ssd.ssd_scan``) and for the ``lax.fori_loop`` form
they replaced (PR 31), which lives on here as the comparison.

Each is compiled at ``(2, 8192, 64 heads of 64, state 128)`` with 1 group
and chunk 256 (``granite4hm-train-1chip``) and with 8 groups and chunk 128
(``nemo3nano-train-1chip``), in bfloat16, forward and vjp apart, the
operands' layouts left to the compiler as inside a step.  Each program
runs ``--calls`` times inside one profiler capture and is read by DEVICE
time (``observability.device_trace``), with its largest ops; the loop
form's device ops a block are counted in its optimized HLO (the loop
bodies' ``fusion`` / ``copy`` / ... instructions).

    chiprun -- env PYTHONPATH=. python benchmarks/ssd_probe.py \
        --out chiprun_out/ssd_probe.json

About two minutes on one chip.  Off the chip the kernels run interpreted
and the capture has no device plane: rows without times (use ``--seq
512`` there).  PERF.md §6 (PR 31) rests on this table.
"""

import argparse
import functools
import json
import os
import re

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from chainermn_tpu.observability.spans import named_scope
from chainermn_tpu.ops import ssd
from ssm_conv_probe import device_ms

GEOMETRIES = {"granite": (1, 256), "nemo": (8, 128)}     # groups, chunk


# ---- the loop form, as ``ops/ssd.py`` had it until PR 31: a
# ``lax.fori_loop`` over the blocks around ``_block`` / ``jax.vjp(_block)``

def _block(h_prev, x, dt, B, C, A, D):
    """One block of ``Q`` tokens, every batch row and head at once, heads
    before tokens (the layout the matrix unit wants them in).

    ``h_prev`` (b, G, r, P, N) float32; ``x`` (b, G, r, Q, P); ``dt``
    (b, G, r, Q) float32; ``B``, ``C`` (b, G, Q, N); ``A``, ``D`` (G, r)
    float32 — ``G`` groups of ``r`` heads.  Returns ``(h_next, y)``, ``y``
    float32 (b, G, r, Q, P) with the skip ``D x`` added."""
    f32, op = jnp.float32, x.dtype
    Q = x.shape[3]
    live = jnp.tril(jnp.ones((Q, Q), bool))               # [t, s]: s <= t
    # the running sum as a (tiny) product with the triangle, at full
    # precision: a ``cumsum`` lowers to reduce-windows that lose their
    # scope and cost more
    cs = jnp.einsum("bgrs,ts->bgrt", dt * A[..., None], live.astype(f32),
                    precision=lax.Precision.HIGHEST)
    gap = cs[..., :, None] - cs[..., None, :]             # cs_t - cs_s
    decay = jnp.exp(jnp.where(live, gap, -jnp.inf))       # (b, G, r, Q, Q)
    cb = jnp.einsum("bgqn,bgsn->bgqs", C, B, preferred_element_type=f32)
    weights = (cb[:, :, None] * decay).astype(op)
    xdt = x.astype(f32) * dt[..., None]
    y = jnp.einsum("bgrqs,bgrsp->bgrqp", weights, xdt.astype(op),
                   preferred_element_type=f32)
    carried = jnp.einsum("bgqn,bgrpn->bgrqp", C, h_prev.astype(op),
                         preferred_element_type=f32)
    y = (y + jnp.exp(cs)[..., None] * carried
         + D[..., None, None] * x.astype(f32))
    to_end = jnp.exp(cs[..., -1:] - cs)                   # (b, G, r, Q)
    h_next = (jnp.exp(cs[..., -1])[..., None, None] * h_prev
              + jnp.einsum("bgrqp,bgqn->bgrpn",
                           (xdt * to_end[..., None]).astype(op), B,
                           preferred_element_type=f32))
    return h_next, y


#: the token axis of each operand of :func:`_ssd`, in its order
_TOKEN_AXES = (3, 3, 2, 2)          # x, dt, B, C


def _blocks_of(arrays, i, chunk):
    return tuple(lax.dynamic_slice_in_dim(a, i * chunk, chunk, axis)
                 for a, axis in zip(arrays, _TOKEN_AXES))


def _put_block(a, i, block, chunk, axis):
    return lax.dynamic_update_slice_in_dim(
        a, block.astype(a.dtype), i * chunk, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _loop_ssd(x, dt, B, C, A, D, chunk):
    """``x`` (b, G, r, S, P), ``dt`` (b, G, r, S), ``B``, ``C``
    (b, G, S, N): heads before tokens, so that a block is a run of rows
    of every head."""
    return _ssd_fwd(x, dt, B, C, A, D, chunk)[0]


# Both passes walk the blocks by index over the whole arrays and write each
# block's results in place: handing ``lax.scan`` block-major operands costs
# a transposing copy of every operand and result (PERF.md §6, PR 26).

def _ssd_fwd(x, dt, B, C, A, D, chunk):
    with named_scope("ssd-scan"):
        b, G, r, S, P = x.shape
        n, N = S // chunk, B.shape[-1]

        def step(i, carry):
            h, starts, y = carry
            h_next, y_i = _block(
                h, *_blocks_of((x, dt, B, C), i, chunk), A, D)
            return (h_next, lax.dynamic_update_index_in_dim(starts, h, i, 0),
                    _put_block(y, i, y_i, chunk, 3))

        zero = jnp.zeros((b, G, r, P, N), jnp.float32)
        _, starts, y = lax.fori_loop(0, n, step, (
            zero, jnp.zeros((n,) + zero.shape, jnp.float32),
            jnp.zeros_like(x)))
        return y, (x, dt, B, C, A, D, starts)


def _ssd_bwd(chunk, saved, dy):
    x, dt, B, C, A, D, starts = saved
    with named_scope("ssd-scan"):
        n = x.shape[3] // chunk

        def step(k, carry):
            i = n - 1 - k
            dh, dA, dD, grads = carry
            _, pull = jax.vjp(_block, starts[i],
                              *_blocks_of((x, dt, B, C), i, chunk), A, D)
            dy_i = lax.dynamic_slice_in_dim(dy, i * chunk, chunk, 3)
            dh, *here, dA_i, dD_i = pull((dh, dy_i.astype(jnp.float32)))
            return (dh, dA + dA_i, dD + dD_i, tuple(
                _put_block(g, i, g_i, chunk, axis)
                for g, g_i, axis in zip(grads, here, _TOKEN_AXES)))

        _, dA, dD, grads = lax.fori_loop(0, n, step, (
            jnp.zeros_like(starts[0]), jnp.zeros_like(A), jnp.zeros_like(D),
            tuple(jnp.zeros_like(a) for a in (x, dt, B, C))))
        return (*grads, dA, dD)


_loop_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def loop_scan(x, dt, A, B, C, D, *, chunk):
    """``ssd_scan`` by the loop form: heads before tokens, and back."""
    b, S, H, P = x.shape
    G = B.shape[2]
    r = H // G
    heads_first = (
        x.reshape(b, S, G, r, P).transpose(0, 2, 3, 1, 4),
        dt.astype(jnp.float32).reshape(b, S, G, r).transpose(0, 2, 3, 1),
        B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3))
    y = _loop_ssd(*heads_first, A.astype(jnp.float32).reshape(G, r),
                  D.astype(jnp.float32).reshape(G, r), chunk)
    return y.transpose(0, 3, 1, 2, 4).reshape(b, S, H, P)


def loop_body_ops(compiled):
    """Device ops in the bodies of a compiled program's ``while`` loops:
    the instructions of each body computation that run as an op of their
    own (no parameter, tuple plumbing, constant or bitcast)."""
    text = compiled.as_text()
    bodies = set(re.findall(r"body=(%?[\w.\-]+)", text))
    free = ("parameter(", "get-tuple-element(", "tuple(", "constant(",
            "bitcast(")
    count = 0
    for block in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)",
                          text):
        name = block.split(" ", 1)[0]
        if name in bodies or name.lstrip("%") in {
                b.lstrip("%") for b in bodies}:
            count += sum(
                1 for line in block.splitlines()[1:]
                if " = " in line and not any(f in line for f in free))
    return count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--d-head", type=int, default=64)
    ap.add_argument("--d-state", type=int, default=128)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--only", default=None,
                    help="granite | nemo (default: both)")
    ap.add_argument("--no-loop", action="store_true",
                    help="the kernels alone")
    ap.add_argument("--heads-a-step", default=None,
                    help="comma-separated caps on the heads a grid step "
                    "holds, tried in place of ops.ssd._SSD_HEADS (the "
                    "sweep the constant was chosen by)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    b, S, H, P, N = (args.batch, args.seq, args.heads, args.d_head,
                     args.d_state)
    here = SingleDeviceSharding(jax.devices()[0])
    free = Format(Layout.AUTO, here)

    def compiled(fn, operands, outs):
        """``fn`` with the layouts of its activations (operands and
        results of three or four axes) the compiler's choice, the per-head
        vectors' row-major, and the operands placed in them."""
        vector = Format(Layout(major_to_minor=(0,)), here)
        c = jax.jit(
            fn, in_shardings=tuple(vector if a.ndim == 1 else free
                                   for a in operands),
            out_shardings=tuple(vector if n == 1 else free for n in outs)
            if len(outs) > 1 else free).lower(*operands).compile()
        return c, tuple(jax.device_put(a, f)
                        for a, f in zip(operands, c.input_formats[0]))

    rows, gaps = [], {}
    for cell, (G, chunk) in GEOMETRIES.items():
        if args.only not in (None, cell):
            continue
        rng = np.random.RandomState(0)
        bf16, f32 = jnp.bfloat16, jnp.float32
        operands = (
            jnp.asarray(rng.randn(b, S, H, P), bf16),
            jnp.asarray(np.log1p(np.exp(rng.randn(b, S, H) - 2.0)), f32),
            jnp.asarray(-np.exp(rng.rand(H) * 2.7), f32),
            jnp.asarray(rng.randn(b, S, G, N) * 0.3, bf16),
            jnp.asarray(rng.randn(b, S, G, N) * 0.3, bf16),
            jnp.asarray(rng.randn(H), f32))
        dy = jnp.asarray(rng.randn(b, S, H, P), bf16)

        def vjp_of(scan):
            return lambda *a: jax.vjp(
                functools.partial(scan, chunk=chunk), *a[:-1])[1](a[-1])

        forms = {"kernel": ssd.ssd_scan}
        if not args.no_loop:
            forms["loop"] = loop_scan
        programs, tiles = {}, {}
        for form, scan in forms.items():
            caps = [None] if form == "loop" or not args.heads_a_step else [
                int(c) for c in args.heads_a_step.split(",")]
            for cap in caps:
                if cap is not None:
                    ssd._SSD_HEADS = cap
                    jax.clear_caches()
                    form = f"kernel{cap}"
                tiles[form] = ssd.ssd_tiles(S, chunk, H, G, P, N, bf16)
                programs[f"{cell}.{form}.forward"] = compiled(
                    functools.partial(scan, chunk=chunk), operands, (4,))
                programs[f"{cell}.{form}.backward"] = compiled(
                    vjp_of(scan), operands + (dy,), (4, 3, 1, 4, 4, 1))
        for name, timed in device_ms(programs, args.calls).items():
            c = programs[name][0]
            row = {"program": name, "groups": G, "chunk": chunk,
                   "blocks": S // chunk,
                   "temp_mb": round(
                       c.memory_analysis().temp_size_in_bytes / 1e6, 1),
                   **timed}
            if ".loop." in name:
                row["loop_body_ops"] = loop_body_ops(c)
            else:
                hb, vmem = tiles[name.split(".")[1]]
                row.update(heads_a_step=hb, vmem_mb=round(vmem / 2**20, 2),
                           grid_steps=b * (S // chunk) * (H // hb))
            rows.append(row)
            print(json.dumps(row), flush=True)

        def ran(name):
            c, placed = programs[name]
            out = c(*placed)
            out = out if isinstance(out, (tuple, list)) else (out,)
            return [np.asarray(a, np.float32) for a in out]

        if not args.no_loop and not args.heads_a_step:
            # the kernels against the loop form, on this device: y, then
            # dx, ddt, dA, dB, dC, dD, each over the loop's largest
            gaps[cell] = [
                float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-30))
                for g, w in zip(
                    ran(f"{cell}.kernel.forward")
                    + ran(f"{cell}.kernel.backward"),
                    ran(f"{cell}.loop.forward")
                    + ran(f"{cell}.loop.backward"))]
            print(json.dumps({"cell": cell,
                              "gap_y_dx_ddt_dA_dB_dC_dD": gaps[cell]}),
                  flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "rows": rows, "gap_y_dx_ddt_dA_dB_dC_dD": gaps},
                      f, indent=1)


if __name__ == "__main__":
    main()
