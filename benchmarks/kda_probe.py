#!/usr/bin/env python
"""The Kimi-Delta-Attention rule WITH its gate side, on the chip: device
time a call of its forward and of its backward at the
``ling3flash-train-1chip`` cell's geometry, from what ``KDAMixer``'s
convolution and projections hand over (``q``, ``k``, ``v``, ``f`` in
bfloat16, ``beta``, ``A_log``, ``dt_bias``) to ``o`` and back, for

- ``kernel``: the tree's ``ops.kda.kda_rule``.  Since PR 52 its two
  Mosaic kernels (``kda-fwd`` / ``kda-bwd``) make the heads' float32 side
  themselves (``gate_side: "kernel"``).  Run from an older checkout
  (``PYTHONPATH=<checkout>``) the same row is that tree's form: the gate
  side as XLA ops beside the calls (:func:`gate_side`, ``KDAMixer``'s
  formulas until PR 52), the running sums a product with the triangle
  over a float32 array, then the kernels (``gate_side: "xla"``);
- ``xla``: the XLA chunked form the kernels replaced (PR 44), behind the
  same XLA gate side, which lives on here as the comparison: every decay
  a float32 array in HBM, the solve by substitution on 16-row blocks
  joined pairwise with the batch on the lanes
  (:func:`unit_lower_inverse`, which ``gdn_probe.py``'s XLA form runs
  too), a ``lax.scan`` step a chunk, the heads in rematerialised groups
  under ``lax.map``, autodiff's backward.

Each is compiled at ``(1, 16384, 32 heads of 128, chunk 64)``, forward
and vjp apart, the operands' layouts left to the compiler as inside a
step.  Each program runs ``--calls`` times inside one profiler capture
and is read by DEVICE time (``observability.device_trace``), with its
largest ops; then the kernels' results against the XLA form's on this
device, by operand (``o``, ``dq``, ``dk``, ``dv``, ``df``, ``dbeta``,
``dA_log``, ``ddt_bias``, each over the XLA form's largest).

    chiprun -- env PYTHONPATH=. python benchmarks/kda_probe.py \
        --out chiprun_out/kda_probe.json

About two minutes on one chip.  Off the chip the kernels run interpreted
and the capture has no device plane: rows without times (use ``--seq 256
--heads 2`` there).  PERF.md section 6 (PR 44, PR 52) rests on this
table.
"""

import argparse
import functools
import inspect
import json
import os

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from chainermn_tpu.observability.spans import named_scope
from chainermn_tpu.ops import kda
from chainermn_tpu.ops.kda import SUB
from ssm_conv_probe import device_ms

_HIGHEST = lax.Precision.HIGHEST


# ---- the XLA chunked form, as ``ops/kda.py`` had it until PR 44

#: Side of the diagonal blocks inverted by substitution, a row a step;
#: larger blocks are put together from their halves.
_BASE = 16

#: Tokens x heads a group of heads holds at most (16,384 tokens: 4 heads,
#: about 0.7 GB of chunk matrices between the passes).
_GROUP_TOKEN_HEADS = 16384 * 4


def heads_a_group(tokens: int, heads: int) -> int:
    """Heads a caller works together: the most that divide ``heads`` with
    ``tokens x heads`` within :data:`_GROUP_TOKEN_HEADS` (at least one)."""
    return max([h for h in range(1, heads + 1)
                if heads % h == 0 and tokens * h <= _GROUP_TOKEN_HEADS],
               default=1)


def _substitute(a):
    """``(I + a)^-1`` by forward substitution, a row a step: row ``i`` is
    ``e_i - sum_{j<i} a_ij row_j``.  ``a``: (m, m, N), strictly lower
    triangular in its first two axes, the batch LAST (on the lanes)."""
    m, _, N = a.shape
    eye = jnp.eye(m, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0][:, None], (m, N))]
    for i in range(1, m):
        done = jnp.stack(rows)                              # (i, m, N)
        rows.append(eye[i][:, None]
                    - jnp.sum(a[i, :i, None, :] * done, axis=0))
    return jnp.stack(rows)


def _mm(x, y):
    """``x @ y`` over the first two axes, the batch last: float32
    multiplies and adds, no matrix unit (the blocks are 16 or 32 wide)."""
    return jnp.sum(x[:, :, None, :] * y[None, :, :, :], axis=1)


def _inverse(a):
    n, _, N = a.shape
    if n <= _BASE or n % 2:
        return _substitute(a)
    h = n // 2
    # Both halves' diagonal blocks side by side on the batch axis; then
    # [[T11, 0], [-T22 A21 T11, T22]].
    both = _inverse(jnp.concatenate([a[:h, :h], a[h:, h:]], axis=-1))
    t11, t22 = both[..., :N], both[..., N:]
    t21 = -_mm(_mm(t22, a[h:, :h]), t11)
    top = jnp.concatenate([t11, jnp.zeros_like(t21)], axis=1)
    return jnp.concatenate(
        [top, jnp.concatenate([t21, t22], axis=1)], axis=0)


def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., n, n) strictly lower triangular
    (what lies on or above the diagonal is NOT read as zero: the caller
    masks it), float32.  Substitution on the diagonal blocks of 16 rows,
    the blocks joined pairwise by ``-T22 A21 T11``: backward-stable as
    substitution is.  Worked with the batch on the last axis, so that a
    step's small rows fill whole registers of lanes."""
    lead, n = a.shape[:-2], a.shape[-1]
    flat = jnp.moveaxis(a.reshape((-1, n, n)), 0, -1)
    return jnp.moveaxis(_inverse(flat), -1, 0).reshape(lead + (n, n))


def _chunked(q, k, v, g, beta, C):
    """The chunked rule for heads that all fit at once: ``q``, ``k`` (b,
    S, H, d_k), ``v`` (b, S, H, d_v), ``g`` (b, S, H, d_k) and ``beta``
    (b, S, H) float32, chunks of ``C`` tokens."""
    b, S, H, dk = q.shape
    dv = v.shape[-1]
    n = -(-S // C)
    f32, dt = jnp.float32, v.dtype
    pad = n * C - S
    sub = SUB if C % SUB == 0 else C
    nb = C // sub

    def chunks(x):
        """(b, S, H, ...) -> (b, H, n, C, ...)"""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta))
    q32, k32 = qc.astype(f32), kc.astype(f32)

    G = jnp.cumsum(gc, axis=3)                        # (b, H, n, C, dk)
    row = jnp.arange(C)
    below = row[:, None] > row[None, :]
    upto = row[:, None] >= row[None, :]

    def blocks(x):
        """(b, H, n, C, dk) -> (b, H, n, nb, sub, dk)"""
        return x.reshape(x.shape[:3] + (nb, sub, dk))

    Gb = blocks(G)
    ref = Gb[..., :1, :]                              # (b, H, n, nb, 1, dk)
    rows = jnp.exp(Gb - ref)                          # <= 1
    # e^{r_I - G_j} for the columns of sub-blocks up to I, 0 past them
    seen = (row[None, :] // sub <= jnp.arange(nb)[:, None])[..., None]
    cols = jnp.where(seen, jnp.exp(jnp.where(
        seen, ref - G[:, :, :, None], 0.0)), 0.0)     # (b, H, n, nb, C, dk)
    k_cols = (k32[:, :, :, None] * cols).astype(dt)

    def against_the_columns(x32):
        out = jnp.einsum("bhnIic,bhnIjc->bhnIij",
                         (blocks(x32) * rows).astype(dt), k_cols,
                         preferred_element_type=f32)
        return out.reshape(out.shape[:3] + (C, C))

    A = jnp.where(below, bc[..., None] * against_the_columns(k32), 0.0)
    P = jnp.where(upto, against_the_columns(q32), 0.0).astype(dt)
    T = unit_lower_inverse(A)
    eG = jnp.exp(G)
    rhs = jnp.concatenate(
        [bc[..., None] * eG * k32, bc[..., None] * vc.astype(f32)], axis=-1)
    WU = jnp.einsum("bhnij,bhnjd->bhnid", T, rhs, precision=_HIGHEST)
    W, U = WU[..., :dk].astype(dt), WU[..., dk:]
    qG = (q32 * eG).astype(dt)
    last = G[..., -1, :]                              # (b, H, n, dk)
    kG = (k32 * jnp.exp(last[..., None, :] - G)).astype(dt)

    def step(state, now):
        W_c, U_c, qG_c, kG_c, P_c, keep = now
        held = state.astype(dt)
        v_new = U_c - jnp.einsum("bhck,bhkv->bhcv", W_c, held,
                                 preferred_element_type=f32)
        v_in = v_new.astype(dt)
        o = jnp.einsum("bhck,bhkv->bhcv", qG_c, held,
                       preferred_element_type=f32) + jnp.einsum(
            "bhij,bhjv->bhiv", P_c, v_in, preferred_element_type=f32)
        state = keep[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", kG_c, v_in, preferred_element_type=f32)
        return state, o.astype(dt)

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    _, o = lax.scan(
        step, jnp.zeros((b, H, dk, dv), f32),
        tuple(by_chunk(x) for x in (W, U, qG, kG, P, jnp.exp(last))))
    # (n, b, H, C, d_v) -> (b, S, H, d_v)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * C, H, dv)
    return o[:, :S]


def xla_rule(q, k, v, g, beta, *, chunk):
    """``kda_rule`` by the XLA form, the heads in rematerialised groups
    (``KDAMixer`` had its float32 gate side inside the groups too)."""
    b, S, H, dk = q.shape
    dv = v.shape[3]
    C = min(chunk, S)
    hg = heads_a_group(b * S, H)
    with named_scope("kda-scan"):
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        if hg == H:
            return _chunked(q, k, v, g, beta, C)

        def groups(x):
            """(b, S, H, ...) -> (H / hg, b, S, hg, ...)"""
            x = x.reshape(x.shape[:2] + (H // hg, hg) + x.shape[3:])
            return jnp.moveaxis(x, 2, 0)

        o = lax.map(
            jax.checkpoint(lambda xs: _chunked(*xs, C)),
            tuple(groups(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 2).reshape(b, S, H, dv)


def gate_side(q, k, f, a_log, dt_bias, floor):
    """``KDAMixer``'s gate side before the rule as XLA ops, as it stood
    until PR 52: float32 unit norms of ``q`` (over ``sqrt(d_k)``) and
    ``k`` rounded to the activations' type, and the float32 log-decay a
    token, head and channel."""
    f32 = jnp.float32
    with named_scope("mixer-gate"):
        def unit(x):
            x = x.astype(f32)
            return x * lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

        g = floor * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * (f.astype(f32) + dt_bias))
        return ((unit(q) * (1.0 / np.sqrt(q.shape[-1]))).astype(q.dtype),
                unit(k).astype(k.dtype), g)


#: Whether the tree's kernels make the gate side themselves (PR 52 on).
IN_KERNEL = "f" in inspect.signature(kda.kda_rule).parameters


def kernel_form(q, k, v, f, beta, a_log, dt_bias, *, floor, chunk):
    """The tree's rule from the mixer's operands."""
    if IN_KERNEL:
        return kda.kda_rule(q, k, v, f, beta, jnp.exp(a_log), dt_bias,
                            lower_bound=floor, chunk=chunk)
    q, k, g = gate_side(q, k, f, a_log, dt_bias, floor)
    return kda.kda_rule(q, k, v, g, beta, chunk=chunk)


def xla_form(q, k, v, f, beta, a_log, dt_bias, *, floor, chunk):
    q, k, g = gate_side(q, k, f, a_log, dt_bias, floor)
    return xla_rule(q, k, v, g, beta, chunk=chunk)


#: The forms' results in order: ``o``, then the seven cotangents.
RESULTS = ("o", "dq", "dk", "dv", "df", "dbeta", "dA_log", "ddt_bias")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--d-head", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--floor", type=float, default=-5.0,
                    help="the log-decay's lower bound")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--no-xla", action="store_true",
                    help="the kernels alone")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    b, S, H, d, chunk = (args.batch, args.seq, args.heads, args.d_head,
                         args.chunk)
    here = SingleDeviceSharding(jax.devices()[0])
    free = Format(Layout.AUTO, here)

    def compiled(fn, operands, outs):
        """``fn`` with the layouts of its operands and results the
        compiler's choice, and the operands placed in them."""
        c = jax.jit(
            fn, in_shardings=(free,) * len(operands),
            out_shardings=(free,) * outs if outs > 1 else free,
        ).lower(*operands).compile()
        return c, tuple(jax.device_put(a, f)
                        for a, f in zip(operands, c.input_formats[0]))

    rng = np.random.RandomState(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    # what a convolution with SiLU and a projection hand over: no unit
    # lengths; the family's initial ``A_log`` and ``dt_bias`` ranges
    operands = (
        jnp.asarray(rng.randn(b, S, H, d), bf16),
        jnp.asarray(rng.randn(b, S, H, d), bf16),
        jnp.asarray(rng.randn(b, S, H, d), bf16),
        jnp.asarray(2 * rng.randn(b, S, H, d), bf16),
        jnp.asarray(1 / (1 + np.exp(-3 * rng.randn(b, S, H))), f32),
        jnp.asarray(np.log(rng.uniform(1, 16, H)), f32),
        jnp.asarray(rng.randn(H, d), f32))
    do = jnp.asarray(rng.randn(b, S, H, d), bf16)

    def vjp_of(rule):
        return lambda *a: jax.vjp(rule, *a[:-1])[1](a[-1])

    forms = {"kernel": kernel_form}
    if not args.no_xla:
        forms["xla"] = xla_form
    programs = {}
    for form, rule in forms.items():
        rule = functools.partial(rule, floor=args.floor, chunk=chunk)
        programs[f"{form}.forward"] = compiled(rule, operands, 1)
        programs[f"{form}.backward"] = compiled(
            vjp_of(rule), operands + (do,), len(operands))
    tokens, heads, vmem = kda.kda_tiles(S, chunk, H, d, d, bf16)
    rows = []
    for name, timed in device_ms(programs, args.calls).items():
        c = programs[name][0]
        row = {"program": name, "chunk": min(chunk, S),
               "chunks": -(-S // min(chunk, S)),
               "temp_mb": round(
                   c.memory_analysis().temp_size_in_bytes / 1e6, 1),
               **timed}
        if name.startswith("kernel"):
            row.update(gate_side="kernel" if IN_KERNEL else "xla",
                       tokens_a_step=tokens, heads_a_step=heads,
                       vmem_mb=round(vmem / 2**20, 2),
                       grid_steps=b * H * (-(-S // tokens)))
        rows.append(row)
        print(json.dumps(row), flush=True)

    def ran(name):
        c, placed = programs[name]
        out = c(*placed)
        out = out if isinstance(out, (tuple, list)) else (out,)
        return [np.asarray(a, np.float32) for a in out]

    gaps = None
    if not args.no_xla:
        gaps = {
            name: float(np.abs(got - want).max()
                        / max(np.abs(want).max(), 1e-30))
            for name, got, want in zip(
                RESULTS, ran("kernel.forward") + ran("kernel.backward"),
                ran("xla.forward") + ran("xla.backward"))}
        print(json.dumps({"gaps_to_xla": gaps}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "gate_side": "kernel" if IN_KERNEL else "xla",
                       "rows": rows, "gaps_to_xla": gaps}, f, indent=1)


if __name__ == "__main__":
    main()
