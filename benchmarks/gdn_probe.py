#!/usr/bin/env python
"""The chunked gated delta rule alone, on the chip: device time a call of
its forward and of its backward at the ``qwen3next-train-1chip`` cell's
geometry, for the two Mosaic kernels (``ops.gated_delta.gated_delta_rule``:
``gdn-fwd`` / ``gdn-bwd``) and for the XLA chunked form they replaced (PR
37), which lives on here as the comparison: the solve by substitution on
16-row blocks joined pairwise, a ``lax.scan`` step a chunk, the value
heads in rematerialised groups under ``lax.map``, autodiff's backward
(the solve itself is ``kda_probe.unit_lower_inverse``, which the
Kimi-Delta-Attention rule's XLA form runs there too).

Each is compiled at ``(2, 8192, 16 key / 32 value heads of 128, chunk
64)`` in bfloat16, forward and vjp apart, the operands' layouts left to
the compiler as inside a step.  Each program runs ``--calls`` times inside
one profiler capture and is read by DEVICE time
(``observability.device_trace``), with its largest ops; then the kernels'
results against the XLA form's on this device, by operand (``o``, ``dq``,
``dk``, ``dv``, ``dg``, ``dbeta``, each over the XLA form's largest).

    chiprun -- env PYTHONPATH=. python benchmarks/gdn_probe.py \
        --out chiprun_out/gdn_probe.json

About two minutes on one chip.  Off the chip the kernels run interpreted
and the capture has no device plane: rows without times (use ``--seq 256
--key-heads 1 --value-heads 2`` there).  PERF.md §6 (PR 37) rests on this
table.
"""

import argparse
import functools
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
from chainermn_tpu.ops import gated_delta
from kda_probe import unit_lower_inverse
from ssm_conv_probe import device_ms

_HIGHEST = lax.Precision.HIGHEST


# ---- the XLA chunked form, as ``ops/gated_delta.py`` had it until PR 37

#: Tokens x value heads a group of heads holds at most (2 x 8192 tokens:
#: 8 heads, about 1.5 GB between the passes).
_GROUP_TOKEN_HEADS = 2 * 8192 * 8


def heads_a_group(tokens: int, heads: int) -> int:
    """Value heads worked together: the most that divide ``heads`` with
    ``tokens x heads`` within :data:`_GROUP_TOKEN_HEADS` (at least one)."""
    return max([h for h in range(1, heads + 1)
                if heads % h == 0 and tokens * h <= _GROUP_TOKEN_HEADS],
               default=1)


def _chunked(q, k, v, g, beta, C):
    """The chunked rule for heads that all fit at once: ``q``, ``k``
    (b, S, H, d_k), ``v`` (b, S, H, d_v), ``g``, ``beta`` (b, S, H)
    float32, chunks of ``C`` tokens."""
    b, S, Hv, dk = q.shape
    dv = v.shape[-1]
    n = -(-S // C)
    f32, dt = jnp.float32, v.dtype
    pad = n * C - S

    def chunks(x):
        """(b, S, H, ...) -> (b, H, n, C, ...)"""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta))

    G = jnp.cumsum(gc, axis=-1)                       # (b, H, n, C)
    row = jnp.arange(C)
    below = row[:, None] > row[None, :]
    upto = row[:, None] >= row[None, :]
    # e^{G_i - G_j} where j <= i (the exponent is <= 0 there), else 0
    decay = jnp.where(upto, jnp.exp(jnp.where(
        upto, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    kk = jnp.einsum("bhnid,bhnjd->bhnij", kc, kc,
                    preferred_element_type=f32)
    A = jnp.where(below, bc[..., None] * kk * decay, 0.0)
    T = unit_lower_inverse(A)
    eG = jnp.exp(G)
    rhs = jnp.concatenate(
        [(bc * eG)[..., None] * kc.astype(f32),
         bc[..., None] * vc.astype(f32)], axis=-1)
    WU = jnp.einsum("bhnij,bhnjd->bhnid", T, rhs, precision=_HIGHEST)
    W, U = WU[..., :dk].astype(dt), WU[..., dk:]
    qk = jnp.einsum("bhnid,bhnjd->bhnij", qc, kc,
                    preferred_element_type=f32)
    P = (qk * decay).astype(dt)
    qG = (qc.astype(f32) * eG[..., None]).astype(dt)
    last = G[..., -1]                                 # (b, H, n)
    kG = (kc.astype(f32)
          * jnp.exp(last[..., None] - G)[..., None]).astype(dt)

    def step(state, now):
        W_c, U_c, qG_c, kG_c, P_c, keep = now
        held = state.astype(dt)
        v_new = U_c - jnp.einsum("bhck,bhkv->bhcv", W_c, held,
                                 preferred_element_type=f32)
        v_in = v_new.astype(dt)
        o = jnp.einsum("bhck,bhkv->bhcv", qG_c, held,
                       preferred_element_type=f32) + jnp.einsum(
            "bhij,bhjv->bhiv", P_c, v_in, preferred_element_type=f32)
        state = keep[..., None, None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", kG_c, v_in, preferred_element_type=f32)
        return state, o.astype(dt)

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    _, o = lax.scan(
        step, jnp.zeros((b, Hv, dk, dv), f32),
        tuple(by_chunk(x) for x in (W, U, qG, kG, P, jnp.exp(last))))
    # (n, b, H, C, d_v) -> (b, S, H, d_v)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * C, Hv, dv)
    return o[:, :S]


def xla_rule(q, k, v, g, beta, *, chunk):
    """``gated_delta_rule`` by the XLA form: ``q`` and ``k`` repeated to the
    value heads, the heads in groups."""
    b, S, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    C = min(chunk, S)
    hg = heads_a_group(b * S, Hv)
    with named_scope("gdn-scan"):
        rep = Hv // Hk
        if rep > 1:
            q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        if hg == Hv:
            return _chunked(q, k, v, g, beta, C)

        def groups(x):
            """(b, S, H, ...) -> (H / hg, b, S, hg, ...)"""
            x = x.reshape(x.shape[:2] + (Hv // hg, hg) + x.shape[3:])
            return jnp.moveaxis(x, 2, 0)

        o = lax.map(
            jax.checkpoint(lambda xs: _chunked(*xs, C)),
            tuple(groups(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 2).reshape(b, S, Hv, dv)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--key-heads", type=int, default=16)
    ap.add_argument("--value-heads", type=int, default=32)
    ap.add_argument("--d-head", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--no-xla", action="store_true",
                    help="the kernels alone")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    b, S, Hk, Hv, d, chunk = (args.batch, args.seq, args.key_heads,
                              args.value_heads, args.d_head, args.chunk)
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

    def unit(x):
        return x / np.sqrt(np.sum(np.square(x), axis=-1, keepdims=True)
                           + 1e-6)

    operands = (
        jnp.asarray(unit(rng.randn(b, S, Hk, d)) / np.sqrt(d), bf16),
        jnp.asarray(unit(rng.randn(b, S, Hk, d)), bf16),
        jnp.asarray(rng.randn(b, S, Hv, d), bf16),
        jnp.asarray(-np.exp(2 * rng.randn(b, S, Hv) - 2), f32),
        jnp.asarray(1 / (1 + np.exp(-3 * rng.randn(b, S, Hv))), f32))
    do = jnp.asarray(rng.randn(b, S, Hv, d), bf16)

    def vjp_of(rule):
        return lambda *a: jax.vjp(
            functools.partial(rule, chunk=chunk), *a[:-1])[1](a[-1])

    forms = {"kernel": gated_delta.gated_delta_rule}
    if not args.no_xla:
        forms["xla"] = xla_rule
    programs = {}
    for form, rule in forms.items():
        programs[f"{form}.forward"] = compiled(
            functools.partial(rule, chunk=chunk), operands, 1)
        programs[f"{form}.backward"] = compiled(
            vjp_of(rule), operands + (do,), 5)
    tokens, heads, vmem = gated_delta.gdn_tiles(
        S, chunk, Hk, Hv, d, d, bf16)
    rows = []
    for name, timed in device_ms(programs, args.calls).items():
        c = programs[name][0]
        row = {"program": name, "chunk": min(chunk, S),
               "chunks": -(-S // min(chunk, S)),
               "temp_mb": round(
                   c.memory_analysis().temp_size_in_bytes / 1e6, 1),
               **timed}
        if name.startswith("kernel"):
            row.update(tokens_a_step=tokens, heads_a_step=heads,
                       vmem_mb=round(vmem / 2**20, 2),
                       grid_steps=b * Hk * (-(-S // tokens)))
        rows.append(row)
        print(json.dumps(row), flush=True)

    def ran(name):
        c, placed = programs[name]
        out = c(*placed)
        out = out if isinstance(out, (tuple, list)) else (out,)
        return [np.asarray(a, np.float32) for a in out]

    gaps = None
    if not args.no_xla:
        gaps = [float(np.abs(got - want).max()
                      / max(np.abs(want).max(), 1e-30))
                for got, want in zip(
                    ran("kernel.forward") + ran("kernel.backward"),
                    ran("xla.forward") + ran("xla.backward"))]
        print(json.dumps({"gap_o_dq_dk_dv_dg_dbeta": gaps}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "rows": rows, "gap_o_dq_dk_dv_dg_dbeta": gaps},
                      f, indent=1)


if __name__ == "__main__":
    main()
