#!/usr/bin/env python
"""Flash-attention kernel micro-benchmark: fwd and fwd+bwd vs the XLA
materialized-logits oracle, honest-sync timed (see utils/profiling.sync).

Run on the real chip (default env) — prints a small table plus speedups.
The numbers recorded in docs/performance.md come from here.
"""

import argparse
import time

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np

from chainermn_tpu.ops.flash_attention import _xla_attention, flash_attention
from chainermn_tpu.utils.profiling import slope_time, sync


def timed(fn, *args, iters=10, warmup=2):
    """Slope-based per-dispatch timing.

    The first dispatch and the readback that ends a timed region are a
    constant per run (docs/performance.md "Measuring"), which a single
    short run does not amortize: run n and 5n iterations, each
    ending in one sync, and take the slope ``(T₂−T₁)/(4n)`` — the
    constant cancels exactly.  Soundness of syncing only the LAST of n
    independent dispatches rests on the device executing enqueued
    programs in FIFO order; :func:`timed_chain` — same measurement with
    every iteration data-dependent on the previous inside one
    ``lax.scan`` — validates that on this backend (forward timings agree
    within noise).  Per-dispatch is the training-representative number
    (one step = one dispatch); the in-scan variant distorts big-memory
    baselines (XLA's materialized-logits backward regresses ~8× under
    scan memory pressure).
    """
    for _ in range(warmup):
        sync(fn(*args))

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        sync(out)
        return time.perf_counter() - t0

    return slope_time(run, iters)


def timed_chain(fn, *args, iters=10, warmup=1):
    """Validation twin of :func:`timed`: iterations chained inside one
    jitted ``lax.scan``, each carry tied to the previous output by a
    rounding-vanishing epsilon term (a real data dependence — an
    ``optimization_barrier`` cannot express this: its outputs depend only
    pairwise on operands, so the body would be dead-code-eliminated).
    One dispatch per measurement; the single readback provably fences the
    whole chain with no FIFO assumption."""

    def chain(n):
        @jax.jit
        def run(first, rest):
            def body(carry, _):
                out = fn(carry, *rest)
                leaf = jax.tree.leaves(out)[0]
                nxt = carry + (leaf * 1e-30).astype(carry.dtype)
                return nxt, ()
            c, _ = jax.lax.scan(body, first, None, length=n)
            return c
        return run

    chains = {n: chain(n) for n in (iters, 5 * iters)}
    rest = tuple(args[1:])
    for f in chains.values():
        for _ in range(warmup):
            sync(f(args[0], rest))

    def run(n):
        t0 = time.perf_counter()
        sync(chains[n](args[0], rest))
        return time.perf_counter() - t0

    return slope_time(run, iters)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="GQA/MQA: K/V head count (divides --heads; "
                         "1 = MQA).  The kernel streams shared KV blocks "
                         "via index maps; the XLA baseline broadcasts")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--d-head", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", action="store_true", default=True)
    ap.add_argument("--no-causal", dest="causal", action="store_false")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--block-q", type=int, default=None)
    ap.add_argument("--block-k", type=int, default=None)
    ap.add_argument(
        "--chain", action="store_true",
        help="time via the in-scan chained variant (FIFO-free validation)",
    )
    args = ap.parse_args()

    B, H, S, D = args.batch, args.heads, args.seq, args.d_head
    Hk = H if args.kv_heads is None else args.kv_heads
    if H % Hk:
        ap.error("--kv-heads must divide --heads")
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D), dtype) / (D**0.25)
    k, v = (
        jnp.asarray(rng.randn(B, S, Hk, D), dtype) / (D**0.25)
        for _ in range(2)
    )

    flash = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, causal=args.causal,
            block_q=args.block_q, block_k=args.block_k,
        )
    )
    # _xla_attention broadcasts the KV heads itself for GQA shapes.
    xla = jax.jit(lambda q, k, v: _xla_attention(q, k, v, 1 / D**0.5, args.causal))

    def make_grad(f):
        return jax.jit(
            jax.grad(
                lambda q, k, v: f(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )
        )

    flash_g = make_grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=args.causal,
            block_q=args.block_q, block_k=args.block_k,
        )
    )
    xla_g = make_grad(lambda q, k, v: _xla_attention(q, k, v, 1 / D**0.5, args.causal))

    rows = []
    for name, fn in [
        ("flash fwd", flash),
        ("xla fwd", xla),
        ("flash fwd+bwd", flash_g),
        ("xla fwd+bwd", xla_g),
    ]:
        t = (timed_chain if args.chain else timed)(fn, q, k, v, iters=args.iters)
        # Causal attention FLOPs: 2 matmuls fwd (QK^T, PV) -> 4*S^2*D per
        # head, halved if causal; bwd adds 5 matmul-equivalents.
        mm = 4 * S * S * D * B * H * (0.5 if args.causal else 1.0)
        flops = mm if "fwd" == name.split()[-1] else mm * (1 + 2.5)
        rows.append((name, t, flops / t / 1e12))
        print(f"{name:16s} {t * 1e3:9.3f} ms   {flops / t / 1e12:7.2f} TFLOP/s")

    d = {n: t for n, t, _ in rows}
    print(f"fwd speedup vs XLA:     {d['xla fwd'] / d['flash fwd']:.2f}x")
    print(f"fwd+bwd speedup vs XLA: {d['xla fwd+bwd'] / d['flash fwd+bwd']:.2f}x")
    bwd_flash = d["flash fwd+bwd"] - d["flash fwd"]
    bwd_xla = d["xla fwd+bwd"] - d["xla fwd"]
    print(f"bwd-only: flash {bwd_flash * 1e3:.3f} ms, xla {bwd_xla * 1e3:.3f} ms, "
          f"speedup {bwd_xla / bwd_flash:.2f}x")


if __name__ == "__main__":
    main()
