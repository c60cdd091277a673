"""Worker for the on-TPU test tier (run as a subprocess with the DEFAULT
environment, i.e. JAX on the TPU — unlike every other worker, which
forces the CPU).  Runs from a checkout without an install:
``python tests/_on_tpu_worker.py flash``.

Subcommands:
  probe      — print the default backend name and exit
  flash      — compiled (non-interpret) flash attention fwd+bwd vs the XLA
               oracle ON THE CHIP; asserts and prints OK
  trainstep  — 3 data-parallel train steps on whatever backend is active;
               prints per-step losses (the pytest side runs this twice,
               chip vs CPU, and compares)

The reference gated GPU tests with ``@attr.gpu`` markers (SURVEY §4); this
is that tier for TPU — the compiled kernel path is correctness-asserted on
the real chip, not just timed.
"""

import os
import sys

# The package is not installed: import it from the checkout this file is in.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np


def probe():
    print(jax.default_backend())


def _assert_grads_close(g, gref, tol, ctx):
    """Per-component max relative error: grad magnitudes vary over orders
    of magnitude, so compare at the scale of the reference gradient."""
    for a, b, name in zip(g, gref, "qkv"):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a32.shape == b32.shape, (name, ctx, a32.shape, b32.shape)
        denom = max(1e-6, float(np.abs(b32).max()))
        err = float(np.abs(a32 - b32).max()) / denom
        assert err < tol, (name, ctx, err)


def flash():
    from chainermn_tpu.ops.flash_attention import _xla_attention, flash_attention

    assert jax.default_backend() == "tpu", jax.default_backend()
    rng = np.random.RandomState(0)
    for dtype, causal, S, D, tol in [
        (jnp.bfloat16, True, 1024, 64, 2e-2),
        (jnp.bfloat16, False, 1024, 64, 2e-2),
        (jnp.float32, True, 1024, 64, 2e-3),
        # The default geometry where it makes several tiles (1024 x 1024
        # of them: 2 x 2 with one dead, 4 x 4 with six), at the
        # benchmark's head dim: clamped index maps, compiled.
        (jnp.bfloat16, True, 2048, 128, 2e-2),
        (jnp.bfloat16, True, 4096, 128, 2e-2),
    ]:
        B, H = 1, 2
        q, k, v = (
            jnp.asarray(rng.randn(B, S, H, D), dtype) / (D**0.25)
            for _ in range(3)
        )

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, interpret=False)
            return (o.astype(jnp.float32) ** 2).sum()

        def loss_xla(q, k, v):
            o = _xla_attention(q, k, v, 1.0 / D**0.5, causal)
            return (o.astype(jnp.float32) ** 2).sum()

        o = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, interpret=False
            )
        )(q, k, v)
        ref = jax.jit(
            lambda q, k, v: _xla_attention(q, k, v, 1.0 / D**0.5, causal)
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol,
        )

        g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gref = jax.jit(jax.grad(loss_xla, argnums=(0, 1, 2)))(q, k, v)
        _assert_grads_close(g, gref, 10 * tol, (dtype, causal))
        print(f"flash-on-tpu ok: dtype={jnp.dtype(dtype).name} "
              f"causal={causal} S={S} D={D}")

    # Segment-id masks (packed sequences), compiled: fwd + grads match the
    # dense oracle; padding rows are exactly zero in BOTH passes.
    B, S, H, D = 2, 1024, 2, 128
    q, k, v = (
        jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
        for _ in range(3)
    )
    seg = np.zeros((B, S), np.int32)
    seg[:, 400:800] = 1
    seg[:, 800:] = -1
    kv_seg = seg.copy()
    kv_seg[kv_seg == -1] = -2
    qs, ks = jnp.asarray(seg), jnp.asarray(kv_seg)
    o = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, q_segment_ids=qs, kv_segment_ids=ks
    ))(q, k, v)
    ref = _xla_attention(
        q, k, v, 1.0 / D**0.5, True, q_segment_ids=qs, kv_segment_ids=ks
    )
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )
    assert np.all(np.asarray(o)[:, 800:] == 0)
    g = jax.jit(jax.grad(lambda q: jnp.sum(jnp.sin(flash_attention(
        q, k, v, causal=True, q_segment_ids=qs, kv_segment_ids=ks
    ).astype(jnp.float32)))))(q)
    gx = jax.grad(lambda q: jnp.sum(jnp.sin(_xla_attention(
        q, k, v, 1.0 / D**0.5, True, q_segment_ids=qs, kv_segment_ids=ks
    ).astype(jnp.float32))))(q)
    np.testing.assert_allclose(
        np.asarray(g, np.float32), np.asarray(gx, np.float32),
        rtol=2e-2, atol=2e-2,
    )
    assert np.all(np.asarray(g)[:, 800:] == 0)
    print("flash-on-tpu ok: segmented")

    # Wide heads (Mosaic-padded lane tiles), compiled: one non-multiple
    # of 128 and the 256 ceiling.
    for D2 in (160, 256):
        q2, k2, v2 = (
            jnp.asarray(rng.randn(1, 512, 2, D2) * 0.2, jnp.bfloat16)
            for _ in range(3)
        )
        o2 = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
            q2, k2, v2
        )
        w2 = _xla_attention(q2, k2, v2, 1.0 / D2**0.5, True)
        np.testing.assert_allclose(
            np.asarray(o2, np.float32), np.asarray(w2, np.float32),
            rtol=2e-2, atol=2e-2,
        )
        print(f"flash-on-tpu ok: D={D2}")

    # GQA / MQA, COMPILED (the b // G index maps and the widened dkv
    # grid have Mosaic lowerings of their own — interpret-mode coverage
    # alone would not pin them): fwd + all three grads vs the
    # broadcast-kv oracle, for a 2-group and an MQA head layout.
    for Hk in (2, 1):
        B3, S3, H3, D3 = 2, 1024, 4, 128
        q3 = jnp.asarray(rng.randn(B3, S3, H3, D3) * 0.3, jnp.bfloat16)
        k3 = jnp.asarray(rng.randn(B3, S3, Hk, D3) * 0.3, jnp.bfloat16)
        v3 = jnp.asarray(rng.randn(B3, S3, Hk, D3) * 0.3, jnp.bfloat16)
        G = H3 // Hk

        def gqa_ref(q, k, v):
            return _xla_attention(
                q, jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2),
                1.0 / D3**0.5, True,
            )

        o3 = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
            q3, k3, v3
        )
        np.testing.assert_allclose(
            np.asarray(o3, np.float32),
            np.asarray(gqa_ref(q3, k3, v3), np.float32),
            rtol=2e-2, atol=2e-2,
        )
        g3 = jax.jit(jax.grad(
            lambda q, k, v: (flash_attention(
                q, k, v, causal=True
            ).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2),
        ))(q3, k3, v3)
        gr3 = jax.jit(jax.grad(
            lambda q, k, v: (gqa_ref(q, k, v).astype(jnp.float32) ** 2)
            .sum(),
            argnums=(0, 1, 2),
        ))(q3, k3, v3)
        _assert_grads_close(g3, gr3, 0.2, ("gqa", Hk))
        print(f"flash-on-tpu ok: GQA Hk={Hk}")

    # The hybrid cell's attention layer, COMPILED: GQA 32 / 8 at D=64,
    # S=8192 with the softmax scale the model states (1/64, not
    # 1/sqrt(D)), through the adapter the layers call.  The oracle walks
    # the kv heads one group at a time (a group's scores are 1 GB).
    from chainermn_tpu.ops.flash_attention import make_flash_attention_fn

    B4, S4, H4, Hk4, D4, scale4 = 1, 8192, 32, 8, 64, 1.0 / 64
    G4 = H4 // Hk4
    q4 = jnp.asarray(rng.randn(B4, S4, H4, D4), jnp.bfloat16)
    k4 = jnp.asarray(rng.randn(B4, S4, Hk4, D4), jnp.bfloat16)
    v4 = jnp.asarray(rng.randn(B4, S4, Hk4, D4), jnp.bfloat16)
    adapter = make_flash_attention_fn(causal=True, scale=scale4)
    assert adapter.scale == scale4

    def sq(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    o4 = jax.jit(adapter)(q4, k4, v4)
    g4 = jax.jit(jax.grad(sq(adapter), argnums=(0, 1, 2)))(q4, k4, v4)
    group_ref = jax.jit(lambda q, k, v: _xla_attention(
        q, jnp.repeat(k, G4, axis=2), jnp.repeat(v, G4, axis=2), scale4,
        True))
    group_grad = jax.jit(jax.grad(sq(group_ref), argnums=(0, 1, 2)))
    for j in range(Hk4):
        hq, hk = slice(j * G4, (j + 1) * G4), slice(j, j + 1)
        np.testing.assert_allclose(
            np.asarray(o4[:, :, hq], np.float32),
            np.asarray(group_ref(q4[:, :, hq], k4[:, :, hk], v4[:, :, hk]),
                       np.float32), rtol=2e-2, atol=2e-2)
        _assert_grads_close(
            (g4[0][:, :, hq], g4[1][:, :, hk], g4[2][:, :, hk]),
            group_grad(q4[:, :, hq], k4[:, :, hk], v4[:, :, hk]), 0.2,
            ("gqa-32/8-s8192", j))
    print("flash-on-tpu ok: GQA 32/8 D=64 S=8192 scale=1/64")

    # Sliding-window band, COMPILED: the band mask and the two-sided
    # block skips have their own Mosaic lowering; fwd + grads vs the
    # dense banded oracle at a window spanning ~1.5 blocks.
    Bw, Sw, Hw, Dw, W = 1, 1024, 2, 128, 200
    qw, kw, vw = (
        jnp.asarray(rng.randn(Bw, Sw, Hw, Dw) * 0.3, jnp.bfloat16)
        for _ in range(3)
    )

    def banded_ref(q, k, v):
        # _xla_attention's band path is itself pinned against an
        # independent hand-rolled oracle in tests/test_flash_attention.py.
        return _xla_attention(q, k, v, 1.0 / Dw**0.5, True, window=W)

    ow = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W
    ))(qw, kw, vw)
    np.testing.assert_allclose(
        np.asarray(ow, np.float32),
        np.asarray(banded_ref(qw, kw, vw), np.float32),
        rtol=2e-2, atol=2e-2,
    )
    gw = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, causal=True, window=W
        ).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2),
    ))(qw, kw, vw)
    gwr = jax.jit(jax.grad(
        lambda q, k, v: (banded_ref(q, k, v).astype(jnp.float32) ** 2)
        .sum(),
        argnums=(0, 1, 2),
    ))(qw, kw, vw)
    _assert_grads_close(gw, gwr, 0.2, ("window", W))
    print(f"flash-on-tpu ok: window W={W}")
    print("OK")


def trainstep():
    import optax

    import chainermn_tpu
    from chainermn_tpu.communicators import create_communicator

    # TPU's DEFAULT f32 matmul precision uses bf16 MXU passes (~1e-3 off
    # a CPU fp32 run); force true fp32 so chip-vs-CPU trajectories are
    # comparable at tight tolerance.
    jax.config.update("jax_default_matmul_precision", "highest")
    comm = create_communicator("xla_ici")
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(16, 4), jnp.float32) * 0.1
    params = {"w": W}

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    opt = optax.sgd(0.1)
    mopt = chainermn_tpu.create_multi_node_optimizer(opt, comm)
    state = mopt.init(params)
    step = mopt.make_train_step(loss_fn)

    # Fixed global batch so the chip run (whatever the pool's device
    # count) and the 1-device CPU run draw identical data; DP averaging
    # makes the trajectory device-count-invariant as long as 16 divides
    # the device count's shard arithmetic.
    n = 16
    for i in range(3):
        x = jnp.asarray(rng.randn(n, 16), jnp.float32)
        y = jnp.asarray(rng.randn(n, 4), jnp.float32)
        batch = comm.global_batch((x, y))
        params, state, loss = step(params, state, batch)
        print(f"loss {i}: {float(loss):.8f}")


if __name__ == "__main__":
    cmd = sys.argv[1]
    {"probe": probe, "flash": flash, "trainstep": trainstep}[cmd]()
