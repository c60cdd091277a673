"""Pallas flash-attention kernel vs the XLA oracle (interpret mode on the
CPU harness; the same kernel compiles for real on TPU)."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _flash_no_window  # noqa: E402

from chainermn_tpu.ops.flash_attention import (  # noqa: E402
    _xla_attention,
    auto_block_size,
    flash_attention,
)

# (the module: ``chainermn_tpu.ops.flash_attention`` names the function)
fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def make_qkv(B=2, S=256, H=2, D=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_oracle(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _xla_attention(q, k, v, 1.0 / 8.0, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_small_blocks():
    q, k, v = make_qkv(S=64)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = _xla_attention(q, k, v, 1.0 / 8.0, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_default_blocks_match_explicit():
    """block_q=block_k=None must be EXACTLY the geometry of
    ``auto_block_size``: bit-identical output and gradients."""
    q, k, v = make_qkv()
    b = auto_block_size(256, 64, jnp.float32, "fwd")
    assert b == 256  # the rule's largest fitting tile: the whole axis
    out_auto = flash_attention(q, k, v, causal=True)
    out_pinned = flash_attention(q, k, v, causal=True, block_q=b, block_k=b)
    np.testing.assert_array_equal(np.asarray(out_auto),
                                  np.asarray(out_pinned))
    bb = auto_block_size(256, 64, jnp.float32, "bwd")

    def grads(**blocks):
        return jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, **blocks).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    for a, p in zip(grads(), grads(block_q=b, block_k=b, block_q_bwd=bb,
                                   block_k_bwd=bb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))


@pytest.mark.parametrize(
    "block_q,block_k", [(32, 32), (64, 128), (128, 64), (256, 256)])
def test_flash_candidate_configs_numerically_match_default(block_q, block_k):
    """A block geometry changes speed, never values."""
    q, k, v = make_qkv(B=1, seed=1)
    ref = flash_attention(q, k, v, causal=True)
    out = flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bwd_blocks_numerics_match():
    """A backward geometry different from the forward's must give the
    same gradients."""
    q, k, v = make_qkv(B=1, S=128, seed=2)

    def loss(q, k, v, **kw):
        return jnp.sum(flash_attention(q, k, v, causal=True, **kw) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1, 2))(
        q, k, v, block_q=64, block_k=64)
    g_bwd = jax.grad(loss, argnums=(0, 1, 2))(
        q, k, v, block_q=64, block_k=64, block_q_bwd=32, block_k_bwd=32)
    for a, b in zip(g_ref, g_bwd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_fallback_on_unaligned_shapes():
    q, k, v = make_qkv(S=100)  # not divisible by any power-of-two block
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _xla_attention(q, k, v, 1.0 / 8.0, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_bf16_inputs():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = _xla_attention(q, k, v, 1.0 / 8.0, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_transformer_attention_fn_plug():
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.ops.flash_attention import make_flash_attention_fn

    vocab, S = 32, 64
    dense = TransformerLM(
        vocab=vocab, d_model=32, n_heads=2, d_ff=64, n_layers=1,
        max_len=S, dtype=jnp.float32,
    )
    flash = TransformerLM(
        vocab=vocab, d_model=32, n_heads=2, d_ff=64, n_layers=1,
        max_len=S, dtype=jnp.float32,
        attention_fn=make_flash_attention_fn(causal=True),
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, S), 0, vocab)
    params = dense.init(jax.random.PRNGKey(1), tokens)
    ref = dense.apply(params, tokens)
    out = flash.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_oracle(causal):
    """The custom_vjp backward kernels must match AD through the XLA
    oracle for dQ, dK, dV."""
    q, k, v = make_qkv()
    D = q.shape[-1]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, 1.0 / D**0.5, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        )


@pytest.mark.slow
def test_flash_trains_through_transformer():
    """End-to-end: a tiny causal LM with flash attention must train (the
    gap that motivated the backward kernels — ulysses/flash paths crashed
    under jax.grad before)."""
    import optax

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.ops import make_flash_attention_fn

    vocab, S = 32, 256
    model = TransformerLM(
        vocab=vocab, d_model=32, n_heads=2, d_ff=64, n_layers=1,
        max_len=S, dtype=jnp.float32,
        attention_fn=make_flash_attention_fn(),
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, S), 0, vocab)
    params = model.init(jax.random.PRNGKey(1), tokens)

    def loss_fn(p):
        logits = model.apply(p, tokens)
        tgt = jnp.roll(tokens, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt
        ).mean()

    l0, g = jax.value_and_grad(loss_fn)(params)
    gnorm = sum(float(jnp.sum(x**2)) for x in jax.tree.leaves(g)) ** 0.5
    assert np.isfinite(float(l0)) and gnorm > 0


def test_auto_block_divides_sequence():
    """Auto block sizes must keep odd-but-aligned lengths (e.g. S=2688) on
    the kernel path instead of silently demoting them to XLA fallback."""
    B, S, H, D = 1, 2688, 2, 64
    q, k, v = make_qkv(B=B, S=S, H=H, D=D)
    out = flash_attention(q, k, v, causal=True)
    ref = _xla_attention(q, k, v, 1.0 / D**0.5, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_flash_block_plan_blocks_always_divide():
    """A block that does not divide the chunk would floor the Pallas grid
    and silently drop tail rows — the plan must never emit one."""
    from chainermn_tpu.ops.flash_attention import flash_block_plan

    for S in (8, 64, 128, 192, 256, 384, 512, 2048):
        for interpret in (True, False):
            ok, b = flash_block_plan(S, 64, jnp.float32, interpret)
            if ok:
                assert S % b == 0, (S, interpret, b)
    # Compiled path: the geometry rule's largest square tile inside the
    # default scoped VMEM, the smaller of the forward's and the
    # backward's (one block serves all three kernels of a chunk).
    ok, b = flash_block_plan(2048, 128, jnp.bfloat16, False)
    assert ok and b == 1024
    ok, b = flash_block_plan(2048, 64, jnp.float32, False)
    assert ok and b == 512      # the fp32 backward's streams are twice as wide
    ok, b = flash_block_plan(8192, 256, jnp.float32, False)
    assert ok and b == 512
    ok, b = flash_block_plan(384, 128, jnp.bfloat16, False)
    assert ok and b == 384


def test_flash_block_plan_interpret_clamps_block():
    """Interpret-mode plans for non-128-divisible S must still emit a
    small block (largest divisor ≤ 512), never the full S — a full-S
    block materializes S×S in the interpreter (ADVICE r1)."""
    from chainermn_tpu.ops.flash_attention import flash_block_plan

    ok, b = flash_block_plan(12000, 64, jnp.float32, True)
    assert ok and b <= 512 and 12000 % b == 0 and b == 500
    ok, b = flash_block_plan(97, 64, jnp.float32, True)   # prime ≤ 512
    assert ok and b == 97


def test_decode_rejects_attention_fn():
    """decode=True + attention_fn would silently mis-attend (the adapters
    impose their own causality and ignore the cache mask) — must raise."""
    import pytest
    from chainermn_tpu.models.transformer import MultiHeadAttention

    mha = MultiHeadAttention(
        d_model=16, n_heads=2, dtype=jnp.float32, decode=True, cache_len=4,
        attention_fn=lambda q, k, v, m: q,
    )
    x = jnp.zeros((1, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="incompatible with attention_fn"):
        mha.init(jax.random.PRNGKey(0), x, x)


# ---------------------------------------------------------------------------
# Segment-id masks (packed sequences) + wide heads
# ---------------------------------------------------------------------------


def _packed_oracle(q, k, v, scale, causal, q_seg, kv_seg):
    """Dense reference: per-(batch) segment-equality mask + causal."""
    import numpy as np

    qf, kf, vf = (np.asarray(t, np.float64) for t in (q, k, v))
    B, Sq, H, D = qf.shape
    Sk = kf.shape[1]
    out = np.zeros_like(qf)
    for b in range(B):
        for h in range(H):
            s = (qf[b, :, h] @ kf[b, :, h].T) * scale
            mask = np.asarray(q_seg)[b][:, None] == np.asarray(kv_seg)[b][None, :]
            if causal:
                mask &= np.tril(np.ones((Sq, Sk), bool))
            s = np.where(mask, s, -1e30)
            m = s.max(-1, keepdims=True)
            p = np.exp(s - m)
            p = np.where(mask, p, 0.0)
            denom = p.sum(-1, keepdims=True)
            w = np.where(denom > 0, p / np.maximum(denom, 1e-30), 0.0)
            out[b, :, h] = w @ vf[b, :, h]
    return out


@pytest.mark.parametrize("causal", [True, False])
def test_flash_segment_mask_matches_oracle(causal):
    """Packed sequences: attention stays within segment boundaries; a
    padding row (segment -1, matching nothing) yields exactly zero."""
    import numpy as np

    from chainermn_tpu.ops.flash_attention import flash_attention

    B, S, H, D = 2, 256, 2, 32
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(B, S, H, D).astype(np.float32) for _ in range(3))
    # Two packed docs + padding tail per row.
    seg = np.zeros((B, S), np.int32)
    seg[:, 100:200] = 1
    seg[:, 200:] = -1          # padding
    kv_seg = seg.copy()
    q_seg = seg.copy()
    kv_seg[kv_seg == -1] = -2  # padding rows match NOTHING (q=-1 vs kv=-2)

    out = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
        block_q=64, block_k=64, interpret=True,
        q_segment_ids=jnp.asarray(q_seg), kv_segment_ids=jnp.asarray(kv_seg),
    )
    want = _packed_oracle(q, k, v, 1.0 / D**0.5, causal, q_seg, kv_seg)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)
    # Padding rows are exactly zero.
    np.testing.assert_array_equal(np.asarray(out)[:, 200:], 0.0)
    # Cross-segment leakage check: recompute with segment 1's K/V zeroed;
    # segment-0 outputs must not move.
    v2 = v.copy()
    v2[:, 100:200] = 1e3
    out2 = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v2), causal=causal,
        block_q=64, block_k=64, interpret=True,
        q_segment_ids=jnp.asarray(q_seg), kv_segment_ids=jnp.asarray(kv_seg),
    )
    np.testing.assert_allclose(
        np.asarray(out)[:, :100], np.asarray(out2)[:, :100], rtol=1e-6
    )


def test_flash_segment_backward_matches_xla_oracle():
    """Gradients through the segmented kernel equal the dense masked
    softmax's — including ZERO grads for padding rows."""
    import numpy as np

    from chainermn_tpu.ops.flash_attention import (
        _xla_attention, flash_attention,
    )

    B, S, H, D = 1, 128, 2, 16
    rng = np.random.RandomState(3)
    q, k, v = (
        jnp.asarray(rng.randn(B, S, H, D), jnp.float32) for _ in range(3)
    )
    q_seg = np.zeros((B, S), np.int32)
    q_seg[:, 64:96] = 1
    q_seg[:, 96:] = -1
    kv_seg = q_seg.copy()
    kv_seg[kv_seg == -1] = -2
    qs, ks = jnp.asarray(q_seg), jnp.asarray(kv_seg)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, interpret=True,
            q_segment_ids=qs, kv_segment_ids=ks,
        )
        return jnp.sum(o * jnp.cos(o))

    def loss_xla(q, k, v):
        o = _xla_attention(
            q, k, v, 1.0 / D**0.5, True, q_segment_ids=qs,
            kv_segment_ids=ks,
        )
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )
    # Padding-row grads are exactly zero through the kernel.
    np.testing.assert_array_equal(np.asarray(gf[0])[:, 96:], 0.0)


@pytest.mark.parametrize("D", [192, 256])
def test_flash_wide_head_matches_oracle(D):
    """head_dim in (128, 256]: kernel path (interpret) matches the dense
    oracle, forward and backward."""
    import numpy as np

    from chainermn_tpu.ops.flash_attention import (
        _xla_attention, flash_attention,
    )

    B, S, H = 1, 128, 2
    rng = np.random.RandomState(7)
    q, k, v = (
        jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.float32)
        for _ in range(3)
    )
    out = flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64, interpret=True
    )
    want = _xla_attention(q, k, v, 1.0 / D**0.5, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4
    )

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    gf = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True
        )), argnums=(0, 1, 2),
    )(q, k, v)
    gx = jax.grad(
        loss(lambda q, k, v: _xla_attention(q, k, v, 1.0 / D**0.5, True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


# ---------------------------------------------------------------------------
# GQA / MQA
# ---------------------------------------------------------------------------


def make_gqa(B=2, S=256, H=4, Hk=2, D=64, seed=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hk, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hk, D), dtype)
    return q, k, v


def _gqa_oracle(q, k, v, scale, causal, q_seg=None, kv_seg=None):
    G = q.shape[2] // k.shape[2]
    return _xla_attention(
        q, jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2),
        scale, causal, q_segment_ids=q_seg, kv_segment_ids=kv_seg,
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("Hk", [1, 2])
def test_flash_gqa_matches_oracle(causal, Hk):
    """VERDICT r4 item 5: kv heads dividing query heads (Hk=1 is MQA) —
    kernel output must match broadcasting the kv heads."""
    q, k, v = make_gqa(Hk=Hk)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _gqa_oracle(q, k, v, 1.0 / 8.0, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("Hk", [1, 2])
def test_flash_gqa_backward_matches_oracle(Hk):
    """dq per query head; dk/dv reduced over the group inside the dkv
    kernel — all three must match AD through the broadcast oracle."""
    q, k, v = make_gqa(S=128, Hk=Hk)

    def f_flash(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64
        ) ** 2).sum()

    def f_ref(q, k, v):
        return (_gqa_oracle(q, k, v, 1.0 / 8.0, True) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


def test_flash_gqa_segmented_matches_oracle():
    """GQA composed with packed-sequence segment masks, fwd + bwd."""
    B, S, H, Hk = 2, 128, 4, 2
    q, k, v = make_gqa(B=B, S=S, H=H, Hk=Hk)
    rng = np.random.RandomState(0)
    seg = np.sort(rng.randint(0, 3, size=(B, S)), axis=1).astype(np.int32)
    seg = jnp.asarray(seg)

    def f_flash(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64,
            q_segment_ids=seg, kv_segment_ids=seg,
        ) ** 2).sum()

    def f_ref(q, k, v):
        return (_gqa_oracle(
            q, k, v, 1.0 / 8.0, True, q_seg=seg, kv_seg=seg
        ) ** 2).sum()

    np.testing.assert_allclose(
        float(f_flash(q, k, v)), float(f_ref(q, k, v)), rtol=1e-5
    )
    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


def test_flash_gqa_rejects_bad_head_counts():
    q, k, v = make_gqa(H=4, Hk=2)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k[:, :, :1], v, causal=True)  # v/k mismatch
    q2, k2, v2 = make_gqa(H=4, Hk=3, S=64)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q2, k2, v2, causal=True)


# ---------------------------------------------------------------------------
# Sliding-window (local) attention
# ---------------------------------------------------------------------------


def _window_oracle(q, k, v, scale, window):
    """Dense oracle: causal AND band mask applied to full logits."""
    S = q.shape[1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = (qpos >= kpos) & (qpos - kpos < window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(
        q.dtype
    )


@pytest.mark.parametrize("window", [1, 17, 64, 300])
def test_flash_window_matches_oracle(window):
    """Sliding-window sizes below, equal to, and spanning multiple blocks
    — including the boundary block whose EARLY rows are fully masked
    while its late rows are live."""
    q, k, v = make_qkv(S=256)
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=64
    )
    ref = _window_oracle(q, k, v, 1.0 / 8.0, window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_window_backward_matches_oracle():
    q, k, v = make_qkv(S=128)
    window = 40

    def f_flash(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, window=window, block_q=32, block_k=32
        ) ** 2).sum()

    def f_ref(q, k, v):
        return (_window_oracle(q, k, v, 1.0 / 8.0, window) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


def test_flash_window_composes_with_gqa_and_segments():
    """window AND GQA AND packed segments in one call, fwd + grads."""
    B, S, H, Hk, window = 2, 128, 4, 2, 48
    q, k, v = make_gqa(B=B, S=S, H=H, Hk=Hk)
    rng = np.random.RandomState(0)
    seg = np.sort(rng.randint(0, 2, size=(B, S)), axis=1).astype(np.int32)
    seg = jnp.asarray(seg)
    G = H // Hk

    def ref(q, k, v):
        # _xla_attention composes band + segments + GQA broadcast; its
        # band path is pinned against the independent _window_oracle in
        # test_flash_window_fallback_and_validation.
        return _xla_attention(
            q, k, v, 1.0 / (q.shape[-1] ** 0.5), True,
            q_segment_ids=seg, kv_segment_ids=seg, window=window,
        )

    def f_flash(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, window=window, block_q=32, block_k=32,
            q_segment_ids=seg, kv_segment_ids=seg,
        ) ** 2).sum()

    def f_ref(q, k, v):
        return (ref(q, k, v) ** 2).sum()

    np.testing.assert_allclose(
        float(f_flash(q, k, v)), float(f_ref(q, k, v)), rtol=1e-5
    )
    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


@pytest.mark.parametrize("H,Hk", [(18, 2), (12, 2)])
@pytest.mark.parametrize("window,block", [(32, 32), (32, 16), (40, 32)])
def test_flash_window_at_gqa_groups_of_nine_and_six(H, Hk, window, block):
    """The ``laguna`` rows' shapes in small: 9 and 6 query head rows to a
    key/value row (neither a power of two) under a window as wide as the
    tile's edge (fill 1/2: a diagonal tile and a far tile a query block),
    twice it, and off it — output and all three gradients against
    ``_xla_attention``'s dense masked softmax."""
    xla = importlib.import_module(
        "chainermn_tpu.ops.flash_attention")._xla_attention
    q, k, v = make_gqa(B=1, S=128, H=H, Hk=Hk, D=16, seed=H)
    scale = 0.25

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, block_q=block, block_k=block,
        scale=scale)
    dense = lambda q, k, v: xla(  # noqa: E731
        q, k, v, scale, True, window=window)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


def test_flash_window_fallback_and_validation():
    # Unaligned shapes route to the XLA fallback with the same band.
    q, k, v = make_qkv(S=100)
    out = flash_attention(q, k, v, causal=True, window=30)
    ref = _window_oracle(q, k, v, 1.0 / 8.0, 30)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=30)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)


# ---------------------------------------------------------------------------
# The geometry rule, the band clamp of the index maps, the tile census.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("segmented", [False, True])
@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("D", [64, 128, 256])
def test_auto_block_size_divides_aligns_and_fits(D, dtype, which, segmented):
    """The static default along an axis: divides S, meets the sublane
    count, and its square tile fits the default scoped VMEM by the
    kernels' own footprint — and is the LARGEST such edge."""
    from chainermn_tpu.ops.flash_attention import (
        VMEM_SCOPED_DEFAULT,
        auto_block_size,
        flash_vmem_bytes,
    )

    itemsize = jnp.dtype(dtype).itemsize
    sublane = 16 if dtype == jnp.bfloat16 else 8

    def fits(b):
        return flash_vmem_bytes(
            b, b, D, itemsize, which, segmented) <= VMEM_SCOPED_DEFAULT

    for S in (128, 256, 384, 512, 1024, 1536, 2048, 2688, 4096, 8192):
        b = auto_block_size(S, D, dtype, which, segmented)
        assert S % b == 0 and b % sublane == 0 and fits(b), (S, b)
        larger = [c for c in range(b + 128, S + 1, 128) if S % c == 0]
        assert not any(fits(c) for c in larger), (S, b, larger)
    # What the benchmark's cells get (D=128, bf16, S=2048), and a head
    # wide enough to halve it.
    assert auto_block_size(2048, 128, jnp.bfloat16, which) == 1024
    assert auto_block_size(2048, 256, jnp.float32, which) == 512
    assert auto_block_size(2048, 128, jnp.bfloat16, which, True) == 512
    # A length no multiple of 128 divides keeps the old answer.
    assert auto_block_size(100, D, dtype, which, segmented) == 100
    assert auto_block_size(1000, D, dtype, which, segmented) == 128
    # Under a sliding window: no wider than the band.
    assert auto_block_size(2048, D, dtype, which, segmented, window=300) == 256
    assert auto_block_size(2048, D, dtype, which, segmented, window=64) == 128


@pytest.mark.parametrize("S,window,fwd,bwd", [
    (16384, 1024, 1024, 512),    # mellum2-train-1chip's sliding rows
    (8192, 4096, 1024, 1024),    # half the window is past what VMEM takes
    (16384, 2048, 1024, 1024),
    (16384, 768, 512, 512),      # never under 512 for the fill's sake
    (16384, 512, 512, 512),
    (8192, 512, 512, 512),       # laguna-train-1chip's sliding rows
    (4096, 512, 512, 512),
    (2048, 300, 256, 256),       # nor wider than the band
    (2048, 64, 128, 128),
])
def test_auto_block_size_under_a_window(S, window, fwd, bwd):
    """The window rule at D=128 in bfloat16 (PERF.md §6, PR 40): the
    forward's edge no wider than the band, the backward's no wider than
    half of it while that leaves 512."""
    got = [auto_block_size(S, 128, jnp.bfloat16, which, window=window)
           for which in ("fwd", "bwd")]
    assert got == [fwd, bwd]


def test_flash_vmem_bytes_counts_tiles_and_columns():
    """The footprint grows with either edge, with D, with the dtype and
    with segment columns; the backward's streams outweigh the forward's;
    a footprint past the default raises the kernel's scoped limit to it,
    one inside it asks for nothing."""
    from chainermn_tpu.ops.flash_attention import (
        VMEM_LIMIT_MAX,
        VMEM_SCOPED_DEFAULT,
        _compiler_params,
        flash_vmem_bytes,
    )

    base = flash_vmem_bytes(512, 512, 128, 2, "fwd")
    assert flash_vmem_bytes(1024, 512, 128, 2, "fwd") > base
    assert flash_vmem_bytes(512, 1024, 128, 2, "fwd") > base
    assert flash_vmem_bytes(512, 512, 256, 2, "fwd") > base
    assert flash_vmem_bytes(512, 512, 128, 4, "fwd") > base
    assert flash_vmem_bytes(512, 512, 128, 2, "fwd", True) > base
    assert flash_vmem_bytes(512, 512, 128, 2, "bwd") > base
    # D <= 128 pads to the same 128 lanes.
    assert flash_vmem_bytes(512, 512, 64, 2, "fwd") == base
    # The (block_q, block_k) fp32 intermediates are in it: the blocks
    # grow with the edge, the tiles with its square.
    tile = 512 * 512 * 4
    for which, segmented, n in [("fwd", False, 2), ("bwd", False, 2),
                                ("fwd", True, 3), ("bwd", True, 3)]:
        small = flash_vmem_bytes(512, 512, 128, 2, which, segmented)
        assert flash_vmem_bytes(1024, 1024, 128, 2, which, segmented) == \
            2 * small + 2 * n * tile
    assert _compiler_params(VMEM_SCOPED_DEFAULT) is None
    big = flash_vmem_bytes(2048, 2048, 128, 2, "fwd")
    assert _compiler_params(big).vmem_limit_bytes == big + big // 4
    assert _compiler_params(10 * VMEM_LIMIT_MAX).vmem_limit_bytes == \
        VMEM_LIMIT_MAX


_GEOMETRIES = [
    # (Sq, Sk, block_q, block_k, causal, window)
    (256, 256, 32, 32, True, None),
    (256, 256, 32, 64, True, None),
    (256, 256, 128, 32, True, None),
    (256, 256, 64, 64, False, None),
    (256, 256, 32, 64, True, 1),
    (256, 256, 64, 32, True, 40),
    (256, 256, 32, 32, True, 300),
    (128, 256, 32, 64, True, None),     # more keys than queries
    (256, 128, 64, 32, True, 17),       # more queries than keys
    (2048, 2048, 1024, 1024, True, None),
    # the band grid's own cases: a window off the block's edge, smaller
    # than a block, wider than S, at a block's edge, Sq != Sk both ways
    (256, 256, 32, 32, True, 33),
    (256, 256, 64, 64, True, 7),
    (256, 256, 64, 128, True, 1000),
    (256, 256, 64, 64, True, 64),
    (256, 256, 128, 32, True, 96),
    (128, 256, 32, 64, True, 40),
    (256, 128, 32, 32, True, 48),
    (16384, 16384, 1024, 1024, True, 1024),   # mellum2-train-1chip's row
    (16384, 16384, 512, 512, True, 1024),
    (8192, 8192, 1024, 1024, True, 4096),     # a Mistral-style row
]


def _grid_walk(Sq, Sk, bq, bk, causal, window):
    """``(kv_walk, q_walk)``: the grid of one head row as the wrappers
    build it (``_streamed_axis``), each step as ``(resident block,
    streamed block the index map names, whether the kernel runs the
    tile)`` in the kernels' own step order — K/V streamed past a q block
    (forward, dq), the query side streamed past a k block (dk/dv)."""
    from chainermn_tpu.ops.flash_attention import (
        _band_live,
        _live_ranges,
        _streamed_axis,
        _streamed_block,
    )

    n_q, n_k = Sq // bq, Sk // bk
    kv_range, q_range = _live_ranges(Sq, Sk, bq, bk, causal, window)

    def walk(live_range, n_outer, n_inner, live):
        index_map, steps = _streamed_axis(
            live_range, n_outer, n_inner, causal, window)
        out = []
        for outer in range(n_outer):
            for step in range(steps):
                block, in_band = _streamed_block(
                    live_range, outer, step, window)
                named = int(index_map(outer, step))
                runs = live(outer, int(block)) and (
                    in_band is None or bool(in_band))
                if runs:        # the kernel works on the block it was given
                    assert named == int(block), (outer, step, named)
                out.append((outer, named, runs))
        return out, steps

    def live_qk(i, j):
        return bool(_band_live(causal, window, i * bq, bq, j * bk, bk))

    kv_walk, kv_steps = walk(kv_range, n_q, n_k, live_qk)
    q_walk, q_steps = walk(q_range, n_k, n_q, lambda j, i: live_qk(i, j))
    return (kv_walk, kv_steps), (q_walk, q_steps)


@pytest.mark.parametrize("Sq,Sk,bq,bk,causal,window", _GEOMETRIES)
def test_band_clamp_stays_in_range_and_matches_band_live(
        Sq, Sk, bq, bk, causal, window):
    """Without a window: the clamped index maps never name a block
    outside [0, n); a tile is live by `_band_live` exactly when the clamp
    leaves its index alone, in the K/V maps (forward, dq) and the
    query-side map (dk/dv); a dead tile repeats a live tile's index.
    With one, a walk of the band grid: no index out of range, every live
    tile run exactly once and none twice, a step that does not run
    repeats the block of the step before it (it copies nothing), and the
    inner axis is no longer than the widest band."""
    from chainermn_tpu.ops.flash_attention import (
        _band_live,
        _banded,
        _kv_live_range,
        _q_live_range,
    )

    n_q, n_k = Sq // bq, Sk // bk
    live_tiles = {(i, j) for i in range(n_q) for j in range(n_k)
                  if _band_live(causal, window, i * bq, bq, j * bk, bk)}
    if window is not None:
        (kv_walk, kv_steps), (q_walk, q_steps) = _grid_walk(
            Sq, Sk, bq, bk, causal, window)
        for walk, n_streamed, as_tile in (
                (kv_walk, n_k, lambda outer, inner: (outer, inner)),
                (q_walk, n_q, lambda outer, inner: (inner, outer))):
            assert all(0 <= named < n_streamed for _, named, _ in walk)
            ran = [as_tile(outer, named) for outer, named, runs in walk
                   if runs]
            assert len(ran) == len(set(ran)), "a tile summed twice"
            assert set(ran) == live_tiles
            for before, (outer, named, runs) in zip(walk, walk[1:]):
                if not runs and before[0] == outer:
                    assert named == before[1]
        rows = [sum(1 for t in live_tiles if t[0] == i) for i in range(n_q)]
        cols = [sum(1 for t in live_tiles if t[1] == j) for j in range(n_k)]
        assert kv_steps == max(max(rows), 1) <= n_k
        assert q_steps == max(max(cols), 1) <= n_q
        return
    kv_j = _banded(
        lambda i: _kv_live_range(i, bq, bk, n_k, causal, None), causal)
    q_i = _banded(
        lambda j: _q_live_range(j, bq, bk, n_q, causal, None), causal)
    for i in range(n_q):
        for j in range(n_k):
            live = (i, j) in live_tiles
            jc, ic = int(kv_j(i, j)), int(q_i(j, i))
            assert 0 <= jc < n_k and 0 <= ic < n_q, (i, j, jc, ic)
            if live:
                assert (jc, ic) == (j, i), (i, j, jc, ic)
            else:
                assert jc != j or ic != i or (n_q == 1 and n_k == 1)
            # Whatever the clamp names instead is live itself, unless the
            # whole row (column) is dead.
            row_live = [jj for jj in range(n_k) if (i, jj) in live_tiles]
            if row_live:
                assert jc in row_live, (i, j, jc, row_live)
            col_live = [ii for ii in range(n_q) if (ii, j) in live_tiles]
            if col_live:
                assert ic in col_live, (i, j, ic, col_live)


@pytest.mark.parametrize("Sq,Sk,bq,bk,causal,window", _GEOMETRIES)
def test_tile_census_counts_the_grid(Sq, Sk, bq, bk, causal, window):
    """live / visited / copied a head row, against a walk of the grid in
    the kernels' own step order: the whole rectangle without a window,
    the band's steps with one."""
    from chainermn_tpu.ops.flash_attention import _band_live, tile_census

    n_q, n_k = Sq // bq, Sk // bk
    live = sum(
        bool(_band_live(causal, window, i * bq, bq, j * bk, bk))
        for i in range(n_q) for j in range(n_k))
    (kv_walk, kv_steps), (q_walk, q_steps) = _grid_walk(
        Sq, Sk, bq, bk, causal, window)
    if window is None:
        assert (kv_steps, q_steps) == (n_k, n_q)

    def fetches(walk):
        named = [block for _, block, _ in walk]
        return 1 + sum(a != b for a, b in zip(named, named[1:]))

    got = tile_census(Sq, Sk, bq, bk, causal, window)
    # (``cut`` and ``halved``, the backward's alone, are held to the
    # kernels' own choice by
    # test_the_backward_halves_the_diagonal_and_the_census_counts_it)
    base = {"block_q": bq, "block_k": bk, "live": live,
            "cut": got["fwd"]["cut"]}
    assert got["fwd"] == dict(got["dq"], halved=0) == dict(
        base, visited=n_q * kv_steps, copied=fetches(kv_walk), halved=0)
    assert got["dkv"] == dict(
        base, visited=n_k * q_steps, copied=fetches(q_walk),
        halved=got["dq"]["halved"])
    if window is not None and window + max(bq, bk) <= min(Sq, Sk) // 4:
        # a band far narrower than the sequence: no more steps than live
        # tiles and one a resident block
        assert got["fwd"]["visited"] <= live + n_q
        assert got["dkv"]["visited"] <= live + n_k


def test_tile_census_at_the_windowed_cells_shape():
    """``mellum2-train-1chip``'s sliding row, S = 16,384 under a window
    of 1024 at 1024 x 1024: 31 live tiles a head row in a grid of 16 x 2
    steps (256 before the band grid, 225 of them entered and skipped),
    one step more than live (q block 0 reaches one tile); the same row
    without the window is the rectangle as it was."""
    from chainermn_tpu.ops.flash_attention import tile_census

    band = tile_census(16384, 16384, 1024, 1024, True, 1024)
    for kernel in ("fwd", "dq", "dkv"):
        t = band[kernel]
        # (a K block serves the q block at its own index and the next:
        # each of the 16 is fetched once)
        assert (t["live"], t["visited"], t["copied"]) == (31, 32, 16)
    full = tile_census(16384, 16384, 1024, 1024, True, None)["fwd"]
    assert (full["live"], full["visited"], full["copied"]) == (136, 256, 135)
    half = tile_census(16384, 16384, 512, 512, True, 1024)["fwd"]
    assert (half["live"], half["visited"]) == (93, 96)


def test_tile_census_at_the_narrow_windowed_cells_shape():
    """``laguna-train-1chip``'s sliding row, S = 8,192 under a window of
    512 at the rule's 512 x 512, forward and backward: 31 live tiles a
    head row (a diagonal tile and a far tile a query block, every one
    cut by an edge of the band) in a grid of 16 x 2 steps: the band's
    pairs fill half of the tiles' area."""
    from chainermn_tpu.ops.flash_attention import tile_census

    band = tile_census(8192, 8192, 512, 512, True, 512)
    for kernel in ("fwd", "dq", "dkv"):
        t = band[kernel]
        assert (t["live"], t["visited"], t["cut"]) == (31, 32, 31)
    pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert 0.50 < pairs / (31 * 512 * 512) < 0.51
    full = tile_census(8192, 8192, 1024, 1024, True, None)["fwd"]
    assert (full["live"], full["visited"]) == (36, 64)


def test_tile_census_at_the_benchmark_shape():
    """S=2048 a head row: the old 128 x 128 default visited 256 tiles to
    run 136 (and copied K/V for all 256 before the clamp); the rule's
    1024 x 1024 visits 4, runs 3, copies 2 (the first K/V block serves
    the first three steps)."""
    from chainermn_tpu.ops.flash_attention import tile_census

    old = tile_census(2048, 2048, 128, 128, True, None)["fwd"]
    assert (old["live"], old["visited"], old["copied"]) == (136, 256, 135)
    new = tile_census(2048, 2048, 1024, 1024, True, None)
    for kernel in ("fwd", "dq", "dkv"):
        t = new[kernel]
        assert (t["live"], t["visited"], t["copied"]) == (3, 4, 2)


_BAND_CASES = {
    # name: (Sq, Sk, block_q, block_k, window, Hk of H = 4, segmented)
    "off-the-edge": (128, 128, 32, 32, 40, 4, False),
    "smaller-than-a-block": (128, 128, 64, 32, 17, 4, False),
    "one": (128, 128, 32, 64, 1, 4, False),
    "wider-than-S": (128, 128, 32, 32, 300, 4, False),
    "at-the-edge": (128, 128, 32, 32, 32, 4, False),
    "wide-q-blocks": (256, 256, 128, 32, 96, 4, False),
    "wide-k-blocks": (256, 256, 32, 128, 96, 4, False),
    "more-keys": (128, 256, 32, 64, 40, 4, False),
    "more-queries": (256, 128, 32, 32, 48, 4, False),
    "gqa": (128, 128, 32, 32, 40, 2, False),
    "mqa-rectangular": (128, 128, 16, 64, 24, 1, False),
    "segments": (128, 128, 32, 32, 40, 4, True),
    "gqa-segments-more-keys": (128, 256, 32, 64, 70, 2, True),
}


@pytest.mark.parametrize("case", sorted(_BAND_CASES))
def test_flash_band_grid_matches_oracle(case):
    """Output and all three gradients of a windowed call — its grid the
    band's width in tiles, every kernel — against the dense oracle."""
    Sq, Sk, bq, bk, window, Hk, segmented = _BAND_CASES[case]
    B, H, D = 2, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, Hk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, Hk, D), jnp.float32)
    segs = {}
    if segmented:
        rng = np.random.RandomState(3)
        ids = np.sort(rng.randint(0, 3, size=(B, max(Sq, Sk))), axis=1)
        segs = {"q_segment_ids": jnp.asarray(ids[:, :Sq], jnp.int32),
                "kv_segment_ids": jnp.asarray(ids[:, :Sk], jnp.int32)}

    # A query past the last key's reach (more queries than keys) attends
    # nothing: the kernels write zeros there, the oracle's softmax of an
    # all-masked row is uniform garbage — compared on the other rows.
    reached = (jnp.arange(Sq) - (window - 1) < Sk)[None, :, None, None]

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk, **segs)

    def f_ref(q, k, v):
        return jnp.where(reached, _xla_attention(
            q, k, v, 1.0 / D**0.5, True, window=window, **segs), 0.0)

    np.testing.assert_allclose(
        np.asarray(f_flash(q, k, v)), np.asarray(f_ref(q, k, v)),
        rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (f_flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (f_ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("case", ["off-the-edge", "more-keys", "gqa",
                                  "gqa-segments-more-keys"])
def test_flash_band_grid_folds_the_lse_cotangent(case):
    """``(o, lse)`` of the forward kernel under a window, and the
    backward pair with a cotangent on BOTH (``dlse``, the path of
    ``flash_attention_with_lse[_seg]``), against autodiff of the dense
    masked softmax."""
    from chainermn_tpu.ops.flash_attention import (
        _flash_bh_bwd,
        _flash_bh_fwd,
    )

    Sq, Sk, bq, bk, window, Hk, segmented = _BAND_CASES[case]
    H, D = 4, 32
    G = H // Hk
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    q = jax.random.normal(ks[0], (H, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (Hk, Sk, D), jnp.float32)
    v = jax.random.normal(ks[2], (Hk, Sk, D), jnp.float32)
    do = jax.random.normal(ks[3], (H, Sq, D), jnp.float32)
    dlse = jax.random.normal(ks[4], (H, Sq), jnp.float32)
    geometry = dict(scale=1.0 / D**0.5, causal=True, block_q=bq, block_k=bk,
                    interpret=True, window=window)
    mask = (jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]) & (
        jnp.arange(Sq)[:, None] - jnp.arange(Sk)[None, :] < window)
    segs = {}
    if segmented:
        ids = np.sort(np.random.RandomState(5).randint(
            0, 2, size=max(Sq, Sk))).astype(np.int32)
        # every row keeps its own position's key: no fully masked row,
        # whose lse the oracle and the kernel word differently
        mask = mask & (ids[:Sq, None] == ids[None, :Sk])
        segs = {"q_seg": jnp.broadcast_to(ids[None, :Sq, None], (H, Sq, 1)),
                "kv_seg": jnp.broadcast_to(ids[None, :Sk, None],
                                           (Hk, Sk, 1))}
    rows = np.asarray(mask.any(axis=1))     # (more queries than keys: none)

    def dense(q, k, v):
        s = jnp.einsum("hqd,hkd->hqk", q, jnp.repeat(k, G, axis=0),
                       precision="highest") * geometry["scale"]
        s = jnp.where(mask[None], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        o = jnp.einsum("hqk,hkd->hqd", jnp.exp(s - lse[..., None]),
                       jnp.repeat(v, G, axis=0), precision="highest")
        return o, lse

    o, lse = _flash_bh_fwd(q, k, v, **geometry, **segs)
    (o_ref, lse_ref), vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(np.asarray(o)[:, rows],
                               np.asarray(o_ref)[:, rows],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse)[:, rows, 0],
                               np.asarray(lse_ref)[:, rows],
                               rtol=2e-5, atol=2e-5)
    got = _flash_bh_bwd(q, k, v, o, lse, do, dlse=dlse, **geometry, **segs)
    for a, b in zip(got, vjp((do, dlse))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


@pytest.fixture(scope="module")
def no_window_golden():
    import json

    path = os.path.join(os.path.dirname(__file__), "golden",
                        "flash_no_window.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(_flash_no_window.CASES))
def test_flash_without_a_window_is_the_parents_program(
        no_window_golden, case, which):
    """A call WITHOUT a window keeps the grid, the index maps and the
    kernels it had before the band grid (PR 40): the grids of its
    ``pallas_call``s are the whole rectangle, and its JAXPR and its text
    lowered for a TPU hash to what the parent commit's did
    (``tests/_flash_no_window.py`` says how the golden file is made).
    The backward is one ``pallas_call`` on the forward's grid since
    PR 48 (rows this short fit the one-pass footprint).  PR 50 moved
    none of the five (no tile here has an edge that halves) and added
    ``blockdiff``: the program its parent traced for a call under the
    block-diffusion mask."""
    BH, BHk, Sq, Sk, _, bq, bk, *_ = _flash_no_window.CASES[case]
    got = _flash_no_window.record(case)[which]
    n_q, n_k = Sq // bq, Sk // bk
    if case == "blockdiff":     # the walk of its live tiles
        assert got["grids"] == [[BH, fa.tile_census(
            Sq, Sk, bq, bk, True, None, (Sq // 2, 4))["fwd"]["live"]]]
    else:
        assert got["grids"] == [[BH, n_q, n_k]]
    assert got == no_window_golden[case][which], (
        f"{case}/{which}: the program of a call without a window moved. "
        f"If ops/flash_attention.py was meant to change it, remake the "
        f"file ON THE CHANGED TREE with `PYTHONPATH=. JAX_PLATFORMS=cpu "
        f"python tests/_flash_no_window.py tests/golden/"
        f"flash_no_window.json` and show in the PR that the cases the "
        f"change does not reach kept their digests (`git diff` of the "
        f"file); a change that was NOT meant to reach this path has a "
        f"fault.")


def _mode_kwargs(mode, B, S, H):
    """(Hk, flash kwargs, oracle kwargs) of one masking mode."""
    if mode == "causal":
        return H, {}, {}
    if mode == "window":
        return H, {"window": 40}, {"window": 40}
    rng = np.random.RandomState(1)
    seg = jnp.asarray(
        np.sort(rng.randint(0, 3, size=(B, S)), axis=1).astype(np.int32))
    segs = {"q_segment_ids": seg, "kv_segment_ids": seg}
    if mode == "segments":
        return H, segs, segs
    if mode == "gqa":
        return H // 2, {}, {}
    assert mode == "gqa+window+segments"
    return H // 2, dict(segs, window=40), dict(segs, window=40)


@pytest.mark.parametrize(
    "mode", ["causal", "window", "segments", "gqa", "gqa+window+segments"])
@pytest.mark.parametrize(
    "blocks", [(32, 32), (32, 64), (64, 32), (16, 128), (128, 16)],
    ids=lambda b: f"{b[0]}x{b[1]}")
def test_flash_rectangular_blocks_match_oracle(blocks, mode):
    """Forward and gradients at pinned rectangular blocks (block_k >
    block_q and <), small enough that several tiles of every kernel lie
    outside the band and take a clamped block index."""
    B, S, H, D = 2, 128, 4, 32
    bq, bk = blocks
    Hk, flash_kw, oracle_kw = _mode_kwargs(mode, B, S, H)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hk, D), jnp.float32)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                               **flash_kw)

    def f_ref(q, k, v):
        return _xla_attention(q, k, v, 1.0 / D**0.5, True, **oracle_kw)

    np.testing.assert_allclose(
        np.asarray(f_flash(q, k, v)), np.asarray(f_ref(q, k, v)),
        rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (f_flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (f_ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_flash_separate_backward_blocks_match_oracle():
    """Forward at one geometry, backward at another (both rectangular)."""
    q, k, v = make_qkv(S=128, D=32)

    def f_flash(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, block_q=64, block_k=32,
            block_q_bwd=32, block_k_bwd=64) ** 2).sum()

    def f_ref(q, k, v):
        return (_xla_attention(q, k, v, 1.0 / 32**0.5, True) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_flash_geometry_record_reaches_the_sinks(tmp_path):
    """A call that reaches the kernels publishes its geometry once, at
    trace time, to the installed sinks — and to none when none is."""
    import json

    from chainermn_tpu.observability import Reporter, step_log
    from chainermn_tpu.observability import reporter as reporter_mod

    q, k, v = make_qkv(S=128, D=32)
    f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=64))
    rep = Reporter()
    path = str(tmp_path / "steps.jsonl")
    with reporter_mod.scope(rep), step_log.recording(path):
        f(q, k, v)
        f(q, k, v)                      # no retrace: no second record
    summary = rep.summary()
    assert summary["counters"]["flash/calls"] == 1
    assert summary["counters"]["flash/bwd_fused_calls"] == 1
    gauges = {n: g["value"] for n, g in summary["gauges"].items()}
    assert gauges["flash/flash-fwd/block_q"] == 32
    assert gauges["flash/flash-fwd/block_k"] == 64
    assert gauges["flash/flash-bwd-dkv/visited"] == 8
    assert gauges["flash/bwd_fused"] == 1
    # (dk and dv of the 128-row KV row in float32, 128 lanes each)
    assert gauges["flash/bwd_resident_bytes"] == 128 * 2 * 128 * 4
    assert "flash/flash-bwd-dq/visited" not in gauges
    assert gauges["flash/flash-fwd/live"] == 6
    rows = [json.loads(line) for line in open(path)]
    rows = [r for r in rows if r["event"] == "flash_geometry"]
    assert len(rows) == 1
    # (of the 6 live tiles the diagonal cuts 4; rectangular: none halved)
    assert rows[0]["flash-fwd"] == {
        "block_q": 32, "block_k": 64, "live": 6, "visited": 8, "copied": 4,
        "cut": 4, "halved": 0}
    assert gauges["flash/flash-fwd/cut"] == 4
    assert gauges["flash/flash-bwd-dkv/halved"] == 0
    # The one-pass backward: its tiles are flash-bwd-dkv's, on dq's grid.
    assert set(rows[0]) >= {"flash-fwd", "flash-bwd-dkv", "bwd_fused",
                            "bwd_resident_bytes"}
    assert "flash-bwd-dq" not in rows[0]
    assert rows[0]["bwd_fused"] is True
    assert rows[0]["flash-bwd-dkv"] == rows[0]["flash-fwd"]
    # and whose it is: the row's shape, under which the census goes a
    # second time (a step's rows may differ in shape)
    assert {f: rows[0][f] for f in (
        "heads", "kv_heads", "group", "window")} == {
            "heads": 2, "kv_heads": 2, "group": 1, "window": 0}
    assert summary["counters"]["flash/shape/h2-kv2-w0/calls"] == 1
    assert gauges["flash/shape/h2-kv2-w0/flash-fwd/live"] == 6
    assert gauges["flash/shape/h2-kv2-w0/heads"] == 2


#: name -> (BH, BHk, Sq, Sk, D, D_v, block_q, block_k, causal, window,
#: segmented, dlse, dtype): the backward's two sides on the same
#: operands.
_BWD_SIDES = {
    "causal": (4, 4, 256, 256, 64, 64, 64, 64, True, None, False, False,
               jnp.bfloat16),
    "full": (2, 2, 256, 256, 128, 128, 128, 64, False, None, False, False,
             jnp.bfloat16),
    "gqa": (4, 2, 256, 256, 64, 64, 64, 128, True, None, False, False,
            jnp.bfloat16),
    "gqa-fp32": (8, 2, 256, 256, 32, 32, 64, 64, True, None, False, False,
                 jnp.float32),
    "window": (4, 4, 256, 256, 64, 64, 32, 32, True, 40, False, False,
               jnp.bfloat16),
    "segments": (4, 2, 256, 256, 64, 64, 128, 64, True, None, True, False,
                 jnp.bfloat16),
    "window-segments-gqa": (4, 2, 256, 256, 64, 64, 32, 64, True, 40, True,
                            False, jnp.float32),
    "dlse-more-keys": (4, 1, 128, 256, 64, 64, 32, 64, True, None, False,
                       True, jnp.bfloat16),
    "dlse-segments-more-keys": (4, 2, 128, 256, 32, 32, 32, 64, True, None,
                                True, True, jnp.float32),
}


def _bwd_operands(case):
    """``(operands, geometry)`` of one :data:`_BWD_SIDES` case: ``(q, k,
    v, o, lse, do)`` from the forward kernel on seeded inputs, the
    segment ids and ``dlse`` in ``geometry``."""
    (BH, BHk, Sq, Sk, D, Dv, bq, bk, causal, window, segmented, dlse,
     dtype) = _BWD_SIDES[case]
    ks = jax.random.split(jax.random.PRNGKey(48), 5)
    q = jax.random.normal(ks[0], (BH, Sq, D), dtype)
    k = jax.random.normal(ks[1], (BHk, Sk, D), dtype)
    v = jax.random.normal(ks[2], (BHk, Sk, Dv), dtype)
    do = jax.random.normal(ks[3], (BH, Sq, Dv), dtype)
    geometry = dict(scale=1.0 / D**0.5, causal=causal, block_q=bq,
                    block_k=bk, interpret=True, window=window)
    if segmented:
        ids = np.sort(np.random.RandomState(5).randint(
            0, 3, size=max(Sq, Sk))).astype(np.int32)
        geometry.update(
            q_seg=jnp.broadcast_to(ids[None, :Sq, None], (BH, Sq, 1)),
            kv_seg=jnp.broadcast_to(ids[None, :Sk, None], (BHk, Sk, 1)))
    o, lse = fa._flash_bh_fwd(q, k, v, **geometry)
    if dlse:
        geometry["dlse"] = jax.random.normal(ks[4], (BH, Sq), jnp.float32)
    return (q, k, v, o, lse, do), geometry


@pytest.mark.parametrize("case", sorted(_BWD_SIDES))
def test_flash_backward_in_one_pass_is_the_two_kernels_to_the_bit(case):
    """``dq``, ``dk``, ``dv`` of the one-pass backward
    (``_flash_bwd_fused``) against the two kernels (``_flash_bwd_pair``,
    the other side of the footprint rule) on the same operands: every
    float32 sum runs in the same order — ``dq`` over the row's K tiles, a
    ``dk`` / ``dv`` tile over ``(head of the group, q block)`` — so the
    three are EQUAL, bit for bit, in interpret mode."""
    operands, geometry = _bwd_operands(case)
    fused = fa._flash_bwd_fused(*operands, **geometry)
    pair = fa._flash_bwd_pair(*operands, **geometry)
    for name, a, b in zip(("dq", "dk", "dv"), fused, pair):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
        assert np.asarray(a, np.float32).any(), name


def _pallas_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_names(sub))
    return out


def test_flash_backward_past_the_footprint_rule_runs_the_two_kernels(
        monkeypatch):
    """The choice is by shape alone: a KV row whose float32 ``dk`` and
    ``dv`` fit in VMEM beside the tile (every row of the benchmark's
    cells; ``bwd_fused_vmem_bytes``) takes the one pass, a longer one the
    parent's two kernels — here the limit is brought down under the
    row, as ring attention's long blocks and 128k rows lie over the real
    one — with the same gradients and no ``flash/bwd_fused_calls``."""
    from chainermn_tpu.observability import Reporter
    from chainermn_tpu.observability import reporter as reporter_mod

    q, k, v = make_qkv(B=1, S=384, H=4, D=32)
    k, v = k[:, :, :2], v[:, :, :2]
    blocks = dict(block_q=64, block_k=128)

    def traced():
        # (a function of its own each time: make_jaxpr keeps what it
        # traced of one)
        def grads(q, k, v):
            return jax.grad(lambda q, k, v: (flash_attention(
                q, k, v, causal=True, **blocks) ** 2).sum(),
                argnums=(0, 1, 2))(q, k, v)

        rep = Reporter()
        with reporter_mod.scope(rep):
            names = _pallas_names(jax.make_jaxpr(grads)(q, k, v).jaxpr)
            return names, rep.summary()["counters"], grads(q, k, v)

    # The real limit: 384 rows are nothing beside it.
    assert fa.bwd_fused_vmem_bytes(384, 64, 128, 32, 4, False, 32) is not None
    assert fa.bwd_fused_vmem_bytes(131072, 1024, 1024, 128, 2) is None
    names, counters, fused = traced()
    assert sorted(names) == ["flash-bwd-dkv", "flash-fwd"]
    assert counters["flash/bwd_fused_calls"] == counters["flash/calls"] == 1
    # Under the row: the pair's own footprint still fits, the row's not.
    monkeypatch.setattr(
        fa, "VMEM_LIMIT_MAX",
        fa.flash_vmem_bytes(64, 128, 32, 4, "bwd_fused", False, 32,
                            rows=384) - 1)
    names, counters, pair = traced()
    assert sorted(names) == ["flash-bwd-dkv", "flash-bwd-dq", "flash-fwd"]
    assert counters["flash/calls"] == 1
    assert "flash/bwd_fused_calls" not in counters
    for a, b in zip(fused, pair):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flash_vmem_bytes_counts_the_resident_rows():
    """The one-pass backward's footprint is the dq kernel's and the
    resident rows beside it — float32 ``dk`` and ``dv`` of the whole KV
    row, and the two whole-row outputs twice — so it grows with the row
    and not only with the tile, and the tile edge rule (``which="bwd"``)
    does not see it."""
    MiB = 1 << 20
    # The issue's table: 2 MiB in the cgpt cells, 8 in granite (64 lanes
    # padded to 128), nemo and zaya, 16 in qwen3next, mellum and sdar, 24
    # in ling3flash (192 -> 256 lanes, + 128).
    for rows, D, Dv, want in [(2048, 128, 128, 2), (8192, 64, 64, 8),
                              (8192, 128, 128, 8), (8192, 256, 256, 16),
                              (16384, 128, 128, 16), (16384, 192, 128, 24)]:
        assert fa.bwd_resident_bytes(rows, D, Dv) == want * MiB
    short = fa.flash_vmem_bytes(1024, 1024, 128, 2, "bwd_fused", rows=2048)
    long = fa.flash_vmem_bytes(1024, 1024, 128, 2, "bwd_fused", rows=16384)
    per_row = (128 + 128) * (4 + 2 * 2)
    assert long - short == (16384 - 2048) * per_row
    assert fa.auto_block_size(16384, 128, jnp.bfloat16, "bwd") == 1024
    # Every cell's backward is inside the limit, the largest (ling3flash)
    # under 64 MiB; a 128k row is past it.
    largest = fa.bwd_fused_vmem_bytes(16384, 1024, 1024, 192, 2, False, 128)
    assert fa.VMEM_SCOPED_DEFAULT < largest < 64 * MiB < fa.VMEM_LIMIT_MAX
    assert fa._compiler_params(largest).vmem_limit_bytes <= fa.VMEM_LIMIT_MAX
    assert fa.bwd_fused_vmem_bytes(131072, 1024, 1024, 128, 2) is None


# ---------------------------------------------------------------------
# The backward's diagonal tiles by halves, a windowed row's guard (PR 50)

#: ``_GEOMETRIES`` and the halves' own cases: S = 2 x edge, Sq != Sk,
#: block_q != block_k at edges that halve, windows off / under / at /
#: over a halving edge.
_HALVES_GEOMETRIES = _GEOMETRIES + [
    (512, 512, 256, 256, True, None),       # S = 2 x edge; no halves at 256
    (3072, 2048, 1024, 1024, True, None),   # more queries, halved backward
    (2048, 3072, 1024, 1024, True, None),   # more keys
    (4096, 4096, 1024, 1024, True, 2048),   # a window two halving edges wide
    (4096, 4096, 1024, 1024, True, 1500),   # off a halving edge
    (4096, 4096, 1024, 1024, True, 1024),   # at the edge: still halved
    (4096, 4096, 1024, 1024, True, 1000),   # under it: the window cuts the
                                            # diagonal's tile, kept whole
    (2048, 2048, 1024, 1024, False, None),  # nothing positional masks
    (2048, 2048, 1024, 512, True, None),    # block_q != block_k: no halves
    (2048, 2048, 512, 1024, True, None),
    (768, 512, 256, 256, True, None),       # more queries than keys
    (1024, 1024, 256, 256, True, 100),
    (1024, 1024, 256, 256, True, 512),
    (8192, 8192, 512, 512, True, None),     # qwen3next's backward
]


def _eager_when(cond):
    """``pl.when`` on concrete scalars: the body runs now, or not."""
    return lambda body: body() if bool(cond) else None


def _parts_of(monkeypatch, Sq, Sk, bq, bk, causal, window, seg=None):
    """What a backward kernel runs through ``_run_tiles`` — the one way
    into its tile body — at every step of a head row's grid, on concrete
    scalars: ``{(iq, ik): [(rows, cols, mask or None), ...]}`` in dq's
    step order, a step that runs nothing left out.  ``seg``: ``(q ids,
    k ids)`` of a call with segment ids."""
    monkeypatch.setattr(fa.pl, "when", _eager_when)
    kv_range, _ = fa._live_ranges(Sq, Sk, bq, bk, causal, window)
    _, steps = fa._streamed_axis(kv_range, Sq // bq, Sk // bk, causal,
                                 window)
    ran = {}
    for iq in range(Sq // bq):
        for j in range(steps):
            ik, in_band = fa._streamed_block(kv_range, iq, j, window)
            ik = int(ik)
            q_start, k_start = iq * bq, ik * bk
            qs = ks = None
            if seg is not None:
                qs = seg[0][None, q_start:q_start + bq, None]
                ks = seg[1][None, k_start:k_start + bk, None]

            def tile(mask_of, rows=fa._WHOLE, cols=fa._WHOLE):
                mask = mask_of((len(range(bq)[rows]), len(range(bk)[cols])))
                ran.setdefault((iq, ik), []).append(
                    (rows, cols, None if mask is None else np.asarray(mask)))

            before = len(ran.get((iq, ik), ()))
            fa._run_tiles(
                tile, None,
                lambda: fa._band_run(causal, window, q_start, bq, k_start,
                                     bk, in_band),
                lambda shape: fa._block_mask(shape, causal, q_start,
                                             k_start, qs, ks, window),
                fa._halves(None, causal, window, seg is not None, q_start,
                           bq, k_start, bk))
            assert not before or len(ran[iq, ik]) == before, (
                "a tile run at two steps")
    return ran


def _dense_masks(bq, bk, causal, window, seg=None):
    """``tile(iq, ik) -> (triangle, band, segments)``: the dense masks'
    (bq, bk) tile, each all-true where the call has no such mask."""
    true = np.ones((bq, bk), bool)

    def tile(iq, ik):
        q_pos = iq * bq + np.arange(bq, dtype=np.int32)[:, None]
        k_pos = ik * bk + np.arange(bk, dtype=np.int32)[None, :]
        return (q_pos >= k_pos if causal else true,
                q_pos - k_pos < window if window is not None else true,
                seg[0][q_pos] == seg[1][k_pos] if seg is not None else true)

    return tile


@pytest.mark.parametrize("segmented", [False, True], ids=["", "segments"])
@pytest.mark.parametrize("Sq,Sk,bq,bk,causal,window", _HALVES_GEOMETRIES)
def test_the_backward_halves_the_diagonal_and_the_census_counts_it(
        monkeypatch, Sq, Sk, bq, bk, causal, window, segmented):
    """What the backward kernels' scalar tests run at each tile of a head
    row against the dense mask: a tile that runs is live and a live tile
    runs once; the masks its part(s) apply ARE the dense mask's tile; a
    tile run by halves lies on the diagonal, clear of the window, in a
    call without segment ids, its dropped quarter all false and its
    unmasked quarter all true.  ``tile_census``'s ``cut`` is the live
    tiles an edge of the dense mask crosses, ``halved`` what ran by
    halves (none in the forward)."""
    seg = None
    if segmented:
        ids = np.sort(np.random.RandomState(2).randint(
            0, 3, size=max(Sq, Sk))).astype(np.int32)
        seg = (ids[:Sq], ids[:Sk])
    ran = _parts_of(monkeypatch, Sq, Sk, bq, bk, causal, window, seg)
    dense_tile = _dense_masks(bq, bk, causal, window, seg)
    cut = halved = 0
    for iq in range(Sq // bq):
        for ik in range(Sk // bk):
            triangle, band, segments = dense_tile(iq, ik)
            dense = triangle & band & segments
            live = (triangle & band).any()
            assert ((iq, ik) in ran) == live, (iq, ik)
            if not live:
                continue
            parts = ran[iq, ik]
            applied = np.zeros((bq, bk), bool)
            for rows, cols, mask in parts:
                applied[rows, cols] = True if mask is None else mask
            np.testing.assert_array_equal(applied, dense, str((iq, ik)))
            cut += not (triangle & band).all()
            if len(parts) == 1:
                assert parts[0][:2] == (fa._WHOLE, fa._WHOLE)
                continue
            assert not triangle.all() and band.all() and not segmented
            assert bq == bk and iq == ik
            half = bq // 2
            top, low = np.s_[:half], np.s_[half:]
            assert not dense[top, low].any() and dense[low, top].all()
            assert [(r.start, c.start, m is None) for r, c, m in parts] == [
                (0, 0, False), (half, 0, True), (half, half, False)]
            halved += 1
    census = fa.tile_census(Sq, Sk, bq, bk, causal, window,
                            segmented=segmented)
    assert (census["fwd"]["cut"], census["fwd"]["halved"]) == (cut, 0)
    for kernel in ("dq", "dkv"):
        assert (census[kernel]["cut"], census[kernel]["halved"]) == (
            cut, halved)
    halves = (causal and not segmented and bq == bk and bq >= 1024
              and (window is None or window >= bq))
    assert (halved > 0) == halves


def test_cut_and_halved_at_the_cells_shapes():
    """The census at the cells' rows: a causal row of 16,384 at
    1024-edge tiles has 136 live tiles, 16 of them on the diagonal,
    halved in the backward; S = 8,192: 8 of 36; cgpt's S = 2,048: 2 of
    3; qwen3next's 512-edge backward halves none (its halves would be
    256 wide); mellum's windowed rows — forward (1024) a diagonal and a
    far tile a q block, backward (512) a diagonal, a far tile and an
    interior tile between them, none halved at that edge; the same row
    at 1024-edge backward tiles would halve its 16 diagonal tiles."""
    for S, b, window, live, cut, halved in [
            (16384, 1024, None, 136, 16, 16),
            (8192, 1024, None, 36, 8, 8),
            (8192, 512, None, 136, 16, 0),
            (2048, 1024, None, 3, 2, 2),
            (16384, 1024, 1024, 31, 31, 16),
            (16384, 512, 1024, 93, 62, 0)]:
        census = fa.tile_census(S, S, b, b, True, window)
        for kernel, halves in (("fwd", 0), ("dq", halved), ("dkv", halved)):
            t = census[kernel]
            assert (t["live"], t["cut"], t["halved"]) == (live, cut, halves)
    # nothing positional masks: nothing is cut
    t = fa.tile_census(2048, 2048, 1024, 1024, False, None)["dq"]
    assert (t["live"], t["cut"], t["halved"]) == (4, 0, 0)


#: name -> (Sq, Sk, block_q, block_k, window, Hk of H = 4, segmented)
_HALVES_CASES = {
    "halved": (2048, 2048, 1024, 1024, None, 2, False),
    "halved-more-keys": (1024, 2048, 1024, 1024, None, 4, False),
    "halved-window-at-the-edge": (3072, 3072, 1024, 1024, 1024, 1, False),
    "square": (512, 512, 256, 256, None, 4, False),
    "rectangular": (512, 512, 256, 128, None, 2, False),
    "window-at-the-edge": (768, 768, 256, 256, 256, 4, False),
    "window-under-the-edge": (512, 512, 256, 256, 100, 4, False),
    "window-off-the-edge": (768, 768, 256, 256, 300, 2, False),
    "window-two-edges": (1024, 1024, 256, 256, 512, 4, False),
    "window-small-blocks": (256, 256, 32, 64, 70, 4, False),
    "segments": (512, 512, 256, 256, None, 4, True),
    "segments-window": (512, 512, 128, 128, 200, 2, True),
}


def _halves_case(case):
    Sq, Sk, bq, bk, window, Hk, segmented = _HALVES_CASES[case]
    B, H, D = 1, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(50), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, Hk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, Hk, D), jnp.float32)
    kwargs = {} if window is None else {"window": window}
    if segmented:
        ids = np.sort(np.random.RandomState(4).randint(
            0, 3, size=(B, max(Sq, Sk))), axis=1)
        kwargs.update(q_segment_ids=jnp.asarray(ids[:, :Sq], jnp.int32),
                      kv_segment_ids=jnp.asarray(ids[:, :Sk], jnp.int32))
    return (q, k, v), kwargs, dict(block_q=bq, block_k=bk)


def _out_and_grads(f, operands):
    out, vjp = jax.vjp(f, *operands)
    return (out, *vjp(2.0 * out))


@pytest.mark.parametrize("case", sorted(_HALVES_CASES))
def test_flash_by_halves_matches_oracle_and_the_whole_tiles(
        monkeypatch, case):
    """Output, ``dq``, ``dk`` and ``dv`` of calls that halve, of calls
    the rule keeps whole and of windowed calls (the guard by row),
    against the dense oracle at the tolerances that are there; and
    where tiles are halved, against the same kernels with the halves
    bypassed from here: the forward EQUAL bit for bit (it halves none),
    the gradients within the float32 tolerance (a halved tile's sums
    reassociate)."""
    operands, kwargs, blocks = _halves_case(case)
    Sq, Sk, bq, bk, window, _, segmented = _HALVES_CASES[case]
    D = operands[0].shape[-1]

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, **blocks, **kwargs)

    def f_ref(q, k, v):
        return _xla_attention(q, k, v, 1.0 / D**0.5, True, **kwargs)

    got = _out_and_grads(f_flash, operands)
    tols = (2e-5, 5e-4, 5e-4, 5e-4)
    for a, b, tol in zip(got, _out_and_grads(f_ref, operands), tols):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                                   atol=tol)

    halved = fa.tile_census(Sq, Sk, bq, bk, True, window,
                            segmented=segmented)["dq"]["halved"]
    assert (halved > 0) == case.startswith("halved")
    if not halved:
        return
    monkeypatch.setattr(fa, "_by_halves", lambda *a, **kw: False)
    jax.clear_caches()
    try:
        whole = _out_and_grads(f_flash, operands)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(whole[0]))
    for name, a, b, tol in zip(("dq", "dk", "dv"), got[1:], whole[1:],
                               tols[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                                   atol=tol, err_msg=name)


@pytest.mark.parametrize("Sq,Sk,b,window", [
    (768, 512, 256, 256), (768, 512, 256, 100), (256, 128, 32, 48),
    (512, 256, 128, 128), (3072, 1024, 1024, 1024)])
def test_a_row_past_every_key_under_a_window_yields_zeros(Sq, Sk, b, window):
    """More queries than keys under a window: a late row's first visited
    tile is one the window cuts and the row has no key in it — nor
    anywhere, past ``Sk + window - 2`` — so its scores are all
    ``_NEG_INF`` and ``exp(s - m)`` would be 1: the bodies with the
    window's compare, by halves or whole, shift such a row by 0 in place
    of its statistic (``_reached``; with segment ids the entries are
    zeroed), which is what makes the row's output, and ``dq``, exactly
    zero."""
    B, H, D = 1, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, H, D), jnp.float32)
    past = np.arange(Sq) - (window - 1) >= Sk
    assert past.any() and not past.all()

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=b, block_k=b)

    out, dq, dk, dv = _out_and_grads(f, (q, k, v))
    assert not np.asarray(out)[:, past].any()
    assert not np.asarray(dq)[:, past].any()
    ref = _xla_attention(q, k, v, 1.0 / D**0.5, True, window=window)
    np.testing.assert_allclose(np.asarray(out)[:, ~past],
                               np.asarray(ref)[:, ~past], rtol=2e-5,
                               atol=2e-5)
    assert np.isfinite(np.asarray(dk)).all() and np.asarray(dv).any()
