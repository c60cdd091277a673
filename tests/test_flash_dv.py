"""The flash kernels at a value width of their own (``D_v != D``: latent
attention scores over 192 and sums values of 128), against the XLA
oracle, forward and the three gradients; and ``D_v == D`` to the bit what
a call without the new argument gives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.flash_attention import (
    _xla_attention,
    auto_block_size,
    flash_attention,
    flash_vmem_bytes,
)


def _qkv(D, Dv, H, Hk, S=128, B=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, H, D), jnp.float32),
            jax.random.normal(ks[1], (B, S, Hk, D), jnp.float32),
            jax.random.normal(ks[2], (B, S, Hk, Dv), jnp.float32),
            jax.random.normal(ks[3], (B, S, H, Dv), jnp.float32))


WIDTHS = [(192, 128), (64, 32)]
HEADS = [(2, 2), (4, 2)]            # MHA, GQA


@pytest.mark.parametrize("H,Hk", HEADS, ids=["mha", "gqa"])
@pytest.mark.parametrize("D,Dv", WIDTHS, ids=["192-128", "64-32"])
def test_forward_matches_the_oracle(D, Dv, H, Hk):
    q, k, v, _ = _qkv(D, Dv, H, Hk)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    assert out.shape == q.shape[:3] + (Dv,)
    ref = _xla_attention(q, k, v, D ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("which", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("H,Hk", HEADS, ids=["mha", "gqa"])
@pytest.mark.parametrize("D,Dv", WIDTHS, ids=["192-128", "64-32"])
def test_gradients_match_the_oracle(D, Dv, H, Hk, which):
    q, k, v, do = _qkv(D, Dv, H, Hk, seed=1)

    def grad(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * do), argnums=which)(
            q, k, v)

    got = grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=64))
    want = grad(lambda q, k, v: _xla_attention(q, k, v, D ** -0.5, True))
    assert got.shape == (q, k, v)[which].shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=5e-5)


def test_keys_of_another_width_than_the_queries_are_refused():
    q, k, v, _ = _qkv(64, 32, 2, 2)
    with pytest.raises(ValueError, match="one width"):
        flash_attention(q, k[..., :32], v)


@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("D", [64, 128, 256])
def test_equal_widths_size_a_tile_as_before(D, which):
    """``D_v == D`` and ``D_v=None`` are one footprint and one block."""
    for itemsize in (2, 4):
        assert flash_vmem_bytes(512, 512, D, itemsize, which) == (
            flash_vmem_bytes(512, 512, D, itemsize, which, D_v=D))
    assert auto_block_size(2048, D, jnp.bfloat16, which) == auto_block_size(
        2048, D, jnp.bfloat16, which, D_v=D)


def test_narrower_values_hold_less_vmem_than_padded_ones():
    """192 / 128 against the values padded to 192 lanes (256 on the
    chip): the streamed ``v``, ``o``, ``do``, ``dv`` and their float32
    accumulators are what the width of their own saves."""
    for which in ("fwd", "bwd"):
        assert flash_vmem_bytes(1024, 1024, 192, 2, which, D_v=128) < (
            flash_vmem_bytes(1024, 1024, 192, 2, which))


@pytest.mark.parametrize("which", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("H,Hk", HEADS, ids=["mha", "gqa"])
@pytest.mark.parametrize("D,Dv", WIDTHS, ids=["192-128", "64-32"])
def test_the_one_pass_backward_at_its_own_value_width_is_the_two_kernels(
        D, Dv, H, Hk, which):
    """``dq``, ``dk`` (``D`` wide) and ``dv`` (``D_v`` wide) of the
    one-pass backward, its resident rows ``(Sk, D)`` and ``(Sk, D_v)``,
    EQUAL to the two kernels' bit for bit (bfloat16 operands, the cells'
    dtype; a rectangular tile)."""
    from chainermn_tpu.ops.flash_attention import (
        _flash_bh_fwd,
        _flash_bwd_fused,
        _flash_bwd_pair,
        bwd_resident_bytes,
        to_bh,
    )

    q, k, v, do = (to_bh(x.astype(jnp.bfloat16))
                   for x in _qkv(D, Dv, H, Hk, S=256, B=2, seed=2))
    geometry = dict(scale=D ** -0.5, causal=True, block_q=64, block_k=128,
                    interpret=True)
    o, lse = _flash_bh_fwd(q, k, v, **geometry)
    fused = _flash_bwd_fused(q, k, v, o, lse, do, **geometry)[which]
    pair = _flash_bwd_pair(q, k, v, o, lse, do, **geometry)[which]
    assert fused.shape == (q, k, v)[which].shape
    assert np.asarray(pair, np.float32).any()
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(pair))
    # (192 takes 256 lanes, 128 and under 128: 24 MiB at the Ling row)
    assert bwd_resident_bytes(16384, 192, 128) == 24 << 20
