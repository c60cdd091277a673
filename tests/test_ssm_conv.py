"""The causal convolution's kernels (``ops/ssd.py``) against the plain
formulas and what autodiff makes of them.

``oracle`` below is the ``jax.numpy`` body ``causal_conv_silu`` had when
it had no kernel and no backward of its own (PR 28): four shifted slices
of a padded tensor, multiplied and summed in float32, SiLU, cast back.
The forward kernel has to equal it, and the written backward
``jax.vjp`` of it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chainermn_tpu.models.block_table import SSMSpec  # noqa: E402
from chainermn_tpu.models.transformer import Mamba2Mixer  # noqa: E402
from chainermn_tpu.observability import device_trace  # noqa: E402
from chainermn_tpu.ops import ssd  # noqa: E402
from chainermn_tpu.ops.ssd import causal_conv_silu  # noqa: E402


def oracle(x, kernel, bias):
    K, S = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    acc = bias.astype(jnp.float32)
    for j in range(K):
        acc = acc + (padded[:, j:j + S].astype(jnp.float32)
                     * kernel[j].astype(jnp.float32))
    return jax.nn.silu(acc).astype(x.dtype)


def operands(B, S, C, K, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (B, S, C), dtype),
            jax.random.normal(keys[1], (K, C)) * 0.5,
            jax.random.normal(keys[2], (C,)) * 0.1,
            jax.random.normal(keys[3], (B, S, C), dtype))


def gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


#: (B, S, C, K): the cell's channel count, one that is no multiple of the
#: 128 lanes and one under them; lengths that no sequence tile divides;
#: two and four taps; one row and three.
SHAPES = [
    (1, 48, 4352, 4), (3, 16, 4352, 2),
    (1, 37, 200, 4), (3, 100, 200, 2), (2, 64, 200, 4),
    (1, 12, 6, 4), (3, 37, 100, 2), (2, 5, 6, 4), (1, 2, 6, 4),
    (2, 130, 256, 4), (1, 1030, 128, 4),
]


@pytest.mark.parametrize("B,S,C,K", SHAPES)
def test_written_backward_equals_autodiff_float32(B, S, C, K):
    x, kernel, bias, dy = operands(B, S, C, K, jnp.float32)
    y, pull = jax.vjp(causal_conv_silu, x, kernel, bias)
    y0, pull0 = jax.vjp(oracle, x, kernel, bias)
    assert y.dtype == y0.dtype and gap(y, y0) < 1e-6
    got, want = pull(dy), pull0(dy)
    for g, w, name in zip(got, want, ("dx", "dkernel", "dbias")):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert gap(g, w) < 1e-5, name


@pytest.mark.parametrize("B,S,C,K", SHAPES)
def test_written_backward_equals_autodiff_bfloat16(B, S, C, K):
    """bfloat16 activations: ``dkernel`` and ``dbias`` are float32 sums
    on both sides; ``dx`` is rounded once here, where autodiff rounds
    each tap's product and adds the four in bfloat16 — so the written
    ``dx`` lies within the oracle's own rounding (a few bfloat16 steps of
    the largest term) and is the closer of the two to float32."""
    x, kernel, bias, dy = operands(B, S, C, K, jnp.bfloat16)
    y, pull = jax.vjp(causal_conv_silu, x, kernel, bias)
    y0, pull0 = jax.vjp(oracle, x, kernel, bias)
    # one bfloat16 step where the float32 sums differ in their last bit
    # (on the chip the two forwards agree to the bit: PERF.md §6, PR 29)
    assert y.dtype == y0.dtype and gap(y, y0) < 2.0 ** -8
    assert np.mean(np.asarray(y) != np.asarray(y0)) < 1e-3
    (dx, dk, db), (dx0, dk0, db0) = pull(dy), pull0(dy)
    assert dx.dtype == jnp.bfloat16 and dk.dtype == db.dtype == jnp.float32
    assert gap(dk, dk0) < 1e-5 and gap(db, db0) < 1e-5
    exact = jax.vjp(oracle, x.astype(jnp.float32), kernel, bias)[1](
        dy.astype(jnp.float32))[0]
    assert gap(dx, dx0) < K * 2.0 ** -8
    assert gap(dx, exact) <= gap(dx0, exact) + 1e-6


def test_backward_reaches_no_earlier_cotangent_and_sees_the_zero_padding():
    """The mirror of ``test_causal_conv_sees_no_later_token``: a cotangent
    at token t moves ``dx`` at t-K+1..t and nowhere else, and ``dx`` at
    the last K-1 tokens is made of fewer taps (zeros past the end)."""
    x, kernel, bias, dy = operands(1, 12, 6, 4, jnp.float32)
    pull = jax.vjp(causal_conv_silu, x, kernel, bias)[1]
    dx = pull(dy)[0]
    bumped = pull(dy.at[:, 7].add(1.0))[0]
    np.testing.assert_array_equal(dx[:, 8:], bumped[:, 8:])
    np.testing.assert_array_equal(dx[:, :4], bumped[:, :4])
    assert not np.allclose(dx[:, 4:8], bumped[:, 4:8])
    # the last token's dx is its own dpre through tap K-1, nothing else
    acc = bias + sum(kernel[3 - k] * x[0, 11 - k] for k in range(4))
    sig = jax.nn.sigmoid(acc)
    dpre = dy[0, 11] * sig * (1.0 + acc * (1.0 - sig))
    np.testing.assert_allclose(dx[0, 11], kernel[3] * dpre, rtol=1e-5)


def _mixer(dtype):
    spec = SSMSpec(n_heads=4, d_head=8, d_state=16, n_groups=1, d_conv=4,
                   chunk=8)
    mixer = Mamba2Mixer(d_model=16, ssm=spec, dtype=dtype)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 16), dtype)
    params = mixer.init(jax.random.PRNGKey(4), h)["params"]
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(5), p.shape,
                                              p.dtype), params)

    def loss(p, h):
        run = jax.checkpoint(lambda p, h: mixer.apply({"params": p}, h))
        return jnp.sum(jnp.square(run(p, h).astype(jnp.float32)))

    return loss, params, h


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_mixer_gradient_under_remat_equals_the_parents(monkeypatch, dtype,
                                                       tol):
    """``jax.grad`` through a rematerialised ``Mamba2Mixer``: every leaf
    and the input's gradient equal what the mixer gives with the
    convolution left to autodiff (the parent commit's)."""
    loss, params, h = _mixer(dtype)
    got = jax.grad(loss, argnums=(0, 1))(params, h)
    monkeypatch.setattr(ssd, "causal_conv_silu", oracle)
    want = jax.grad(loss, argnums=(0, 1))(params, h)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert gap(g, w) < tol, jax.tree_util.keystr(path)


def test_backward_ops_sit_under_ssm_conv_inside_the_mixer():
    """``kernel.ssm_conv_ms`` reads scope ``ssm-conv`` and
    ``ssm.mixer_ms`` reads ``mamba-mixer`` with it nested inside: the
    written backward's ops (a custom call's included) carry both, in that
    order, as the forward's do."""
    loss, params, h = _mixer(jnp.float32)
    table = device_trace.scope_table(
        jax.jit(jax.grad(loss)).lower(params, h).compile())
    conv = [path for path in table.values() if "ssm-conv" in path]
    assert all(device_trace.classify(path)[1] == "ssm-conv" for path in conv)
    forward = [path for path in conv if "_conv_silu_bwd_call" not in path]
    backward = [path for path in conv if "_conv_silu_bwd_call" in path]
    assert forward and backward
    assert any("ssm-conv-bwd" in path for path in backward)    # the kernel
    for path in backward + [p for p in forward if "Mamba2Mixer" in p]:
        parts = device_trace.scope_components(path)
        assert parts.index("mamba-mixer") < parts.index("ssm-conv"), path
    assert all("transpose(" in path for path in backward)


def test_scan_kernels_sit_under_ssd_scan_inside_the_mixer():
    """``kernel.ssd_ms`` / ``nemo.ssd_ms`` read scope ``ssd-scan`` and
    the mixers' readers ``mamba-mixer`` with it nested inside: both
    Mosaic calls (``ssd-fwd``, ``ssd-bwd``) and the relabelings around
    them carry both, in that order."""
    loss, params, h = _mixer(jnp.float32)
    table = device_trace.scope_table(
        jax.jit(jax.grad(loss)).lower(params, h).compile())
    scan = [path for path in table.values() if "ssd-scan" in path]
    assert scan and all(
        device_trace.classify(path)[1] == "ssd-scan" for path in scan)
    assert any("ssd-fwd" in path for path in scan)
    assert any("ssd-bwd" in path for path in scan)
    for path in [p for p in scan if "Mamba2Mixer" in p]:
        parts = device_trace.scope_components(path)
        assert parts.index("mamba-mixer") < parts.index("ssd-scan"), path
