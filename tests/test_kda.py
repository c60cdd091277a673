"""``ops.kda.kda_rule`` — the chunked delta rule under a decay a key
channel — against the recurrence it is the chunked form of, one token a
step; with ``g`` at the family's lower bound everywhere (the float32 range
the sub-blocks' reference points are there for); and with ``g`` equal
across a head's channels against ``gated_delta_rule``.  The rule is two
Mosaic kernels, interpreted here; a tile's edge, the tile rule and the
kept states have their own cases."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chainermn_tpu.ops.gated_delta import gated_delta_rule
from chainermn_tpu.ops.kda import SUB, kda_rule


def recurrence(q, k, v, g, beta):
    """The definition, float32 at ``highest``, a ``lax.scan`` step a token."""
    hi = lax.Precision.HIGHEST
    b, S, H, dk = q.shape

    def step(state, now):
        q_t, k_t, v_t, g_t, b_t = now                   # (b, H, ...)
        state = jnp.exp(g_t)[..., None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - read),
            precision=hi)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)

    first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    _, o = lax.scan(step, jnp.zeros((b, H, dk, v.shape[-1]), jnp.float32),
                    tuple(first(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def operands(S=128, H=2, dk=16, dv=8, b=1, seed=0, floor=-5.0):
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.sqrt(np.sum(np.square(x), -1, keepdims=True) + 1e-6)

    q = unit(rng.randn(b, S, H, dk)) / np.sqrt(dk)
    k = unit(rng.randn(b, S, H, dk))
    v = rng.randn(b, S, H, dv)
    g = floor / (1.0 + np.exp(-2.0 * rng.randn(b, S, H, dk)))
    beta = 1.0 / (1.0 + np.exp(-rng.randn(b, S, H)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_matches_the_recurrence(chunk):
    ops = operands()
    got = kda_rule(*ops, chunk=chunk)
    want = recurrence(*ops)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("which", range(5),
                         ids=["dq", "dk", "dv", "dg", "dbeta"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_gradients_match_the_recurrence(chunk, which):
    ops = operands(seed=1)
    do = jnp.asarray(np.random.RandomState(2).randn(
        *ops[2].shape), jnp.float32)

    def grad(rule):
        return jax.grad(lambda *a: jnp.sum(rule(*a) * do),
                        argnums=which)(*ops)

    got = grad(lambda *a: kda_rule(*a, chunk=chunk))
    want = grad(recurrence)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-5 * max(scale, 1.0))


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_lower_bound_everywhere_stays_finite_and_right(chunk):
    """``g = -5`` a token and channel: a column's factor inside its own
    sub-block reaches ``e^{5 (SUB - 1)} = e^75``, which float32 holds; one
    reference a chunk of 64 would need ``e^315``."""
    q, k, v, _, beta = operands(seed=3)
    g = jnp.full(q.shape, -5.0, jnp.float32)
    assert 5.0 * (SUB - 1) < 88.0
    got, grads = jax.value_and_grad(
        lambda *a: jnp.sum(kda_rule(*a, chunk=chunk) ** 2),
        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert np.isfinite(float(got))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)
    np.testing.assert_allclose(
        np.asarray(kda_rule(q, k, v, g, beta, chunk=chunk)),
        np.asarray(recurrence(q, k, v, g, beta)), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("which", ["o", "dq", "dk", "dv", "dg", "dbeta"])
def test_equal_across_channels_is_the_scalar_rule(which):
    """With ``g`` one number a head the vector rule IS
    ``gated_delta_rule``'s (its kernels, interpreted here)."""
    q, k, v, g, beta = operands(S=128, H=2, dk=128, dv=128, seed=4)
    g1 = g[..., 0]
    do = jnp.asarray(np.random.RandomState(5).randn(*v.shape), jnp.float32)

    def vector(q, k, v, g1, beta):
        return kda_rule(q, k, v, jnp.broadcast_to(g1[..., None], q.shape),
                        beta, chunk=64)

    def scalar(q, k, v, g1, beta):
        return gated_delta_rule(q, k, v, g1, beta, chunk=64)

    if which == "o":
        got, want = vector(q, k, v, g1, beta), scalar(q, k, v, g1, beta)
    else:
        i = ["dq", "dk", "dv", "dg", "dbeta"].index(which)
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * do), argnums=i)(
            q, k, v, g1, beta) for f in (vector, scalar))
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4 * scale)


def test_a_ragged_sequence_is_padded_with_tokens_that_write_nothing():
    ops = operands(S=100)
    np.testing.assert_allclose(
        np.asarray(kda_rule(*ops, chunk=64)), np.asarray(recurrence(*ops)),
        rtol=2e-4, atol=2e-5)


@pytest.fixture
def two_tiles(monkeypatch):
    """Tiles of two chunks of 64: 256 tokens are two grid steps a head."""
    from chainermn_tpu.ops import kda

    monkeypatch.setattr(kda, "_KDA_TOKENS", 128)
    jax.clear_caches()              # the calls are jitted by shapes alone
    yield kda
    jax.clear_caches()


@pytest.mark.parametrize("which", ["o", "dq", "dk", "dv", "dg", "dbeta"])
def test_the_state_crosses_tiles_forward_and_its_cotangent_back(
        two_tiles, which):
    """More than one TILE a head: forward the state is carried from tile
    to tile, backward each tile starts from the state the forward kept
    and hands the state's cotangent to the tile before it."""
    ops = operands(S=256, seed=6)
    assert two_tiles.kda_tiles(256, 64, 2, 16, 8, jnp.float32)[:2] == (128, 2)
    do = jnp.asarray(np.random.RandomState(7).randn(
        *ops[2].shape), jnp.float32)
    if which == "o":
        got, want = kda_rule(*ops, chunk=64), recurrence(*ops)
    else:
        i = ["dq", "dk", "dv", "dg", "dbeta"].index(which)
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * do), argnums=i)(
            *ops) for f in (lambda *a: kda_rule(*a, chunk=64), recurrence))
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-5 * scale)


def test_the_kept_states_are_the_states_the_tiles_started_from(two_tiles):
    q, k, v, g, beta = operands(S=256, seed=8)
    o, starts = two_tiles._kda_fwd_call(q, k, v, g, beta, C=64, keep=True,
                                        interpret=True)
    assert starts.shape == (1, 2, 2, 16, 8)
    np.testing.assert_array_equal(np.asarray(starts[:, :, 0]), 0.0)
    hi = lax.Precision.HIGHEST
    state = jnp.zeros((1, 2, 16, 8), jnp.float32)
    for t in range(128):                # the recurrence over the first tile
        state = jnp.exp(g[:, t])[..., None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k[:, t], precision=hi)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k[:, t],
            beta[:, t][..., None] * (v[:, t] - read), precision=hi)
    np.testing.assert_allclose(np.asarray(starts[:, :, 1]),
                               np.asarray(state), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(kda_rule(q, k, v, g, beta, chunk=64)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape,tile", [
    ((16384, 64, 32, 128, 128, jnp.bfloat16), (512, 2)),    # the cell's rows
    ((128, 16, 2, 16, 8, jnp.float32), (128, 2)),
    ((100, 64, 3, 16, 8, jnp.float32), (128, 1)),   # padded; an odd head
    ((8, 64, 1, 8, 8, jnp.float32), (8, 1))])       # one short chunk
def test_tiles_come_from_the_shapes_inside_the_default_vmem(
        monkeypatch, shape, tile):
    from chainermn_tpu.ops import kda

    if shape[0] == 16384:               # as on the chip: whole registers
        monkeypatch.setattr(kda, "default_interpret", lambda: False)
    tokens, heads, vmem = kda.kda_tiles(*shape)
    assert (tokens, heads) == tile
    assert 0 < vmem <= kda.VMEM_SCOPED_DEFAULT


def test_channels_that_do_not_fill_registers_are_refused_on_the_chip(
        monkeypatch):
    from chainermn_tpu.ops import kda

    monkeypatch.setattr(kda, "default_interpret", lambda: False)
    with pytest.raises(ValueError, match="whole registers"):
        kda.kda_tiles(256, 64, 2, 12, 128, jnp.bfloat16)


@pytest.mark.parametrize("chunk", [16, 64])
def test_dg_where_a_heads_channels_decay_at_very_different_rates(chunk):
    """One channel at the lower bound (its history gone in three tokens),
    one that barely decays, the rest in between: ``dg`` is a number a
    channel, and every channel's is the recurrence's."""
    q, k, v, g, beta = operands(seed=9)
    g = g.at[..., 0].set(-5.0).at[..., 1].set(-1e-4)
    do = jnp.asarray(np.random.RandomState(10).randn(*v.shape), jnp.float32)
    got, want = (jax.grad(lambda g: jnp.sum(f(q, k, v, g, beta) * do))(g)
                 for f in (lambda *a: kda_rule(*a, chunk=chunk), recurrence))
    for channel in (0, 1, slice(2, None)):
        scale = max(float(jnp.max(jnp.abs(want[..., channel]))), 1e-3)
        np.testing.assert_allclose(
            np.asarray(got[..., channel]), np.asarray(want[..., channel]),
            rtol=1e-3, atol=2e-4 * scale)


def test_shapes_that_do_not_fit_are_refused():
    q, k, v, g, beta = operands(S=32)
    with pytest.raises(ValueError, match="do not fit"):
        kda_rule(q, k, v, g[..., 0], beta)
