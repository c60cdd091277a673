"""``ops.kda.kda_rule`` — the chunked delta rule under a decay a key
channel — against the recurrence it is the chunked form of, one token a
step; with ``g`` at the family's lower bound everywhere (the float32 range
the sub-blocks' reference points are there for); and with ``g`` equal
across a head's channels against ``gated_delta_rule``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chainermn_tpu.ops.gated_delta import gated_delta_rule
from chainermn_tpu.ops.kda import (
    SUB,
    heads_a_group,
    kda_rule,
    unit_lower_inverse,
)


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


def test_heads_a_group_divides_the_heads_within_the_bound():
    assert heads_a_group(16384, 32) == 4        # the cell's mixer
    assert heads_a_group(2 * 8192, 32) == 4
    assert heads_a_group(64, 4) == 4            # a tiny model: all at once
    assert heads_a_group(16384 * 5, 6) == 1     # never less than one


def test_unit_lower_inverse_inverts():
    rng = np.random.RandomState(0)
    a = np.tril(rng.randn(3, 64, 64) * 0.3, -1).astype(np.float32)
    t = np.asarray(unit_lower_inverse(jnp.asarray(a)))
    np.testing.assert_allclose(t @ (np.eye(64) + a), np.broadcast_to(
        np.eye(64), a.shape), atol=2e-4)


def test_shapes_that_do_not_fit_are_refused():
    q, k, v, g, beta = operands(S=32)
    with pytest.raises(ValueError, match="do not fit"):
        kda_rule(q, k, v, g[..., 0], beta)
