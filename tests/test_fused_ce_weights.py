"""``fused_cross_entropy`` with a float32 weight a row inside its chunked
scan, forward and backward, and a normaliser that is not the count of
kept rows — the masked-diffusion loss — against the materialised-logits
oracle times the weights; and the unweighted call's program, held to the
parent's (``tests/_fused_ce_unweighted.py`` says how the golden file is
made)."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _fused_ce_unweighted  # noqa: E402

ce = importlib.import_module("chainermn_tpu.ops.fused_ce")


def problem(seed, rows=96, d=32, vocab=211, dtype=jnp.float32):
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = jax.random.normal(key[0], (2, rows // 2, d), dtype)
    e = 0.3 * jax.random.normal(key[1], (vocab, d))
    labels = jax.random.randint(key[2], (2, rows // 2), -1, vocab)
    t = jax.random.uniform(key[3], (2, rows // 2), minval=1e-3)
    masked = jax.random.uniform(key[0], t.shape) < t
    return h, e, labels, jnp.where(masked, 1.0 / t, 0.0)


def naive_weighted(h, e, labels, weights, normaliser):
    """Materialised logits, the bf16-operand product the fused path
    makes, every row's term times its weight."""
    logits = ce._chunk_logits(h.reshape(-1, h.shape[-1]), e)
    l2, w2 = labels.reshape(-1), weights.reshape(-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(l2, 0)[:, None], axis=-1)[:, 0]
    terms = (jax.scipy.special.logsumexp(logits, axis=-1) - picked) * w2
    return jnp.where(l2 >= 0, terms, 0.0).sum() / normaliser


@pytest.mark.parametrize("which", ["loss", "d_hidden", "d_embedding"])
@pytest.mark.parametrize("chunk,normaliser,dtype", [
    (16, 96.0, jnp.float32), (96, 7.0, jnp.float32), (32, 96.0,
                                                      jnp.bfloat16)])
def test_weighted_loss_and_gradients_match_the_oracle(
        which, chunk, normaliser, dtype):
    h, e, labels, w = problem(1, dtype=dtype)

    def fused(h, e):
        return ce.fused_cross_entropy(h, e, labels, chunk=chunk, weights=w,
                                      normaliser=normaliser)

    got = jax.value_and_grad(fused, (0, 1))(h, e)
    want = jax.value_and_grad(
        lambda h, e: naive_weighted(h, e, labels, w, normaliser),
        (0, 1))(h, e)
    got, want = {"loss": (got[0], want[0]),
                 "d_hidden": (got[1][0], want[1][0]),
                 "d_embedding": (got[1][1], want[1][1])}[which]
    # the fused rule rounds dlogits to bfloat16 once for its two products;
    # the oracle differentiates through float32 logits
    tol = 1e-5 if which == "loss" else (
        3e-2 if dtype == jnp.bfloat16 else 1e-2)
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32),
        atol=tol * scale, rtol=tol)


def test_weights_of_ones_over_kept_rows_are_the_unweighted_path_to_the_bit():
    h, e, labels, _ = problem(2)
    plain = jax.jit(jax.value_and_grad(
        lambda h, e: ce.fused_cross_entropy(h, e, labels, chunk=16),
        (0, 1)))(h, e)
    ones = jax.jit(jax.value_and_grad(
        lambda h, e: ce.fused_cross_entropy(
            h, e, labels, chunk=16, weights=jnp.ones(labels.shape)),
        (0, 1)))(h, e)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(ones)):
        assert jnp.array_equal(a, b)


def test_a_row_of_weight_zero_adds_nothing_and_takes_no_gradient():
    h, e, labels, w = problem(3)
    labels = jnp.maximum(labels, 0)
    off = w.at[0, :24].set(0.0)

    def loss(h, w):
        return ce.fused_cross_entropy(h, e, labels, chunk=16, weights=w,
                                      normaliser=96.0)

    dh = jax.grad(loss)(h, off)
    assert not jnp.any(dh[0, :24]) and jnp.any(dh[0, 24:])
    dropped = jnp.where(off > 0, labels, -1)
    assert jnp.allclose(loss(h, off), ce.fused_cross_entropy(
        h, e, dropped, chunk=16, weights=off, normaliser=96.0))
    # the weights are data: their cotangent is zero
    assert not jnp.any(jax.grad(loss, 1)(h, w))


def test_the_forward_alone_runs_the_loss_scan_with_the_weights():
    h, e, labels, w = problem(4)
    got = jax.jit(lambda h: ce.fused_cross_entropy(
        h, e, labels, chunk=32, weights=w, normaliser=48.0))(h)
    assert jnp.allclose(got, naive_weighted(h, e, labels, w, 48.0),
                        rtol=1e-5)


def test_what_the_weighted_call_refuses():
    h, e, labels, w = problem(5)
    with pytest.raises(ValueError, match="normaliser"):
        ce.fused_cross_entropy(h, e, labels, normaliser=4.0)
    with pytest.raises(ValueError, match="weights"):
        ce.fused_cross_entropy(h, e, labels, weights=w[:, :5])


@pytest.mark.parametrize("which", ["loss", "grads"])
@pytest.mark.parametrize("case", sorted(_fused_ce_unweighted.CASES))
def test_the_unweighted_call_traces_the_parents_program(case, which):
    path = os.path.join(os.path.dirname(__file__), "golden",
                        "fused_ce_unweighted.json")
    with open(path) as f:
        golden = json.load(f)
    assert _fused_ce_unweighted.record(case)[which] == golden[case][which]
