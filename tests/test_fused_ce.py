"""Chunked cross-entropy vs the materialized-logits oracle: values,
gradients, ignored labels, chunk-size invariance, the memory claim
(no (N, V) residual in the jaxpr), and the two gradient rules: the
loss-only path makes its gradients in the forward scan (one scan, three
matmuls), the with-lse path recomputes (two scans, four)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.fused_ce import (
    DEFAULT_CHUNK,
    fused_cross_entropy,
    fused_cross_entropy_with_lse,
    naive_cross_entropy,
)


def _mk(n=96, d=32, v=50, seed=0, neg_frac=0.0):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(n, d).astype(np.float32))
    e = jnp.asarray(rng.randn(v, d).astype(np.float32) * 0.1)
    lab = rng.randint(0, v, size=n)
    if neg_frac:
        lab[rng.rand(n) < neg_frac] = -1
    return h, e, jnp.asarray(lab, jnp.int32)


def _grads(loss, h, e):
    return jax.grad(loss, argnums=(0, 1))(h, e)


def _assert_grads_close(got, want):
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=5e-2, atol=2e-3)


@pytest.mark.parametrize("chunk", [7, 32, 96, 1000])
def test_value_matches_oracle(chunk):
    h, e, lab = _mk()
    got = fused_cross_entropy(h, e, lab, chunk=chunk)
    want = naive_cross_entropy(h, e, lab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3)


def test_fused_ce_chunk_none_is_static_default():
    h, e, lab = _mk()
    got = fused_cross_entropy(h, e, lab)
    want = fused_cross_entropy(h, e, lab, chunk=DEFAULT_CHUNK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_ce_rejects_bad_chunk():
    h, e, lab = _mk()
    with pytest.raises(ValueError, match="chunk"):
        fused_cross_entropy(h, e, lab, chunk=0)


def test_grads_match_oracle():
    h, e, lab = _mk()
    _assert_grads_close(
        _grads(lambda h, e: fused_cross_entropy(h, e, lab, chunk=32), h, e),
        _grads(lambda h, e: naive_cross_entropy(h, e, lab), h, e))


@pytest.mark.parametrize("checkpointed", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("chunk", [7, 32, 96, 1000])
def test_grads_match_oracle_ignored_labels_scaled_cotangent(
        chunk, dtype, checkpointed):
    """The forward-scan rule against autodiff of the oracle: some labels
    ignored (a valid count that is no power of two), an upstream
    cotangent other than 1, every chunking, and the loss head under
    ``jax.checkpoint`` (the forward rule is then run again in backward)."""
    h, e, lab = _mk(neg_frac=0.3, seed=2)
    n_valid = int((np.asarray(lab) >= 0).sum())
    assert 0 < n_valid < 96 and n_valid & (n_valid - 1)
    h = h.astype(dtype)

    def head(h, e):
        return fused_cross_entropy(h, e, lab, chunk=chunk)

    if checkpointed:
        head = jax.checkpoint(head)
    got = _grads(lambda h, e: 3.7 * head(h, e), h, e)
    want = _grads(lambda h, e: 3.7 * naive_cross_entropy(h, e, lab), h, e)
    assert got[0].dtype == dtype and got[1].dtype == e.dtype
    _assert_grads_close(got, want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("chunk", [16, 128])
def test_forward_scan_rule_equals_recompute_rule_to_the_bit(chunk, dtype):
    """All labels valid and N a power of two: the cotangent is 2**-7, a
    scaling that commutes with every rounding, so scaling after the
    matmuls (the forward-scan rule) and before them (the recomputing
    rule of the with-lse path) give the same bits."""
    h, e, lab = _mk(n=128, seed=3)
    h = h.astype(dtype)
    new = jax.value_and_grad(
        lambda h, e: fused_cross_entropy(h, e, lab, chunk=chunk),
        argnums=(0, 1))(h, e)
    old = jax.value_and_grad(
        lambda h, e: fused_cross_entropy_with_lse(h, e, lab, chunk=chunk)[0],
        argnums=(0, 1))(h, e)
    for got, want in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _count(jaxpr, counts, carries):
    """Primitive counts, and the shapes of the scans' carries, of a jaxpr
    and all the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        if name == "scan":
            carries.extend(
                v.aval.shape for v in eqn.outvars[:eqn.params["num_carry"]])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, counts, carries)
    return counts, carries


@pytest.mark.parametrize("which,scans,dots", [
    ("grad", 1, 3), ("forward", 1, 1), ("grad_with_lse", 2, 4)])
def test_jaxpr_scans_and_matmuls(which, scans, dots):
    """The loss-only gradient is ONE chunk scan with three matmuls a
    chunk (no logits recomputed); forward-only it is one matmul and
    carries no ``(V, D)`` embedding gradient; the with-lse gradient keeps
    the recomputing rule: two scans, four matmuls."""
    n, d, v, chunk = 256, 16, 40, 64
    h = jnp.zeros((n, d), jnp.bfloat16)
    e = jnp.zeros((v, d), jnp.float32)
    lab = jnp.zeros((n,), jnp.int32)
    fn = {
        "grad": jax.grad(
            lambda h, e: fused_cross_entropy(h, e, lab, chunk=chunk),
            argnums=(0, 1)),
        "forward": lambda h, e: fused_cross_entropy(h, e, lab, chunk=chunk),
        "grad_with_lse": jax.grad(
            lambda h, e: fused_cross_entropy_with_lse(
                h, e, lab, chunk=chunk)[0], argnums=(0, 1)),
    }[which]
    counts, carries = _count(jax.make_jaxpr(fn)(h, e).jaxpr, {}, [])
    assert counts.get("scan", 0) == scans
    assert counts.get("dot_general", 0) == dots
    assert ((v, d) in carries) == (which != "forward")


def test_ignored_labels_zero_loss_and_grad():
    h, e, lab = _mk(neg_frac=0.3, seed=1)
    mask = np.asarray(lab) >= 0
    # Value equals the oracle restricted to valid tokens.
    got = fused_cross_entropy(h, e, lab, chunk=16)
    want = naive_cross_entropy(h, e, lab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3)
    # Ignored rows get exactly zero hidden-gradient.
    gh = jax.grad(lambda h: fused_cross_entropy(h, e, lab, chunk=16))(h)
    np.testing.assert_array_equal(
        np.asarray(gh)[~mask], np.zeros_like(np.asarray(gh)[~mask])
    )
    assert np.abs(np.asarray(gh)[mask]).max() > 0


def test_all_labels_ignored_is_zero_not_nan():
    h, e, _ = _mk(n=8)
    lab = jnp.full((8,), -1, jnp.int32)
    out = fused_cross_entropy(h, e, lab)
    assert float(out) == 0.0
    gh, ge = jax.grad(
        lambda h, e: fused_cross_entropy(h, e, lab), argnums=(0, 1)
    )(h, e)
    assert np.all(np.asarray(gh) == 0) and np.all(np.asarray(ge) == 0)


def test_batched_shape_and_bf16_hidden():
    h, e, lab = _mk(n=96)
    h3 = h.reshape(4, 24, -1).astype(jnp.bfloat16)
    got = fused_cross_entropy(h3, e, lab.reshape(4, 24), chunk=24)
    want = fused_cross_entropy(h.astype(jnp.bfloat16), e, lab, chunk=24)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-3
    )


def test_with_lse_matches_oracle_lse():
    h, e, lab = _mk(n=64, v=40)
    loss, lse = fused_cross_entropy_with_lse(h, e, lab, chunk=16)
    logits = jnp.dot(
        h.astype(jnp.bfloat16), e.astype(jnp.bfloat16).T,
        preferred_element_type=jnp.float32,
    )
    want_lse = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want_lse), rtol=1e-3, atol=1e-3
    )


def test_lse_output_is_differentiable():
    """The z-loss pattern: grad through mean(lse^2) must flow (the lse
    cotangent path in the custom vjp)."""
    h, e, lab = _mk(n=32, v=20)

    def zloss(h, e):
        loss, lse = fused_cross_entropy_with_lse(h, e, lab, chunk=8)
        return loss + 1e-3 * jnp.mean(lse**2)

    def zloss_oracle(h, e):
        logits = jnp.dot(
            h.astype(jnp.bfloat16), e.astype(jnp.bfloat16).T,
            preferred_element_type=jnp.float32,
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return naive_cross_entropy(h, e, lab) + 1e-3 * jnp.mean(lse**2)

    _assert_grads_close(_grads(zloss, h, e), _grads(zloss_oracle, h, e))


def test_no_full_logit_residual_in_grad_jaxpr():
    """The memory claim, checked structurally: the grad computation never
    holds an (N, V) array — every intermediate with a V axis is at most
    (chunk, V)."""
    n, d, v, chunk = 4096, 16, 512, 64
    h = jnp.zeros((n, d), jnp.bfloat16)
    e = jnp.zeros((v, d), jnp.float32)
    lab = jnp.zeros((n,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda h, e: fused_cross_entropy(h, e, lab, chunk=chunk),
                 argnums=(0, 1))
    )(h, e)
    biggest = 0
    for eqn in jaxpr.jaxpr.eqns:
        for var in list(eqn.outvars):
            shape = getattr(var.aval, "shape", ())
            if len(shape) >= 2 and shape[-1] == v:
                biggest = max(biggest, int(np.prod(shape[:-1])))
    assert biggest <= chunk, (
        f"grad holds a ({biggest}, {v}) logit-like array; chunking broken"
    )


def test_shape_mismatch_raises():
    h, e, lab = _mk()
    with pytest.raises(ValueError, match="labels"):
        fused_cross_entropy(h, e, lab[:-1])
    with pytest.raises(ValueError, match="dim"):
        fused_cross_entropy(h, e[:, :-1], lab)
