"""``models.transformer.rotate_partial`` turns a head whole — ``x cos +
(x P) sin`` against full-width tables, the partner lanes fetched by one
product with the signed permutation ``P`` — and computes what the plain
half-split formula, written out here, computes: values and the gradient,
at the three cells' head geometries and a tiny one, for a bfloat16 and a
float32 operand, plain and under YaRN, at positions up to 16,383."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models.block_table import YarnSpec, rotary_frequencies
from chainermn_tpu.models.transformer import (
    fetch_partner,
    rotary_partner,
    rotate_partial,
)
from chainermn_tpu.observability import reporter, step_log

#: (d_head, rotary_dim): mellum's head, qwen3next's, zaya's, a tiny one
GEOMETRIES = [(128, 128), (256, 64), (128, 64), (16, 8)]
YARN = YarnSpec(16.0, 8192, attention_factor=1.2772588722239782)
#: 24 positions spread over a 16k row, its last among them
POSITIONS = np.unique(np.concatenate(
    [np.arange(4), np.linspace(5, 16383, 20).astype(np.int64)]))


def plain_halves(x, positions, rotary_dim, theta, yarn=None):
    """The half-split formula: ``a cos - b sin``, ``b cos + a sin`` over
    the two halves of the first ``rotary_dim`` lanes, the rest passed
    through; float32 against float32 tables."""
    half = rotary_dim // 2
    freq, scale = rotary_frequencies(rotary_dim, theta, yarn)
    angle = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if yarn is not None:
        cos, sin = cos * scale, sin * scale
    x = x.astype(jnp.float32)
    a, b, rest = (x[..., :half], x[..., half:rotary_dim],
                  x[..., rotary_dim:])
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def operand(d_head, dtype, key=0):
    return jax.random.normal(
        jax.random.PRNGKey(key), (2, len(POSITIONS), 3, d_head),
        jnp.float32).astype(dtype)


@pytest.mark.parametrize("yarn", [None, YARN], ids=["plain", "yarn"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("d_head,rotary_dim", GEOMETRIES)
def test_a_head_turned_whole_is_the_half_split_formula(d_head, rotary_dim,
                                                       dtype, yarn):
    """Values and the gradient through ``jax.vjp``: the float32 results
    within 1e-6, and the operand's cotangent in the operand's type — the
    cotangent turned back in float32 and rounded once, as the float32
    copy a caller used to make had it."""
    x, pos = operand(d_head, dtype), jnp.asarray(POSITIONS)
    g = jax.random.normal(jax.random.PRNGKey(1), x.shape, jnp.float32)
    got, back = jax.vjp(
        lambda x: rotate_partial(x, pos, rotary_dim, 5e5, yarn), x)
    want, plain_back = jax.vjp(
        lambda x: plain_halves(x, pos, rotary_dim, 5e5, yarn), x)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    (dx,), (plain_dx,) = back(g), plain_back(g)
    assert dx.dtype == x.dtype
    # in float32 the two cotangents agree to 1e-6; a bfloat16 one is that
    # float32 value rounded, so at most the last place apart
    ulp = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(
        dx.astype(jnp.float32), plain_dx.astype(jnp.float32),
        rtol=ulp, atol=ulp)
    # the last position is a 16k row's last
    assert POSITIONS[-1] == 16383


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("d_head,rotary_dim", GEOMETRIES)
def test_the_partner_product_is_the_gathered_partner_to_the_bit(
        d_head, rotary_dim, dtype):
    """``x @ P`` as ``rotate_partial`` makes it (one bfloat16 pass summed
    in float32 for a bfloat16 operand, ``HIGHEST`` for a float32 one) is
    ``-x[i + half]`` in lane ``i``, ``x[i]`` in lane ``i + half`` and 0
    past ``rotary_dim``: every bit of it."""
    x = operand(d_head, dtype, key=2)
    half = rotary_dim // 2
    partner = rotary_partner(rotary_dim, d_head)
    assert set(np.unique(partner)) <= {-1.0, 0.0, 1.0}
    assert np.array_equal(partner.T, -partner)    # turned back: -P
    got = fetch_partner(x, rotary_dim)
    x32 = np.asarray(x.astype(jnp.float32))
    want = np.concatenate(
        [-x32[..., half:rotary_dim], x32[..., :half],
         np.zeros_like(x32[..., rotary_dim:])], axis=-1)
    assert got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), want)


def test_the_traced_rotation_holds_no_half_wide_array():
    """No slice of the head and no concatenate: the JAXPR of the rotation
    and of its gradient names no array whose last axis is ``rotary_dim /
    2`` wide, at a geometry where no other axis is."""
    x = jnp.zeros((1, 24, 3, 256), jnp.bfloat16)

    def both(x):
        y, back = jax.vjp(
            lambda x: rotate_partial(x, jnp.arange(24), 64, 1e7), x)
        return y, back(y)[0]

    text = str(jax.make_jaxpr(both)(x))
    assert ",32]" not in text and "concatenate" not in text
    assert "slice" not in text
    assert text.count("dot_general") == 2     # the partner, and it back


def test_positions_take_no_gradient_and_the_type_follows_the_operand():
    x = operand(16, jnp.bfloat16)
    pos = jnp.asarray(POSITIONS)
    dx = jax.grad(lambda x: jnp.sum(rotate_partial(x, pos, 8, 1e4)))(x)
    assert dx.dtype == jnp.bfloat16 and dx.shape == x.shape
    # under jit and under a second transformation
    y = jax.jit(jax.vmap(lambda x: rotate_partial(x[None], pos, 8, 1e4)[0]))(
        x)
    np.testing.assert_allclose(y, rotate_partial(x, pos, 8, 1e4),
                               rtol=1e-6, atol=1e-6)


def test_the_rotation_publishes_its_geometry(tmp_path):
    """A ``rope_geometry`` row and ``rope/*`` gauges at trace time: the
    operator's report, as ``conv_geometry`` / ``gdn_geometry`` are."""
    rep, path = reporter.Reporter(), str(tmp_path / "steps.jsonl")
    with reporter.scope(rep), step_log.recording(path):
        rotate_partial(operand(256, jnp.bfloat16), jnp.asarray(POSITIONS),
                       64, 1e7)
        rotate_partial(operand(128, jnp.float32), jnp.asarray(POSITIONS),
                       64, 5e6)
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    assert gauges["rope/d_head"] == 128 and gauges["rope/rotary_dim"] == 64
    assert gauges["rope/lane_dense_product"] == 1
    assert gauges["rope/bfloat16"] == 1 and gauges["rope/float32"] == 1
    assert gauges["rope/one_bf16_pass"] == 1 and gauges["rope/highest"] == 1
    assert rep.summary()["counters"]["rope/calls"] == 2
    first, second = [r for r in map(json.loads, open(path))
                     if r["event"] == "rope_geometry"]
    assert (first["operand"], first["precision"], first["d_head"]) == (
        "bfloat16", "one_bf16_pass", 256)
    assert (second["operand"], second["precision"], second["form"]) == (
        "float32", "highest", "lane_dense_product")
