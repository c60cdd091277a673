"""``ops.kda.kda_rule`` — the chunked delta rule under a decay a key
channel, with the heads' float32 side (the ``q`` / ``k`` unit norms, the
log-decay from ``f`` and its running sums) made inside the kernels —
against the recurrence it is the chunked form of, one token a step, fed
by ``KDAMixer``'s XLA formulas for that side (:func:`gate_side`); with
``g`` at the family's lower bound everywhere (the float32 range the
sub-blocks' reference points are there for); and with ``g`` equal across a
head's channels against ``gated_delta_rule``.  The rule is two Mosaic
kernels, interpreted here; a tile's edge, the tile rule, the kept states
and the parameters' gradients summed over tiles and batch rows have their
own cases."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chainermn_tpu.ops.gated_delta import gated_delta_rule
from chainermn_tpu.ops.kda import SUB, kda_rule

FLOOR = -5.0
#: The rule's operands in order, by their cotangents' names (``A_log``:
#: the rule takes ``exp(A_log)``).
WRT = ["dq", "dk", "dv", "df", "dbeta", "dA_log", "ddt_bias"]


def gate_side(q, k, f, a_log, dt_bias, floor=FLOOR):
    """What ``KDAMixer`` made beside the calls until PR 52, float32:
    ``q / |q| / sqrt(d_k)``, ``k / |k|`` and the log-decay ``g``."""
    def unit(x):
        return x * lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    g = floor * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * (f + dt_bias))
    return unit(q) * (1.0 / np.sqrt(q.shape[-1])), unit(k), g


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


def reference(q, k, v, f, beta, a_log, dt_bias, floor=FLOOR):
    """The recurrence behind the mixer's XLA gate side."""
    qn, kn, g = gate_side(q, k, f, a_log, dt_bias, floor)
    return recurrence(qn, kn, v, g, beta)


def rule(chunk, floor=FLOOR):
    """``kda_rule`` over the same seven operands as :func:`reference`."""
    def run(q, k, v, f, beta, a_log, dt_bias):
        return kda_rule(q, k, v, f, beta, jnp.exp(a_log), dt_bias,
                        lower_bound=floor, chunk=chunk)
    return run


def operands(S=128, H=2, dk=16, dv=8, b=1, seed=0):
    """``q``, ``k`` as a convolution hands them (no unit length), ``v``,
    ``f``, ``beta``, ``A_log`` (H,) and ``dt_bias`` (H, d_k)."""
    rng = np.random.RandomState(seed)
    q = rng.randn(b, S, H, dk) * np.exp(rng.randn(b, S, H, 1))
    k = rng.randn(b, S, H, dk) * np.exp(rng.randn(b, S, H, 1))
    v = rng.randn(b, S, H, dv)
    f = 2.0 * rng.randn(b, S, H, dk)
    beta = 1.0 / (1.0 + np.exp(-rng.randn(b, S, H)))
    a_log = 0.3 * rng.randn(H)
    dt_bias = 0.5 * rng.randn(H, dk)
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, f, beta, a_log, dt_bias))


def grad_of(fn, ops, do, which):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * do),
                    argnums=WRT.index(which))(*ops)


def cotangent(ops, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(
        *ops[2].shape), jnp.float32)


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_matches_the_recurrence(chunk):
    """The norms, the decay and its sums made in the kernel: ``o`` at one
    sub-block a chunk and at four."""
    ops = operands()
    np.testing.assert_allclose(
        np.asarray(rule(chunk)(*ops)), np.asarray(reference(*ops)),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("which", WRT)
@pytest.mark.parametrize("chunk", [16, 64])
def test_gradients_match_the_recurrence(chunk, which):
    """Every cotangent the backward kernel hands back — through the
    norms, the sums and the sigmoid, and summed for ``A_log`` and
    ``dt_bias`` — against autodiff of the recurrence behind the XLA
    formulas."""
    ops = operands(seed=1)
    do = cotangent(ops, 2)
    got = grad_of(rule(chunk), ops, do, which)
    want = grad_of(reference, ops, do, which)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-5 * max(scale, 1.0))


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_lower_bound_everywhere_stays_finite_and_right(chunk):
    """``g = -5`` a token and channel (``f`` so large that the sigmoid
    reads 1): a column's factor inside its own sub-block reaches ``e^{5
    (SUB - 1)} = e^75``, which float32 holds; one reference a chunk of 64
    would need ``e^315``."""
    q, k, v, f, beta, a_log, dt_bias = operands(seed=3)
    ops = (q, k, v, jnp.full(f.shape, 40.0), beta, a_log, dt_bias)
    np.testing.assert_array_equal(
        np.asarray(gate_side(q, k, ops[3], a_log, dt_bias)[2]), FLOOR)
    assert -FLOOR * (SUB - 1) < 88.0
    got, grads = jax.value_and_grad(
        lambda *a: jnp.sum(rule(chunk)(*a) ** 2),
        argnums=tuple(range(7)))(*ops)
    assert np.isfinite(float(got))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)
    np.testing.assert_allclose(
        np.asarray(rule(chunk)(*ops)), np.asarray(reference(*ops)),
        rtol=2e-4, atol=2e-5)


def test_a_lower_bound_past_float32s_range_is_refused():
    ops = operands(S=32)
    with pytest.raises(ValueError, match="lower_bound"):
        rule(16, floor=-6.0)(*ops)


@pytest.mark.parametrize("which", ["o", "dq", "dk", "dv", "dg", "dbeta"])
def test_equal_across_channels_is_the_scalar_rule(which):
    """With ``f`` and ``dt_bias`` one number a head the decay is, and the
    vector rule IS ``gated_delta_rule``'s (its kernels, interpreted
    here, fed the XLA formulas' ``q``, ``k`` and ``g``)."""
    q, k, v, f, beta, a_log, _ = operands(S=128, H=2, dk=128, dv=128, seed=4)
    f1 = f[..., 0]
    do = cotangent((q, k, v), 5)
    zero = jnp.zeros((2, 128), jnp.float32)

    def vector(q, k, v, f1, beta):
        return rule(64)(q, k, v, jnp.broadcast_to(f1[..., None], q.shape),
                        beta, a_log, zero)

    def scalar(q, k, v, f1, beta):
        qn, kn, g = gate_side(q, k, f1[..., None], a_log, zero[:, :1])
        return gated_delta_rule(qn, kn, v, g[..., 0], beta, chunk=64)

    if which == "o":
        got, want = vector(q, k, v, f1, beta), scalar(q, k, v, f1, beta)
    else:
        i = ["dq", "dk", "dv", "dg", "dbeta"].index(which)
        got, want = (jax.grad(lambda *a: jnp.sum(fn(*a) * do), argnums=i)(
            q, k, v, f1, beta) for fn in (vector, scalar))
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4 * scale)


@pytest.mark.parametrize("which", ["o", "df", "dA_log", "ddt_bias", "dk"])
def test_a_ragged_sequence_is_padded_with_tokens_that_write_nothing(which):
    """100 tokens in chunks of 64: the 28 lanes past the last token write
    nothing (``beta`` 0) and — though ``sigmoid(rate * (0 + dt_bias))`` is
    no 0 — decay nothing, and hand nothing to ``A_log`` or ``dt_bias``."""
    ops = operands(S=100)
    if which == "o":
        got, want = rule(64)(*ops), reference(*ops)
    else:
        do = cotangent(ops, 11)
        got, want = (grad_of(fn, ops, do, which)
                     for fn in (rule(64), reference))
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-5 * scale)


@pytest.fixture
def two_tiles(monkeypatch):
    """Tiles of two chunks of 64: 256 tokens are two grid steps a head."""
    from chainermn_tpu.ops import kda

    monkeypatch.setattr(kda, "_KDA_TOKENS", 128)
    jax.clear_caches()              # the calls are jitted by shapes alone
    yield kda
    jax.clear_caches()


@pytest.mark.parametrize("which", ["o"] + WRT)
def test_the_state_crosses_tiles_forward_and_its_cotangent_back(
        two_tiles, which):
    """More than one TILE a head and two batch rows: forward the state is
    carried from tile to tile, backward each tile starts from the state
    the forward kept and hands the state's cotangent to the tile before
    it; the cotangents of ``A_log`` and ``dt_bias`` add up over the tiles
    of a batch row in the kernel and over the batch rows beside it."""
    ops = operands(S=256, b=2, seed=6)
    assert two_tiles.kda_tiles(256, 64, 2, 16, 8, jnp.float32)[:2] == (128, 2)
    if which == "o":
        got, want = rule(64)(*ops), reference(*ops)
    else:
        do = cotangent(ops, 7)
        got, want = (grad_of(fn, ops, do, which)
                     for fn in (rule(64), reference))
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-5 * scale)


def test_the_kept_states_are_the_states_the_tiles_started_from(two_tiles):
    q, k, v, f, beta, a_log, dt_bias = operands(S=256, seed=8)
    o, starts = two_tiles._kda_fwd_call(
        q, k, v, f, beta, jnp.exp(a_log), dt_bias, C=64, floor=FLOOR,
        keep=True, interpret=True)
    assert starts.shape == (1, 2, 2, 16, 8)
    np.testing.assert_array_equal(np.asarray(starts[:, :, 0]), 0.0)
    hi = lax.Precision.HIGHEST
    q, k, g = gate_side(q, k, f, a_log, dt_bias)
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
        np.asarray(o), np.asarray(rule(64)(*operands(S=256, seed=8))),
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
def test_df_where_a_heads_channels_decay_at_very_different_rates(chunk):
    """One channel at the lower bound (its history gone in three tokens),
    one that barely decays, the rest in between: ``df`` is a number a
    channel, and every channel's is the recurrence's."""
    q, k, v, f, beta, a_log, dt_bias = operands(seed=9)
    a_log, dt_bias = jnp.zeros_like(a_log), jnp.zeros_like(dt_bias)
    f = f.at[..., 0].set(9.0).at[..., 1].set(-9.0)
    g = gate_side(q, k, f, a_log, dt_bias)[2]
    assert float(jnp.max(g[..., 0])) < -4.99 and (
        float(jnp.min(g[..., 1])) > -1e-3)
    do = cotangent((q, k, v), 10)
    got, want = (jax.grad(lambda f: jnp.sum(
        fn(q, k, v, f, beta, a_log, dt_bias) * do))(f)
        for fn in (rule(chunk), reference))
    for channel in (0, 1, slice(2, None)):
        scale = float(jnp.max(jnp.abs(want[..., channel])))
        assert scale > 0
        np.testing.assert_allclose(
            np.asarray(got[..., channel]), np.asarray(want[..., channel]),
            rtol=1e-3, atol=2e-4 * scale)


def test_the_parameters_cotangents_are_float32_sums_of_unrounded_terms():
    """bfloat16 activations: ``df`` is written rounded, the cotangents of
    ``A_log`` and ``dt_bias`` are summed from the float32 terms before
    that rounding — closer to the sum of ``df``'s float32 value than the
    sum of the rounded ``df`` is to it."""
    ops = operands(S=128, seed=12)
    low = tuple(x.astype(jnp.bfloat16) for x in ops[:4]) + ops[4:]
    do = cotangent(ops, 13).astype(jnp.bfloat16)
    df, ddt_bias = jax.grad(
        lambda *a: jnp.sum(rule(64)(*a).astype(jnp.float32) * do),
        argnums=(3, 6))(*low)
    assert df.dtype == jnp.bfloat16 and ddt_bias.dtype == jnp.float32
    rounded = jnp.sum(df.astype(jnp.float32), axis=(0, 1))
    # the same sum, from terms that were not rounded: not bit-equal
    assert bool(jnp.any(ddt_bias != rounded))
    np.testing.assert_allclose(np.asarray(ddt_bias), np.asarray(rounded),
                               rtol=0.05, atol=0.05 * float(
                                   jnp.max(jnp.abs(rounded))))


def test_shapes_that_do_not_fit_are_refused():
    q, k, v, f, beta, a_log, dt_bias = operands(S=32)
    with pytest.raises(ValueError, match="do not fit"):
        kda_rule(q, k, v, f[..., 0], beta, jnp.exp(a_log), dt_bias,
                 lower_bound=FLOOR)
    with pytest.raises(ValueError, match="do not fit"):
        kda_rule(q, k, v, f, beta, jnp.exp(a_log), dt_bias.reshape(-1),
                 lower_bound=FLOOR)
