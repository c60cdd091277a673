"""The dropless dispatch's two row movers (``parallel.moe_dropless``:
``take_rows`` / ``add_rows``, and ``gather_rows`` / ``combine`` joined
over them) against the plain ``jax.numpy`` bodies the package had until
PR 33, kept here as the reference: values and every gradient, at one and
at six experts a token, over the layouts a routing can leave."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chainermn_tpu.observability import device_trace  # noqa: E402
from chainermn_tpu.observability.spans import named_scope  # noqa: E402
from chainermn_tpu.parallel import moe_dropless as moe  # noqa: E402

TOKENS, EXPERTS, D, TILE = 48, 16, 24, 8


# ------------------------------------------------------ the plain reference

def gather_rows_ref(x, plan):
    return jnp.where(plan.valid[:, None], x[plan.token], 0)


def combine_ref(y, weight, plan, n_tokens):
    w = jnp.where(plan.valid, weight.reshape(-1)[plan.pair], 0.0)
    rows = jnp.where(plan.valid[:, None], y.astype(jnp.float32), 0.0
                     ) * w[:, None]
    out = jnp.zeros((n_tokens, y.shape[-1]), jnp.float32).at[
        plan.token].add(rows)
    return jnp.where(plan.past_bound > 0, jnp.nan, out)


# ------------------------------------------------------------- the layouts

#: name: (held, pairs on each held expert (None: as the scores fall),
#:        rows the buffer is laid out for)
LAYOUTS = {
    "as_it_falls": ((4, 4), None, 64),
    "one_live_tile": ((4, 1), [5], 32),
    "every_tile_live": ((4, 4), [9, 9, 9, 9], 28),
    "an_empty_group": ((4, 4), [7, 0, 12, 3], 64),
    "a_group_ends_on_a_tiles_last_row": ((4, 4), [16, 5, 8, 1], 64),
}


def choice(layout, top_k, seed=0):
    """(TOKENS, top_k) distinct experts a token with the layout's pairs
    on the held experts: at one expert a token the held experts' tokens
    are disjoint runs, at six they overlap (a token then has rows in
    several groups)."""
    (first, count), sizes, rows = LAYOUTS[layout]
    scores = np.random.default_rng(seed).random((TOKENS, EXPERTS))
    if sizes is not None:
        scores[:, first:first + count] = -1.0
        start = 0
        for e, n in enumerate(sizes):
            scores[start:start + n, first + e] = 2.0
            start += n if top_k == 1 else 2
    chosen = np.argsort(-scores, axis=1)[:, :top_k].astype(np.int32)
    return jnp.asarray(chosen), (first, count), rows


def poisoned(a, plan, value=np.nan):
    """``a`` (rows, ...) with the rows of dead tiles set to NaN: nothing
    may read them."""
    a = np.array(a)
    a[~live_rows(plan)] = value
    return jnp.asarray(a)


def operands(plan, top_k, dtype, seed=1):
    rng = np.random.default_rng(seed)
    rows = plan.token.shape[0]
    return (jnp.asarray(rng.normal(size=(TOKENS, D)), dtype),
            jnp.asarray(rng.normal(size=(rows, D)), dtype),
            jnp.asarray(rng.random((TOKENS, top_k)), jnp.float32))


def live_rows(plan):
    return np.repeat(np.arange(plan.tile_group.shape[0])
                     < int(plan.n_live[0]), TILE)


CASES = [(layout, k) for layout in LAYOUTS for k in (1, 6)]


@pytest.mark.parametrize("layout,top_k", CASES)
def test_the_plan_reads_the_same_from_the_tokens_side(layout, top_k):
    """Every row that holds a pair is its token's first row or one of the
    packed further rows, once; at one expert a token there are none."""
    chosen, held, rows = choice(layout, top_k)
    plan = moe.dispatch(chosen, held, rows, tile_rows=TILE)
    assert int(plan.past_bound) == 0
    n_rows = plan.token.shape[0]
    valid = np.flatnonzero(np.asarray(plan.valid))
    first = np.asarray(plan.first_row)
    more = np.asarray(plan.more_rows)[:int(plan.n_more)]
    assert sorted(first[first < n_rows].tolist() + more.tolist()) == (
        valid.tolist())
    token = np.asarray(plan.token)
    held_tokens = np.flatnonzero(first < n_rows)
    np.testing.assert_array_equal(token[first[held_tokens]], held_tokens)
    assert np.all(np.diff(more) > 0)                      # in row order
    # a token's first row is its first held choice's
    pair = np.asarray(plan.pair)
    for t in held_tokens:
        assert pair[first[t]] == min(pair[r] for r in valid if token[r] == t)
    if top_k == 1:
        assert plan.more_rows.shape == (0,) and int(plan.n_more) == 0
    elif LAYOUTS[layout][1] is not None and held[1] > 1:
        assert int(plan.n_more) > 0
    stats = moe.load_stats(chosen, EXPERTS, held, tile_rows=TILE)
    assert stats["live_tiles"] <= int(plan.n_live[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout,top_k", CASES)
def test_values_match_the_plain_bodies(layout, top_k, dtype):
    chosen, held, rows = choice(layout, top_k)
    plan = moe.dispatch(chosen, held, rows, tile_rows=TILE)
    x, y, weight = operands(plan, top_k, dtype)
    live = live_rows(plan)
    assert live.sum() == TILE * int(plan.n_live[0])
    if layout == "every_tile_live":
        assert live.all()
    if layout == "one_live_tile":
        assert live.sum() == TILE

    took = moe.gather_rows(x, plan)
    assert took.dtype == x.dtype and took.shape == (live.size, D)
    np.testing.assert_array_equal(
        np.asarray(took, np.float32)[live],
        np.asarray(gather_rows_ref(x, plan), np.float32)[live])
    np.testing.assert_array_equal(
        np.asarray(moe.take_rows(x, plan), np.float32)[live],
        np.asarray(took, np.float32)[live])

    want = np.asarray(combine_ref(y, weight, plan, TOKENS))
    got = moe.combine(poisoned(y, plan), weight, plan, TOKENS)
    assert got.dtype == jnp.float32
    if top_k == 1:                      # one term a token: bit for bit
        np.testing.assert_array_equal(np.asarray(got), want)
    else:                               # up to the order of six additions
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6,
                                   atol=1e-6)
    # add_rows is combine at weight one
    ones = jnp.ones((TOKENS, top_k), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(moe.add_rows(poisoned(y, plan), plan, TOKENS)),
        np.asarray(combine_ref(y, ones, plan, TOKENS)), rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("layout,top_k", CASES)
def test_every_gradient_matches_the_plain_bodies(layout, top_k):
    """``gather_rows``'s transpose is ``add_rows``, ``combine``'s is
    ``take_rows`` of the cotangent times the weight, and the weight's
    gradient the live rows' sums: against autodiff of the plain bodies,
    with the cotangents' and the rows' dead tiles poisoned."""
    chosen, held, rows = choice(layout, top_k)
    plan = moe.dispatch(chosen, held, rows, tile_rows=TILE)
    x, y, weight = operands(plan, top_k, jnp.float32)
    rng = np.random.default_rng(2)
    d_rows = jnp.asarray(rng.normal(size=y.shape), jnp.float32)
    d_out = jnp.asarray(rng.normal(size=(TOKENS, D)), jnp.float32)
    live = live_rows(plan)

    dx = jax.vjp(lambda x: moe.gather_rows(x, plan), x)[1](
        poisoned(d_rows, plan))[0]
    dx_ref = jax.vjp(lambda x: gather_rows_ref(x, plan), x)[1](d_rows)[0]
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=2e-6, atol=1e-6)

    dy, dw = jax.vjp(lambda y, w: moe.combine(y, w, plan, TOKENS),
                     poisoned(y, plan), weight)[1](d_out)
    dy_ref, dw_ref = jax.vjp(
        lambda y, w: combine_ref(y, w, plan, TOKENS), y, weight)[1](d_out)
    np.testing.assert_array_equal(np.asarray(dy)[live],
                                  np.asarray(dy_ref)[live])
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(dw))

    # in the compute type a token's rows are summed in float32 and
    # rounded once
    xb = x.astype(jnp.bfloat16)
    dxb = jax.vjp(lambda x: moe.gather_rows(x, plan), xb)[1](
        poisoned(d_rows, plan).astype(jnp.bfloat16))[0]
    assert dxb.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(dxb, np.float32),
        np.asarray(moe.add_rows(
            poisoned(d_rows, plan).astype(jnp.bfloat16), plan,
            TOKENS).astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("layout,top_k", CASES)
def test_take_and_add_are_each_others_transpose(layout, top_k):
    chosen, held, rows = choice(layout, top_k)
    plan = moe.dispatch(chosen, held, rows, tile_rows=TILE)
    x, y, _ = operands(plan, top_k, jnp.float32)
    live = live_rows(plan)
    took = np.asarray(moe.take_rows(x, plan), np.float64)[live]
    added = np.asarray(moe.add_rows(poisoned(y, plan), plan, TOKENS),
                       np.float64)
    np.testing.assert_allclose(
        np.sum(took * np.asarray(y, np.float64)[live]),
        np.sum(np.asarray(x, np.float64) * added), rtol=1e-5)


@pytest.mark.parametrize("top_k", [1, 6])
def test_a_pair_past_the_bound_is_nan_throughout(top_k):
    """A buffer too small for the held pairs: the output is NaN, both
    from ``combine`` and from ``add_rows``, as the plain body's."""
    chosen, held, _ = choice("a_group_ends_on_a_tiles_last_row", top_k)
    plan = moe.dispatch(chosen, held, 0, tile_rows=TILE)   # a tile a group
    assert int(plan.past_bound) > 0
    _, y, weight = operands(plan, top_k, jnp.float32)
    assert np.all(np.isnan(combine_ref(y, weight, plan, TOKENS)))
    assert np.all(np.isnan(moe.combine(y, weight, plan, TOKENS)))
    assert np.all(np.isnan(moe.add_rows(y, plan, TOKENS)))


def test_under_jit_and_remat_the_gradients_are_the_same():
    """The two custom rules inside ``jax.checkpoint`` and ``jit``, as a
    rematerialised layer runs them."""
    chosen, held, rows = choice("as_it_falls", 6)
    plan = moe.dispatch(chosen, held, rows, tile_rows=TILE)
    x, _, weight = operands(plan, 6, jnp.float32)

    def loss(gather_rows, combine):
        def f(x, weight):
            took = gather_rows(x, plan)
            return jnp.sum(combine(jnp.tanh(took), weight, plan, TOKENS) ** 2)
        return f

    want = jax.grad(loss(gather_rows_ref, combine_ref), argnums=(0, 1))(
        x, weight)
    got = jax.jit(jax.grad(jax.checkpoint(
        loss(moe.gather_rows, moe.combine)), argnums=(0, 1)))(x, weight)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("top_k", [1, 6])
def test_every_dispatch_op_lowers_under_its_scope(top_k):
    """Forward and backward, every op the two movers lower to (loops,
    gathers, scatters, slices, selects) resolves to ``moe-dispatch``: what
    ``moe.dispatch_ms`` / ``zaya.dispatch_ms`` join the trace to, and
    what ``moe-experts`` must read none of.  The test's own ops (the
    stand-in for the experts, the loss) carry ``moe-experts``."""
    chosen, held, rows = choice("as_it_falls", top_k)
    x, _, weight = operands(
        moe.dispatch(chosen, held, rows, tile_rows=TILE), top_k,
        jnp.bfloat16)

    def loss(x, weight, chosen):
        with named_scope("moe-route"):
            plan = moe.dispatch(chosen, held, rows, tile_rows=TILE)
        took = moe.gather_rows(x, plan)
        with named_scope("moe-experts"):
            took = jnp.tanh(took)
        out = moe.combine(took, weight, plan, TOKENS)
        with named_scope("moe-experts"):
            return jnp.sum(out ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, weight, chosen).compile()
    regions = {}
    for name, path in device_trace.scope_table(compiled).items():
        if path:
            regions.setdefault(device_trace.classify(path)[1], []).append(
                path)
    # outside every scope: parameters and reducers' bodies (bare names),
    # and the calls that hold the jitted movers (containers: their ops
    # are counted, not they)
    unscoped = {p for p in regions.get(None, [])
                if "/" in p and "jit(" not in p.split("/")[-1]}
    assert not unscoped, unscoped
    assert set(regions) - {None} == {"moe-route", "moe-dispatch",
                                     "moe-experts"}
    moved = regions["moe-dispatch"]
    assert any("transpose(jvp(" in p for p in moved)
    assert any("transpose(jvp(" not in p for p in moved)
    for path in regions["moe-experts"]:       # the test's own ops alone
        assert path.split("/")[-1] not in (
            "while", "gather", "scatter", "scatter-add", "dynamic_slice",
            "dynamic_update_slice", "select_n"), path
    assert any(p.endswith("/while") for p in moved)
    assert any(p.endswith("/gather") for p in moved)


def test_load_stats_counts_the_live_share():
    chosen, held, _ = choice("an_empty_group", 6)
    stats = moe.load_stats(chosen, EXPERTS, held, tile_rows=TILE)
    assert stats["live_rows_share"] == pytest.approx(
        stats["live_tiles"] / stats["buffer_tiles"])
    assert 0 < stats["live_rows_share"] <= 1


def test_the_layer_names_its_dispatch_form_when_someone_listens():
    """``moe_geometry`` and the ``moe/*`` gauges carry the dispatch's form
    and the most steps its loop over the live tiles can take."""
    from chainermn_tpu.models.block_table import ExpertsSpec
    from chainermn_tpu.models.transformer import ExpertLayer
    from chainermn_tpu.observability import reporter

    spec = ExpertsSpec(n_experts=8, top_k=3, d_expert=16, d_shared=16,
                       held=(2, 2))
    layer = ExpertLayer(32, spec, jnp.float32)
    rep = reporter.Reporter()
    with reporter.scope(rep):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((2, 8, 32)))
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    assert moe.DISPATCH_FORM == "xla_gather_both_ways"
    assert gauges[f"moe/{moe.DISPATCH_FORM}"] == 1
    # 48 pair rows, all within the bound: one tile of 256 + one a group
    assert gauges["moe/dispatch_steps"] == 3
    assert gauges["moe/buffer_rows"] == 3 * gauges["moe/tile_rows"]
