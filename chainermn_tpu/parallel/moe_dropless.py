"""Dropless top-k-of-many expert routing for one expert-parallel rank.

Three routers, one dispatch: :func:`route` (sigmoid scores of one matrix,
top-k, weights normalised over the chosen), :func:`route_softmax` (a
softmax of one matrix over all the experts, top-k, renormalised over the
chosen, no bias) and :func:`route_mlp_softmax` (a small MLP over a state
handed from layer to layer, a softmax, the one most probable expert
weighed by its probability).  All are float32 at ``highest`` throughout
and return ``(chosen, weight)`` alike; everything after them is shared.
No bias gets a gradient; :func:`rebalance` is one step of the controller
that moves it by the load instead.

The rank is told which experts it holds, ``(first, count)`` of the
published ``n_experts``.  It routes every token over ALL the experts (the
router keeps its published width), sorts the ``tokens x top_k`` (token,
choice) pairs by expert, lays the pairs of its own experts out as row
groups for :func:`chainermn_tpu.ops.grouped_matmul.grouped_matmul` (the
caller applies its experts to them) and adds the results back into the
tokens' rows, each times its router weight.  What the absent experts would have
added is left out: with every expert held (``count == n_experts``) the
same code is the whole layer; across ranks the shares add up to it (the
exchange that would carry a token to another rank's expert is not built
here — ``parallel/moe.py`` has an all-to-all, for its capacity layout).

No capacity factor: no pair is dropped for its expert being full, however
the router skews.  Shapes are static all the same.  The row buffer has
:func:`rows_bound` rows (rounded up to whole tiles, plus a tile an expert
for the zeros behind each group's last row): ``tokens x top_k``, which no
routing exceeds, or :data:`BOUND_OVER_EXPECTED` times the pairs the held
share expects, whichever is less — a rank that holds a sixteenth of the
experts sees about a sixteenth of the pairs, and a buffer for all of them
would be 0.5 GB a layer of rows that hold nothing.  A pair past the bound
cannot be computed, and then the layer's output is NaN, so that the step's
loss is: loud, never a silent drop.  :func:`load_stats` counts what a
batch did.

What the buffer's dead tiles cost.  The live tiles are a prefix of the
buffer (``plan.n_live``), and the rows move through one pair of
primitives that are each other's transpose and never visit a dead tile:
:func:`take_rows` gathers a live tile's rows a step of a loop whose trip
count is ``n_live`` (0.011 ms a tile of 256 rows x 2688; a dead tile
costs its share of one zero fill of the buffer, 0.002 ms), and
:func:`add_rows` reads from the tokens' side — every token's first row
gathered (one pass over the tokens, whatever the buffer's size), the
further rows of tokens with several scatter-added a tile's worth a step
(none at one expert a token).  :func:`gather_rows` and :func:`combine`
are the two joined by ``custom_vjp``: all five row movements of a
rematerialised layer's step go through the same two pieces of code.
Until PR 33 both were XLA ops over every row of the buffer, dead or not
(PERF.md section 6: a scatter-add of 26,624 float32 rows took 3.1 ms, a
row gather 1.4 + 0.8 ms for its select; ``benchmarks/
moe_dispatch_probe.py`` keeps that form and the others that were
weighed).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.ad_checkpoint import checkpoint_name

# (``named_scope``, the scope vocabulary's, comes by way of the kernels'
# module: this layer imports ``ops``, which owns the sibling scope)
from chainermn_tpu.ops.grouped_matmul import (
    SAVED_PRODUCTS,
    TILE_ROWS,
    named_scope,
)

#: The name (``jax.ad_checkpoint.checkpoint_name``) of the routers'
#: choice, 24 bytes a token.  With it saved a rematerialised layer's
#: backward pass multiplies the rows its forward pass chose: a choice
#: made again from recomputed activations need not round alike, and
#: settles a near-tie the other way (and would read the saved products
#: by another layout than they were written in).
ROUTER_CHOICE = "moe-router-choice"

#: What a rematerialised model keeps of its expert layers
#: (``jax.checkpoint_policies.save_only_these_names``).
REMAT_SAVES = (ROUTER_CHOICE, SAVED_PRODUCTS)

#: Rows of the held experts' buffer over the pairs their share of the
#: experts expects.  Under Zipf ids and seeded weights a layer's held
#: pairs read 0.5-1.5x the expectation (PERF.md section 6, PR 30); 4x has
#: never been approached.
BOUND_OVER_EXPECTED = 4


def rows_bound(pairs: int, count: int, n_experts: int) -> int:
    """Rows the held experts' buffer is laid out for: every pair where
    the rank holds a quarter of the experts or more (8 of 16 at one
    expert a token: all 16,384 pairs of a 16,384-token step)."""
    return min(pairs, -(-BOUND_OVER_EXPECTED * pairs * count // n_experts))


def keep_groups(biased, n_group: int, topk_group: int):
    """``biased`` (T, E) with the experts outside each token's
    ``topk_group`` best groups at ``-inf``.  The ``E`` experts are
    ``n_group`` equal runs; a group's score is the sum of its two largest
    entries; the best groups are kept (ties to the lower index)."""
    T, E = biased.shape
    if E % n_group or not 1 <= topk_group <= n_group or E // n_group < 2:
        raise ValueError(
            f"{n_group} groups, {topk_group} kept, of {E} experts: the "
            f"groups are equal runs of at least two experts")
    best_two, _ = lax.top_k(biased.reshape(T, n_group, E // n_group), 2)
    _, kept = lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    mask = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                              # (T, n_group)
    return jnp.where(jnp.repeat(mask, E // n_group, axis=1), biased,
                     -jnp.inf)


def route(h, w_router, bias, *, top_k: int, scaling: float = 1.0,
          n_group: int = 0, topk_group: int = 0):
    """Sigmoid top-k router (an ``ExpertsSpec`` whose ``router`` is
    ``"sigmoid"``; ``"mlp_softmax"`` is :func:`route_mlp_softmax`),
    float32 throughout.

    ``h``: (T, d); ``w_router``: (d, E); ``bias``: (E,), the per-expert
    correction added to the scores for the CHOICE only (it gets no
    gradient, and the weights do not see it).  Returns ``(chosen, weight)``,
    (T, top_k) int32 and float32: the ``top_k`` experts with the largest
    ``sigmoid(h w) + bias`` (ties to the lower index) and ``scaling *
    s[chosen]`` over the chosen scores' sum.  With ``n_group`` > 0 the
    choice is group-limited (DeepSeek-V3, arXiv:2412.19437): only the
    experts of a token's ``topk_group`` best groups stand
    (:func:`keep_groups`, by the biased scores); ``topk_group ==
    n_group`` keeps every group and is the choice without groups."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(f32), w_router.astype(f32),
        precision=lax.Precision.HIGHEST))
    biased = scores + lax.stop_gradient(bias.astype(f32))
    if n_group:
        biased = keep_groups(biased, n_group, topk_group)
    _, chosen = lax.top_k(biased, top_k)
    chosen = checkpoint_name(chosen.astype(jnp.int32), ROUTER_CHOICE)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, weight * scaling


def route_softmax(h, w_router, *, top_k: int, scaling: float = 1.0):
    """Softmax top-k router (an ``ExpertsSpec`` whose ``router`` is
    ``"softmax"``), float32 throughout, no bias on the choice.

    ``h``: (T, d); ``w_router``: (d, E).  Returns ``(chosen, weight)``,
    (T, top_k) int32 and float32: the ``top_k`` experts with the largest
    ``p = softmax(h w)`` over ALL the experts (ties to the lower index)
    and ``scaling * p[chosen]`` over the chosen probabilities' sum."""
    f32 = jnp.float32
    p = jax.nn.softmax(jnp.dot(
        h.astype(f32), w_router.astype(f32),
        precision=lax.Precision.HIGHEST), axis=-1)
    _, chosen = lax.top_k(p, top_k)
    chosen = checkpoint_name(chosen.astype(jnp.int32), ROUTER_CHOICE)
    weight = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, weight * (
        scaling / jnp.sum(weight, axis=-1, keepdims=True))


def route_mlp_softmax(h, state, params, bias, *, eps: float):
    """The ZAYA router (arXiv:2511.17127), float32 throughout: top-1 of a
    softmax over an MLP of a state that passes from layer to layer.

    ``h``: (T, d); ``state``: (T, r) float32, the state the layer before
    handed on (zeros before the first); ``params``: ``down`` (d, r),
    ``gamma`` (r,), ``norm`` (r,), ``w1``, ``w2`` (r, r), ``w3`` (r, E);
    ``bias``: (E,), the balancing bias, added to the probabilities for the
    CHOICE only (no gradient; the weight does not see it).  ``r_l = h
    down + gamma * r_(l-1)``; ``p = softmax(gelu(gelu(RMSNorm(r_l) w1)
    w2) w3)``.  Returns ``(chosen, weight, r_l)``: (T, 1) int32, the
    expert with the largest ``p + bias`` (ties to the lower index); (T,
    1) float32, ``p[chosen]`` itself — with one expert a token a weight
    normalised over the chosen is 1 and teaches the router nothing —;
    and the state to hand on."""
    f32 = jnp.float32
    dot = lambda a, b: jnp.dot(  # noqa: E731
        a, b.astype(f32), precision=lax.Precision.HIGHEST)
    state = dot(h.astype(f32), params["down"]) + (
        params["gamma"].astype(f32) * state.astype(f32))
    x = state * lax.rsqrt(
        jnp.mean(jnp.square(state), axis=-1, keepdims=True) + eps
    ) * params["norm"].astype(f32)
    x = jax.nn.gelu(dot(x, params["w1"]), approximate=False)
    x = jax.nn.gelu(dot(x, params["w2"]), approximate=False)
    p = jax.nn.softmax(dot(x, params["w3"]), axis=-1)
    _, chosen = lax.top_k(p + lax.stop_gradient(bias.astype(f32)), 1)
    chosen = checkpoint_name(chosen.astype(jnp.int32), ROUTER_CHOICE)
    return chosen, jnp.take_along_axis(p, chosen, axis=-1), state


def rebalance(bias, chosen, rate: float):
    """One step of a proportional balancing controller on a router's
    bias, applied BESIDE the optimizer's update (the bias gets no
    gradient: it enters the choice only).  ``bias``: (E,); ``chosen``:
    (T, top_k), the experts the router chose this step.  The bias of an
    expert that took more than its even share ``1/E`` of the pairs falls
    by ``rate`` times the excess share, that of one that took less rises;
    their sum stays.  The bias integrates the error, so a load held off
    its share by a steady drift of the router's weights settles
    ``drift / rate`` away from even."""
    n = bias.shape[0]
    share = jnp.mean(jax.nn.one_hot(
        chosen.reshape(-1), n, dtype=jnp.float32), axis=0)
    return bias + rate * (1.0 / n - share)


class Dispatch(NamedTuple):
    """Where each row of the held experts' buffer comes from."""

    token: jax.Array        # (rows,) int32: the row's token (0 if none)
    pair: jax.Array         # (rows,) int32: its index into (T * top_k,)
    valid: jax.Array        # (rows,) bool: the row holds a pair
    tile_group: jax.Array   # (n_tiles,) int32: the tile's local expert
    n_live: jax.Array       # (1,) int32: tiles that hold anything
    past_bound: jax.Array   # () int32: held pairs the buffer had no row for
    first_row: jax.Array    # (T,) int32: the row of a token's first held
    #                         choice (``rows`` if it has none)
    more_rows: jax.Array    # (rows,) int32: the rows of further choices,
    #                         packed to the front in row order; (0,) at one
    #                         expert a token
    n_more: jax.Array       # () int32: how many of them there are


def buffer_tiles(rows: int, count: int, tile_rows: int = TILE_ROWS) -> int:
    """Tiles of a buffer for ``rows`` rows however they fall into
    ``count`` groups: a group leaves under one tile of zeros behind its
    last row, and an empty group holds one tile."""
    return -(-rows // tile_rows) + count


def dispatch(chosen, held: Tuple[int, int], rows: int,
             tile_rows: int = TILE_ROWS) -> Dispatch:
    """Sort the (token, choice) pairs by expert and lay those of the held
    experts out in the tiles of a buffer for ``rows`` rows, each group
    from a tile's first row — and the same plan from the tokens' side,
    for :func:`add_rows`: the row of each token's first choice on a held
    expert, and the rows of its further ones."""
    first, count = held
    T, k = chosen.shape
    pairs = T * k
    n_tiles = buffer_tiles(rows, count, tile_rows)
    expert = chosen.reshape(pairs) - first
    local = jnp.where((expert >= 0) & (expert < count), expert, count)
    # Sorted with each pair goes one bit: whether it is a FURTHER pair of
    # its token, one after the token's first on a held expert (none at one
    # expert a token).  The tokens' side of the plan is read off it.
    with named_scope("moe-dispatch"):
        on_held = (local < count).reshape(T, k)
        further = on_held & (jnp.cumsum(on_held, axis=1) > 1)
    _, order = lax.sort(
        (local, 2 * jnp.arange(pairs, dtype=jnp.int32)
         + further.reshape(pairs)), num_keys=1, is_stable=True)
    sizes = jnp.sum(local[:, None] == jnp.arange(count), axis=0,
                    dtype=jnp.int32)     # (a scatter of ones is 5x slower)
    tiles = jnp.maximum(1, -(-sizes // tile_rows))
    tile_end = jnp.cumsum(tiles)                  # in tiles, exclusive end
    tile_start = tile_end - tiles
    pair_start = jnp.cumsum(sizes) - sizes        # in the sorted order
    n_live = jnp.minimum(tile_end[-1], n_tiles)

    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, tile, side="right"), count - 1
    ).astype(jnp.int32)
    row = jnp.arange(n_tiles * tile_rows, dtype=jnp.int32)
    group = jnp.repeat(tile_group, tile_rows)
    within = row - tile_start[group] * tile_rows
    valid = (row < n_live * tile_rows) & (within < sizes[group])
    sorted_pair = order[jnp.clip(pair_start[group] + within, 0, pairs - 1)]
    pair = jnp.where(valid, sorted_pair // 2, 0)
    token = pair // k
    with named_scope("moe-dispatch"):
        # the plan from the tokens' side: where each token's first row is,
        # and the further rows packed to the front of a list, in row order
        more = valid & (sorted_pair % 2 == 1)
        first_row = jnp.full((T,), row.shape[0], jnp.int32).at[
            jnp.where(valid & ~more, token, T)].set(row, mode="drop")
        packed = jnp.cumsum(more, dtype=jnp.int32) - 1
        more_rows = jnp.zeros_like(row).at[
            jnp.where(more, packed, row.shape[0])].set(row, mode="drop")
        if k == 1:             # no token has a further row: an empty list
            more_rows = more_rows[:0]
    return Dispatch(
        token=token, pair=pair, valid=valid, tile_group=tile_group,
        n_live=n_live.reshape(1).astype(jnp.int32),
        past_bound=jnp.sum(sizes) - jnp.sum(valid.astype(jnp.int32)),
        first_row=first_row, more_rows=more_rows, n_more=packed[-1] + 1)


#: The dispatch's form, as the ``moe_geometry`` row names it: both row
#: movers are XLA row gathers.  ``take_rows`` gathers a tile's rows a step
#: of a loop over the plan's live tiles (a ``while`` whose trip count is
#: ``plan.n_live``); ``add_rows`` gathers every token's first row, and
#: only the further rows of tokens with several (``plan.n_more``: none at
#: one expert a token) are scatter-added, a tile's worth a step.
DISPATCH_FORM = "xla_gather_both_ways"


def _tile_rows(plan: Dispatch) -> int:
    return plan.token.shape[0] // plan.tile_group.shape[0]


def _over_tiles(n, tile_rows: int, body, init):
    """``body(first row, tile, carry)`` for ``n`` tiles in order, ``tile``
    taking a tile's slice of a (rows, ...) array."""

    def step(t, carry):
        at = t * tile_rows
        return body(at, lambda a: lax.dynamic_slice_in_dim(
            a, at, tile_rows), carry)

    return lax.fori_loop(0, n, step, init)


@jax.jit
def _take_call(x, plan: Dispatch, scale=None, y=None):
    """``take_rows``; with ``scale`` (rows,) and ``y`` (rows, d) the two
    gradients of a weighted add instead: ``(the rows of x times scale in
    y.dtype, the row sums of float32(y) times the rows of x)``."""
    rows, d = plan.token.shape[0], x.shape[1]

    def body(at, tile, carry):
        ok = tile(plan.valid)[:, None]
        took = jnp.where(ok, x[tile(plan.token)], 0)
        if y is None:
            return lax.dynamic_update_slice_in_dim(carry, took, at, 0)
        out, dots = carry
        dot = jnp.sum(jnp.where(ok, tile(y).astype(jnp.float32), 0.0)
                      * took, axis=-1)
        return (lax.dynamic_update_slice_in_dim(
                    out, (took * tile(scale)[:, None]).astype(out.dtype),
                    at, 0),
                lax.dynamic_update_slice_in_dim(dots, dot, at, 0))

    with named_scope("moe-dispatch"):
        init = jnp.zeros((rows, d), x.dtype) if y is None else (
            jnp.zeros((rows, d), y.dtype), jnp.zeros((rows,), jnp.float32))
        return _over_tiles(
            jnp.minimum(plan.n_live[0], plan.tile_group.shape[0]),
            _tile_rows(plan), body, init)


@jax.jit
def _add_call(rows, plan: Dispatch, scale=None):
    """``add_rows``, each row times ``scale`` (rows,) where given."""
    n_rows, tile_rows = rows.shape[0], _tile_rows(plan)

    def weighed(at):
        took = rows[at].astype(jnp.float32)
        return took if scale is None else took * scale[at][:, None]

    def body(at, tile, out):
        more = tile(plan.more_rows)
        listed = (at + jnp.arange(tile_rows) < plan.n_more)[:, None]
        return out.at[plan.token[more]].add(
            jnp.where(listed, weighed(more), 0.0))

    with named_scope("moe-dispatch"):
        has = plan.first_row < n_rows
        first = jnp.where(has[:, None],
                          weighed(jnp.where(has, plan.first_row, 0)), 0.0)
        first = jnp.where(plan.past_bound > 0, jnp.nan, first)
        if not plan.more_rows.shape[0]:
            return first
        return _over_tiles(-(-plan.n_more // tile_rows), tile_rows, body,
                           first)


def take_rows(x, plan: Dispatch):
    """(rows, d) in ``x.dtype``: a live tile's rows are their tokens' rows
    of ``x`` (T, d), zeros where a row holds no pair; the rows of dead
    tiles are left unvisited, whatever they hold (the contract
    ``grouped_matmul`` gives for its own output: read live rows only)."""
    return _take_call(x, plan)


def add_rows(rows, plan: Dispatch, n_tokens: int):
    """(n_tokens, d) float32: every row of a live tile that holds a pair
    added into its token's row, its first choice's row first and the
    others in row order (their experts' order); the rows of dead tiles
    are never read.  NaN throughout where a held pair found no row
    (``plan.past_bound``)."""
    assert n_tokens == plan.first_row.shape[0]
    return _add_call(rows, plan)


@jax.custom_vjp
def gather_rows(x, plan: Dispatch):
    """The held pairs' token rows, (rows, d); zeros where no pair is.
    :func:`take_rows`, its transpose :func:`add_rows`."""
    return take_rows(x, plan)


def _gather_rows_fwd(x, plan):
    # an empty array carries what the transpose needs of x: its dtype
    return take_rows(x, plan), (plan, jnp.zeros((0,), x.dtype))


def _gather_rows_bwd(saved, drows):
    plan, like_x = saved
    with named_scope("moe-dispatch"):
        return _add_call(drows, plan).astype(like_x.dtype), None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _pair_weights(weight, plan: Dispatch):
    with named_scope("moe-dispatch"):
        return jnp.where(plan.valid, weight.reshape(-1)[plan.pair], 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(y, weight, plan: Dispatch, n_tokens: int):
    """Add every row of ``y`` (rows, d) into its token's row, times the
    pair's router weight (``weight``: (n_tokens, top_k) float32):
    (n_tokens, d) float32.  NaN throughout where a held pair found no row
    (``plan.past_bound``).  :func:`add_rows` of ``float32(y) * w``; its
    transpose is :func:`take_rows` of the cotangent times ``w``, and the
    weight's gradient the row sums of ``float32(y)`` times those rows."""
    assert n_tokens == plan.first_row.shape[0]
    return _add_call(y, plan, _pair_weights(weight, plan))


def _combine_fwd(y, weight, plan, n_tokens):
    return combine(y, weight, plan, n_tokens), (y, weight, plan)


def _combine_bwd(n_tokens, saved, dout):
    del n_tokens
    y, weight, plan = saved
    dy, dots = _take_call(dout, plan, _pair_weights(weight, plan), y)
    with named_scope("moe-dispatch"):
        # a pair has one row at most: the rows of dead tiles read zero
        dweight = jnp.zeros((weight.size,), jnp.float32).at[plan.pair].add(
            dots).reshape(weight.shape).astype(weight.dtype)
    return dy, dweight, None


combine.defvjp(_combine_fwd, _combine_bwd)


def load_stats(chosen, n_experts: int, held: Tuple[int, int],
               tile_rows: int = TILE_ROWS, n_group: int = 0) -> dict:
    """What one batch's routing did, from the chosen experts on the host
    (``chosen``: (T, top_k) integers): pairs in all and on the held
    experts, the largest held expert's load over the mean load of an
    expert, the tiles the held rows take, and the pairs past the buffer's
    bound; with ``n_group`` router groups also ``held_group_token_share``,
    the share of tokens that chose an expert of a group the held experts
    lie in (such a token kept that group: only then can it reach them)."""
    chosen = np.asarray(chosen)
    first, count = held
    groups = {}
    if n_group:
        size = n_experts // n_group
        group = chosen // size
        near = (group >= first // size) & (
            group <= (first + count - 1) // size)
        groups["held_group_token_share"] = float(near.any(axis=-1).mean())
    pairs = chosen.size
    load = np.bincount(chosen.reshape(-1), minlength=n_experts)
    mine = load[first:first + count]
    tiles = np.maximum(1, -(-mine // tile_rows))
    n_tiles = buffer_tiles(rows_bound(pairs, count, n_experts), count,
                           tile_rows)
    room = np.clip(n_tiles - (np.cumsum(tiles) - tiles), 0, None) * tile_rows
    live = int(min(tiles.sum(), n_tiles))
    return {
        "pairs": int(pairs), "held_pairs": int(mine.sum()),
        "held_pairs_expected": pairs * count / n_experts,
        "max_load_over_mean": float(mine.max() / (pairs / n_experts)),
        "live_tiles": live, "buffer_tiles": int(n_tiles),
        "live_rows_share": live / n_tiles,
        "pairs_past_bound": int(np.maximum(mine - room, 0).sum()),
        **groups,
    }
