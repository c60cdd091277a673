"""Dropless top-k-of-many expert routing for one expert-parallel rank.

Two routers, one dispatch: :func:`route` (sigmoid scores of one matrix,
top-k, weights normalised over the chosen) and :func:`route_mlp_softmax`
(a small MLP over a state handed from layer to layer, a softmax, the one
most probable expert weighed by its probability).  Both are float32 at
``highest`` throughout and return ``(chosen, weight)`` alike; everything
after them is shared.  Neither's bias gets a gradient; :func:`rebalance`
is one step of the controller that moves it by the load instead.

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
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.ad_checkpoint import checkpoint_name

from chainermn_tpu.ops.grouped_matmul import SAVED_PRODUCTS, TILE_ROWS

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


def route(h, w_router, bias, *, top_k: int, scaling: float = 1.0):
    """Sigmoid top-k router (an ``ExpertsSpec`` whose ``router`` is
    ``"sigmoid"``; ``"mlp_softmax"`` is :func:`route_mlp_softmax`),
    float32 throughout.

    ``h``: (T, d); ``w_router``: (d, E); ``bias``: (E,), the per-expert
    correction added to the scores for the CHOICE only (it gets no
    gradient, and the weights do not see it).  Returns ``(chosen, weight)``,
    (T, top_k) int32 and float32: the ``top_k`` experts with the largest
    ``sigmoid(h w) + bias`` (ties to the lower index) and ``scaling *
    s[chosen]`` over the chosen scores' sum."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(f32), w_router.astype(f32),
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + lax.stop_gradient(bias.astype(f32)),
                          top_k)
    chosen = checkpoint_name(chosen.astype(jnp.int32), ROUTER_CHOICE)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, weight * scaling


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


def buffer_tiles(rows: int, count: int, tile_rows: int = TILE_ROWS) -> int:
    """Tiles of a buffer for ``rows`` rows however they fall into
    ``count`` groups: a group leaves under one tile of zeros behind its
    last row, and an empty group holds one tile."""
    return -(-rows // tile_rows) + count


def dispatch(chosen, held: Tuple[int, int], rows: int,
             tile_rows: int = TILE_ROWS) -> Dispatch:
    """Sort the (token, choice) pairs by expert and lay those of the held
    experts out in the tiles of a buffer for ``rows`` rows, each group
    from a tile's first row."""
    first, count = held
    T, k = chosen.shape
    pairs = T * k
    n_tiles = buffer_tiles(rows, count, tile_rows)
    expert = chosen.reshape(pairs) - first
    local = jnp.where((expert >= 0) & (expert < count), expert, count)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
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
    pair = jnp.where(
        valid, order[jnp.clip(pair_start[group] + within, 0, pairs - 1)], 0)
    return Dispatch(
        token=pair // k, pair=pair, valid=valid, tile_group=tile_group,
        n_live=n_live.reshape(1).astype(jnp.int32),
        past_bound=jnp.sum(sizes) - jnp.sum(valid.astype(jnp.int32)))


def gather_rows(x, plan: Dispatch):
    """The held pairs' token rows, (rows, d); zeros where no pair is."""
    return jnp.where(plan.valid[:, None], x[plan.token], 0)


def combine(y, weight, plan: Dispatch, n_tokens: int):
    """Add every row of ``y`` (rows, d) into its token's row, times the
    pair's router weight: (n_tokens, d) float32.  NaN throughout where a
    held pair found no row (``plan.past_bound``)."""
    # The rows of dead tiles are whatever memory held: selected away
    # before the product, so that neither side's gradient sees them.
    w = jnp.where(plan.valid, weight.reshape(-1)[plan.pair], 0.0)
    rows = jnp.where(plan.valid[:, None], y.astype(jnp.float32), 0.0
                     ) * w[:, None]
    out = jnp.zeros((n_tokens, y.shape[-1]), jnp.float32).at[
        plan.token].add(rows)
    return jnp.where(plan.past_bound > 0, jnp.nan, out)


def load_stats(chosen, n_experts: int, held: Tuple[int, int],
               tile_rows: int = TILE_ROWS) -> dict:
    """What one batch's routing did, from the chosen experts on the host
    (``chosen``: (T, top_k) integers): pairs in all and on the held
    experts, the largest held expert's load over the mean load of an
    expert, the tiles the held rows take, and the pairs past the buffer's
    bound."""
    chosen = np.asarray(chosen)
    first, count = held
    pairs = chosen.size
    load = np.bincount(chosen.reshape(-1), minlength=n_experts)
    mine = load[first:first + count]
    tiles = np.maximum(1, -(-mine // tile_rows))
    n_tiles = buffer_tiles(rows_bound(pairs, count, n_experts), count,
                           tile_rows)
    room = np.clip(n_tiles - (np.cumsum(tiles) - tiles), 0, None) * tile_rows
    return {
        "pairs": int(pairs), "held_pairs": int(mine.sum()),
        "held_pairs_expected": pairs * count / n_experts,
        "max_load_over_mean": float(mine.max() / (pairs / n_experts)),
        "live_tiles": int(min(tiles.sum(), n_tiles)),
        "buffer_tiles": int(n_tiles),
        "pairs_past_bound": int(np.maximum(mine - room, 0).sum()),
    }
