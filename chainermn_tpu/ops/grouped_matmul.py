"""Grouped matmul over ragged row groups: the held experts' products of a
dropless expert layer (``parallel.moe_dropless``).

``x`` is a buffer of ``n_tiles`` row tiles of ``tile_rows`` rows; every
tile belongs to ONE group (``tile_group``), a group's rows fill its tiles
from the front and the rest of a tile is zeros, and only the first
``n_live`` tiles hold anything.  ``y[tile t] = x[tile t] @ w[tile_group[t]]``.
Which rows exist is data (how the router chose); the shapes are not.  The
dispatch aligns groups to tiles so that no tile straddles two experts:
the three products then need no masks —

* forward  ``y  = x  @ w[g]``          one tile a grid step,
* ``dx = dy @ w[g]^T``                 the same kernel, the weights' block
                                      contracted over its other axis,
* ``dw[g] = sum_{t in g} x_t^T dy_t``  the row tiles innermost, a group's
                                      tiles adding into its block while it
                                      stays in VMEM

— and a dead tile (``t >= n_live``) costs a grid step and nothing else:
its blocks' indices are those of the step before, so nothing is fetched,
and its rows of ``y`` are NOT written (the caller reads live rows only).
Work is done on live tiles alone: with 8 of 128 experts held, a sixteenth
of the (token, choice) pairs.

Every group has at least one tile (an expert nobody chose has a tile of
zeros), so that ``dw`` visits, and zeroes, every group's block.

The weights are float32 parameters: they are rounded to ``x.dtype`` once a
call outside the kernel, and ``dw`` comes back float32 from the float32
sums, never rounded to the compute type on its way to the optimizer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability.spans import named_scope
from chainermn_tpu.ops.flash_attention import default_interpret

#: Rows a tile: a multiple of the matrix unit's 128 large enough that a
#: weight block (some MB) is reused over many rows, small enough that the
#: zeros behind a group's last row (under one tile a group) stay a small
#: share of a layer's few thousand held rows.
TILE_ROWS = 256

#: A weights' block may take this much VMEM (and is held twice: the
#: pipeline fetches the next group's while this one's is in use).  A whole
#: expert matrix of the cell (2688 x 1856: 10 MB in bfloat16, 20 MB as the
#: float32 sum of its gradient) fits, and then it is fetched once a GROUP
#: and not once a tile: the weights stay, the rows stream.
_BLOCK_BYTES = 24 * 1024 * 1024
_VMEM_LIMIT = 100 * 1024 * 1024


def feature_block(n: int, cap: int) -> int:
    """The block of a feature axis of ``n``: its largest divisor that is a
    multiple of 128 and at most ``cap``; the whole axis where it is no
    multiple of 128 or fits under the cap."""
    if n <= cap or n % 128:
        return n
    return max(b for b in range(128, max(cap, 128) + 1, 128) if n % b == 0)


def weight_blocks(K: int, N: int, itemsize: int):
    """``(tk, tn)``: the whole (K, N) matrix where it fits the block's
    budget, else ``K`` cut first (a cut of ``N`` repeats the rows'
    traffic, a cut of ``K`` only revisits the accumulator)."""
    budget = _BLOCK_BYTES // itemsize
    tn = feature_block(N, max(128, budget // 128))
    return feature_block(K, max(128, budget // tn)), tn


def _live(t, n_live_ref):
    return t < n_live_ref[0]


def _gmm_kernel(tile_group_ref, n_live_ref, x_ref, w_ref, y_ref, acc_ref, *,
                transpose_w):
    del tile_group_ref
    t, k = pl.program_id(0), pl.program_id(2)

    @pl.when(_live(t, n_live_ref))
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1 if transpose_w else 0,)), ((), ()))
        acc_ref[...] += lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(2) - 1)
        def _():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transpose_w", "interpret"))
def _gmm_call(x, w, tile_group, n_live, *, transpose_w, interpret):
    """``y[tile] = x[tile] @ w[g]`` (``w``: (G, K, N)), or ``@ w[g]^T``
    (``w``: (G, N, K)) with ``transpose_w``."""
    M, K = x.shape
    N = w.shape[1] if transpose_w else w.shape[2]
    n_tiles = tile_group.shape[0]
    tm, (tk, tn) = M // n_tiles, weight_blocks(K, N, x.dtype.itemsize)
    grid = (n_tiles, N // tn, K // tk)
    last_j, last_k = grid[1] - 1, grid[2] - 1

    def held(t, j, k, n_live_ref):
        """A dead tile's step reads and writes where the step before it
        did: the last live tile's last blocks."""
        live = _live(t, n_live_ref)
        return (jnp.where(live, t, n_live_ref[0] - 1),
                jnp.where(live, j, last_j), jnp.where(live, k, last_k))

    def x_map(t, j, k, tile_group_ref, n_live_ref):
        t, _, k = held(t, j, k, n_live_ref)
        return t, k

    def w_map(t, j, k, tile_group_ref, n_live_ref):
        t, j, k = held(t, j, k, n_live_ref)
        return (tile_group_ref[t], j, k) if transpose_w else (
            tile_group_ref[t], k, j)

    def y_map(t, j, k, tile_group_ref, n_live_ref):
        t, j, _ = held(t, j, k, n_live_ref)
        return t, j

    with named_scope("moe-experts"):
        return pl.pallas_call(
            functools.partial(_gmm_kernel, transpose_w=transpose_w),
            out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=grid,
                in_specs=[
                    pl.BlockSpec((tm, tk), x_map),
                    pl.BlockSpec((None, tn, tk) if transpose_w
                                 else (None, tk, tn), w_map)],
                out_specs=pl.BlockSpec((tm, tn), y_map),
                scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=_VMEM_LIMIT),
            cost_estimate=pl.CostEstimate(
                flops=2 * M * K * N, transcendentals=0,
                bytes_accessed=(x.size + M * N) * x.dtype.itemsize
                + n_tiles * K * N * w.dtype.itemsize),
            interpret=interpret, name="moe-gmm",
        )(tile_group, n_live, x, w)


def _dw_kernel(tile_group_ref, n_live_ref, x_ref, dy_ref, dw_ref):
    t = pl.program_id(2)
    before = tile_group_ref[jnp.maximum(t, 1) - 1]

    @pl.when((t == 0) | (tile_group_ref[t] != before))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(_live(t, n_live_ref))
    def _():
        dw_ref[...] += lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_groups", "interpret"))
def _dw_call(x, dy, tile_group, n_live, *, n_groups, interpret):
    """``dw[g] = sum over g's tiles of x_tile^T dy_tile``, float32."""
    (M, K), N = x.shape, dy.shape[1]
    n_tiles = tile_group.shape[0]
    tm = M // n_tiles
    tk, tn = weight_blocks(K, N, 4)      # summed where it is written

    def row(t, n_live_ref):
        return jnp.minimum(t, n_live_ref[0] - 1)

    with named_scope("moe-experts"):
        return pl.pallas_call(
            _dw_kernel,
            out_shape=jax.ShapeDtypeStruct((n_groups, K, N), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(K // tk, N // tn, n_tiles),
                in_specs=[
                    pl.BlockSpec((tm, tk), lambda k, j, t, g, n: (
                        row(t, n), k)),
                    pl.BlockSpec((tm, tn), lambda k, j, t, g, n: (
                        row(t, n), j))],
                out_specs=pl.BlockSpec(
                    (None, tk, tn), lambda k, j, t, g, n: (g[t], k, j))),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=_VMEM_LIMIT),
            cost_estimate=pl.CostEstimate(
                flops=2 * M * K * N, transcendentals=0,
                bytes_accessed=(x.size * (N // tn) + dy.size * (K // tk))
                * x.dtype.itemsize + n_groups * K * N * 4),
            interpret=interpret, name="moe-gmm-dw",
        )(tile_group, n_live, x, dy)


def _rounded(w, dtype):
    with named_scope("moe-experts"):
        return w.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, n_live, transpose_w=False):
    """``y[tile t] = x[tile t] @ w[tile_group[t]]`` for the first
    ``n_live[0]`` tiles; with ``transpose_w``, ``@ w[tile_group[t]]^T``.

    ``x``: (n_tiles * tile_rows, K), the compute type, zeros wherever a
    row holds no pair; ``w``: (G, K, N) float32 (or the compute type), or
    (G, N, K) with ``transpose_w`` — a stack whose last axis is a multiple
    of 128 keeps the layout the kernels read it in, where the compiler
    would turn a (.., 2688, 1856) stack around for its own fusions and
    copy it back for every call; ``tile_group``: (n_tiles,) int32,
    non-decreasing, every group in ``[0, G)`` present — a dead tile
    carries the last group's number; ``n_live``: (1,) int32, at least 1.
    Returns (n_tiles * tile_rows, N) in ``x.dtype``; the rows of dead
    tiles are left as they were in memory, whatever that was: read live
    rows only."""
    return _gmm_call(x, _rounded(w, x.dtype), tile_group, n_live,
                     transpose_w=transpose_w, interpret=default_interpret())


def _grouped_matmul_fwd(x, w, tile_group, n_live, transpose_w):
    return grouped_matmul(x, w, tile_group, n_live, transpose_w), (
        x, w, tile_group, n_live)


def _grouped_matmul_bwd(transpose_w, saved, dy):
    x, w, tile_group, n_live = saved
    interpret = default_interpret()
    dx = _gmm_call(dy, _rounded(w, x.dtype), tile_group, n_live,
                   transpose_w=not transpose_w, interpret=interpret)
    rows, cols = (dy, x) if transpose_w else (x, dy)
    dw = _dw_call(rows, cols, tile_group, n_live, n_groups=w.shape[0],
                  interpret=interpret)
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


#: The name (``jax.ad_checkpoint.checkpoint_name``) of the two grouped
#: products' results.  The grouped kernels are the one part of a step
#: whose time follows the routing (0.1 ms a live tile for eight products,
#: at the matrix unit's rate); a rematerialised layer that saves these
#: two runs six, and its step time moves a quarter less with the load.
SAVED_PRODUCTS = "moe-grouped-products"


def grouped_relu2_mlp(rows, w_up, w_down, tile_group, n_live):
    """``relu(rows w_up[g]^T)^2 w_down[g]``, each tile by its group's two
    matrices, both (G, d_expert, d_model): an expert FFN of the
    squared-ReLU kind over the held experts' rows, two grouped matmuls."""
    hidden = checkpoint_name(
        grouped_matmul(rows, w_up, tile_group, n_live, True), SAVED_PRODUCTS)
    with named_scope("moe-experts"):
        hidden = jnp.square(jax.nn.relu(hidden))
    return checkpoint_name(
        grouped_matmul(hidden, w_down, tile_group, n_live), SAVED_PRODUCTS)


def grouped_swiglu_mlp(rows, w_gate, w_up, w_down, tile_group, n_live):
    """``(silu(rows w_gate[g]^T) * (rows w_up[g]^T)) w_down[g]``, each
    tile by its group's three matrices, all (G, d_expert, d_model): an
    expert FFN of the gated kind over the held experts' rows, three
    grouped matmuls and the gate's product between them."""
    gate = checkpoint_name(
        grouped_matmul(rows, w_gate, tile_group, n_live, True),
        SAVED_PRODUCTS)
    up = checkpoint_name(
        grouped_matmul(rows, w_up, tile_group, n_live, True), SAVED_PRODUCTS)
    with named_scope("moe-experts"):
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(rows.dtype)
    return checkpoint_name(
        grouped_matmul(hidden, w_down, tile_group, n_live), SAVED_PRODUCTS)
