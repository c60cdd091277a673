"""The Mamba-2 state-space recurrence in its chunked dual form (SSD), with
a backward pass, and the causal depthwise convolution in front of it.

The recurrence, a head with channels ``x_t`` in R^P, a state ``H_t`` in
R^(P x N), a step ``dt_t > 0`` and a decay rate ``A < 0``::

    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T        y_t = H_t C_t

is linear in the state, so a block of ``Q`` tokens can be done at once
(arXiv:2405.21060, section 6).  With ``cs_t`` the running sum of
``dt A`` inside the block::

    y_t = sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s      within the block
        + exp(cs_t) H_prev C_t                                  from the blocks before
    H_next = exp(cs_Q) H_prev + sum_s exp(cs_Q - cs_s) dt_s x_s B_s^T

— three matrix products a block (``C B^T``, the masked product with
``dt x``, the two state products) for the MXU, and one state handed from
block to block.  Decays, ``dt``, the running sums and the state are
float32; the matrix products take their operands in the activations'
type and accumulate in float32.

Each pass is ONE Mosaic kernel (``ssd-fwd``, ``ssd-bwd``) whose grid
walks (batch row, block, group, run of heads) in order: the state of
every head (in backward its cotangent) is carried from block to block in
VMEM, and a block's decay matrix, ``C B^T`` and masked weights are made
and used there (the running sums are made beside the call: a product with
the triangle, an array of ``dt``'s size).  The forward writes ``y`` and
the state each block started from (``S/Q`` states of ``H x P x N`` floats,
kept only for a backward pass); the backward walks the blocks in reverse,
rebuilds each block's decay from the sums and writes ``dx``, ``ddt``,
``dB``, ``dC`` a block and ``dD`` summed.  The kernels hold the tokens on
the lanes — the layout the convolution's kernels hand their result in and
the compiler keeps a mixer's activations in (d_head 64 is half a register
of lanes) — so a head is a run of 64 sublanes, sliced at no cost, and no
transposing copy stands between the two.  A block's ``Q x Q`` matrices
are worked in tiles of 128: only a tile on the diagonal takes an
exponential an entry (:func:`_tiles`).  :func:`ssd_tiles` is the one rule
for the heads a grid step holds, from the operands' shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability import reporter as _reporter
from chainermn_tpu.observability import step_log as _step_log
from chainermn_tpu.observability.spans import named_scope, telemetry_active
from chainermn_tpu.ops.flash_attention import default_interpret


#: The convolution's two kernels work with the SEQUENCE on the lanes,
#: operands (B, C, S): the layout the compiler gives a mixer's
#: activations anyway when the head size is under a register's 128 lanes
#: (d_head 64: the scan's operands are sequence-minor, and so is
#: ``in_proj``'s output), so the transposes around a kernel are
#: relabelings.  With the channels on the lanes the backward kernel is
#: faster alone (0.75 ms a call against 1.05 at (2, 8192, 4352)) and costs
#: the step seven transposing copies a layer around it (PERF.md §6, PR 29).
#:
#: Tokens of the neighbouring sequence tiles a grid step reads beside its
#: own: one register of lanes, so a filter may have up to 129 taps.
_CONV_HALO = 128
#: Tokens a grid step holds, at most: a whole sequence of the hybrid cell,
#: so no halo is read there.  Channel rows a grid step holds.  At 8192 x
#: 64, two operands and the result, double-buffered, are 6.3 MB of VMEM in
#: bfloat16 and 12.6 in float32, with 1.1 MB of scratch: inside the 16 MiB
#: a kernel gets by default.
_CONV_SEQ_TILE = 8192
_CONV_CHANNELS = 64
#: What a kernel's body handles at once: 16 channel rows (one bfloat16
#: register of sublanes) by 512 tokens, eight float32 registers a value.
_CONV_ROWS = 16
_CONV_RUN = 512


def conv_tiles(S: int, C: int):
    """``(tokens, channels)`` of a grid step of the convolution's
    kernels: the sequence in the fewest equal tiles of at most
    :data:`_CONV_SEQ_TILE` tokens, each a whole number of runs (the
    sequence is padded up to them); :data:`_CONV_CHANNELS` channel rows,
    the last block ragged where they do not divide ``C`` (a channel never
    reads another), or all of them when there are fewer (padded up to
    whole runs of :data:`_CONV_ROWS`)."""
    n = -(-S // _CONV_SEQ_TILE)
    run = _CONV_RUN if S > _CONV_RUN else _CONV_HALO
    ts = -(-S // (n * run)) * run
    return ts, min(_CONV_CHANNELS, -(-C // _CONV_ROWS) * _CONV_ROWS)


def _conv_rows(body, x_ref):
    """``body(rs)`` for every run ``rs`` of :data:`_CONV_ROWS` channel
    rows of a block."""
    G = _CONV_ROWS

    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * G, G), G))
        return carry

    lax.fori_loop(0, x_ref.shape[1] // G, step, 0)


def _conv_pre(held, weights, bias):
    """``(acc, taps)`` of the tokens ``held`` holds after its first
    :data:`_CONV_HALO`: a tap is a rotation of the lanes, the halo
    supplying the tokens before.  Summed in the forward's order."""
    K, H = len(weights), _CONV_HALO
    taps = [pltpu.roll(held, K - 1 - j, axis=1)[:, H:]
            for j in range(K - 1)] + [held[:, H:]]
    acc = bias
    for tap, weight in zip(taps, weights):
        acc = acc + tap * weight
    return acc, taps


def _conv_fwd_kernel(before_ref, x_ref, k_ref, b_ref, y_ref, xs, *, K, ts,
                     run):
    """One (channel block, batch row, sequence tile) of the forward:
    ``xs`` holds a run of rows in float32 behind the last
    :data:`_CONV_HALO` tokens of the tile before (zeros before the
    sequence starts)."""
    f32, H = jnp.float32, _CONV_HALO
    i = pl.program_id(2)

    def rows(rs):
        xs[:, 0:H] = jnp.where(i > 0, before_ref[0, rs, :].astype(f32), 0.0)
        xs[:, H:H + ts] = x_ref[0, rs, :].astype(f32)
        weights = [k_ref[rs, j:j + 1] for j in range(K)]
        for r in range(0, ts, run):
            acc, _ = _conv_pre(xs[:, r:r + H + run], weights, b_ref[rs, :])
            y_ref[0, rs, r:r + run] = jax.nn.silu(acc).astype(y_ref.dtype)

    _conv_rows(rows, x_ref)


def _conv_bwd_kernel(before_ref, x_ref, after_ref, dy_ref, dy_after_ref,
                     k_ref, b_ref, dx_ref, dk_ref, db_ref, xs, dpre_s, *,
                     K, ts, run):
    """One (channel block, batch row, sequence tile) of the backward.

    ``xs`` holds a run of rows in float32 between the last
    :data:`_CONV_HALO` tokens of the tile before and the first of the
    tile after; ``dpre_s`` their ``dpre`` and the halo's after it,
    float32, which never leaves the chip.  Two passes over the runs of
    tokens: ``dpre`` with the partial sums of ``dkernel`` and ``dbias``
    (folded to one register of lanes a row); then ``dx``, the
    anti-causal filter over ``dpre``.  The sums accumulate in the output
    block over the batch and sequence axes of the grid."""
    f32, H = jnp.float32, _CONV_HALO
    row, i, n = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    first = jnp.logical_and(row == 0, i == 0)

    def rows(rs):
        # zeros before the sequence starts; past its end ``dpre`` is zero
        xs[:, 0:H] = jnp.where(i > 0, before_ref[0, rs, :].astype(f32), 0.0)
        xs[:, H:H + ts] = x_ref[0, rs, :].astype(f32)
        xs[:, H + ts:] = after_ref[0, rs, :].astype(f32)
        weights = [k_ref[rs, j:j + 1] for j in range(K)]

        def dpre_of(at, width, dy):
            acc, taps = _conv_pre(xs[:, at:at + H + width], weights,
                                  b_ref[rs, :])
            sig = jax.nn.sigmoid(acc)
            return dy.astype(f32) * sig * (1.0 + acc * (1.0 - sig)), taps

        def fold(v):        # (rows, width) -> (rows, H): registers added
            return sum(v[:, at:at + H] for at in range(0, v.shape[1], H))

        sums = [jnp.zeros((_CONV_ROWS, H), f32)] * (K + 1)
        for r in range(0, ts, run):
            dpre, taps = dpre_of(r, run, dy_ref[0, rs, r:r + run])
            dpre_s[:, r:r + run] = dpre
            sums = [s + fold(part) for s, part in zip(
                sums, [dpre * tap for tap in taps] + [dpre])]
        after, _ = dpre_of(ts, H, dy_after_ref[0, rs, :])
        dpre_s[:, ts:] = jnp.where(i < n - 1, after, 0.0)
        for r in range(0, ts, run):
            held = dpre_s[:, r:r + run + H]
            dx = held[:, :run] * weights[K - 1]
            for j in range(K - 1):
                ahead = pltpu.roll(held, run + H - (K - 1 - j), axis=1)
                dx = dx + ahead[:, :run] * weights[j]
            dx_ref[0, rs, r:r + run] = dx.astype(dx_ref.dtype)
        sums = [s.sum(axis=1, keepdims=True) for s in sums]

        @pl.when(first)
        def _():
            for j in range(K):
                dk_ref[rs, j:j + 1] = sums[j]
            db_ref[rs, :] = sums[K]

        @pl.when(jnp.logical_not(first))
        def _():
            for j in range(K):
                dk_ref[rs, j:j + 1] += sums[j]
            db_ref[rs, :] += sums[K]

    _conv_rows(rows, x_ref)


def _conv_layout(kernel, bias, *activations):
    """What both kernels are called with: the activations sequence-minor
    and padded with zeros up to whole tiles (zero cotangents: no ``dpre``
    past the end, none in the rows), the filter a column a tap and the
    bias a column; the grid; the block specs of a tile, of the halo
    before and after it, and of ``width`` columns a channel."""
    B, S, C = activations[0].shape
    H = _CONV_HALO
    ts, tc = conv_tiles(S, C)
    n, rows = -(-S // ts), max(C, tc)
    acts = [jnp.swapaxes(a, 1, 2) for a in activations]
    params = [kernel.astype(jnp.float32).T,
              bias.astype(jnp.float32).reshape(C, 1)]
    if (rows, n * ts) != (C, S):
        acts = [jnp.pad(a, ((0, 0), (0, rows - C), (0, n * ts - S)))
                for a in acts]
        params = [jnp.pad(p, ((0, rows - C), (0, 0))) for p in params]
    per, last = ts // H, n * ts // H - 1
    tile = pl.BlockSpec((1, tc, ts), lambda c, b, i: (b, c, i))
    before = pl.BlockSpec(
        (1, tc, H), lambda c, b, i: (b, c, jnp.maximum(i * per - 1, 0)))
    after = pl.BlockSpec(
        (1, tc, H), lambda c, b, i: (b, c, jnp.minimum((i + 1) * per, last)))

    def per_channel(width):
        return pl.BlockSpec((tc, width), lambda c, b, i: (c, 0))

    return (acts + params, (pl.cdiv(rows, tc), B, n), ts,
            tile, before, after, per_channel)


#: The wrappers are jitted in their own right, as the flash kernels'
#: are: the layers of a model share one lowering of each.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_silu_fwd_call(x, kernel, bias, *, interpret):
    (_, S, C), K = x.shape, kernel.shape[0]
    with named_scope("ssm-conv"):
        (xt, kt, bt), grid, ts, tile, before, _, per_channel = _conv_layout(
            kernel, bias, x)
        yt = pl.pallas_call(
            functools.partial(_conv_fwd_kernel, K=K, ts=ts,
                              run=min(_CONV_RUN, ts)),
            out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
            grid=grid,
            in_specs=[before, tile, per_channel(K), per_channel(1)],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((_CONV_ROWS, ts + _CONV_HALO), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            cost_estimate=pl.CostEstimate(
                flops=(2 * K + 4) * xt.size, transcendentals=xt.size,
                bytes_accessed=2 * xt.size * x.dtype.itemsize),
            interpret=interpret, name="ssm-conv-fwd",
        )(xt, xt, kt, bt)
        return jnp.swapaxes(yt[:, :C, :S], 1, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_silu_bwd_call(x, kernel, bias, dy, *, interpret):
    (_, S, C), K, H = x.shape, kernel.shape[0], _CONV_HALO
    with named_scope("ssm-conv"):
        ((xt, dyt, kt, bt), grid, ts, tile, before, after,
         per_channel) = _conv_layout(kernel, bias, x, dy)
        rows = kt.shape[0]
        dxt, dk, db = pl.pallas_call(
            functools.partial(_conv_bwd_kernel, K=K, ts=ts,
                              run=min(_CONV_RUN, ts)),
            out_shape=[jax.ShapeDtypeStruct(xt.shape, x.dtype),
                       jax.ShapeDtypeStruct((rows, K), jnp.float32),
                       jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
            grid=grid,
            in_specs=[before, tile, after, tile, after,
                      per_channel(K), per_channel(1)],
            out_specs=[tile, per_channel(K), per_channel(1)],
            scratch_shapes=[
                pltpu.VMEM((_CONV_ROWS, ts + 2 * H), jnp.float32),
                pltpu.VMEM((_CONV_ROWS, ts + H), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "arbitrary", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=(6 * K + 12) * xt.size, transcendentals=xt.size,
                bytes_accessed=3 * xt.size * x.dtype.itemsize),
            interpret=interpret, name="ssm-conv-bwd",
        )(xt, xt, xt, dyt, dyt, kt, bt)
        return (jnp.swapaxes(dxt[:, :C, :S], 1, 2),
                dk[:C].T.astype(kernel.dtype),
                db[:C, 0].astype(bias.dtype))


@jax.custom_vjp
def _conv_silu(x, kernel, bias):
    return _conv_silu_fwd_call(x, kernel, bias,
                               interpret=default_interpret())


def _conv_silu_fwd(x, kernel, bias):
    return _conv_silu(x, kernel, bias), (x, kernel, bias)


def _conv_silu_bwd(saved, dy):
    return _conv_silu_bwd_call(*saved, dy, interpret=default_interpret())


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv_silu(x, kernel, bias=None):
    """``silu(conv(x) + bias)``: a causal depthwise convolution along the
    sequence.  ``x``: (B, S, C); ``kernel``: (K, C), tap ``K-1`` weighs
    the current token and tap ``j`` the one ``K-1-j`` back (zeros before
    the sequence starts); ``bias``: (C,), or None for a convolution that
    has none (the kernels then add a column of zeros made here: one
    pair of kernels serves both).  Sums in float32, returns ``x.dtype``.

    The backward is written, not derived: autodiff transposes the
    forward's shifted slices into pads that are added, which the compiler
    does not fuse — a float32 ``dpre`` and one copy of it a tap go
    through HBM, 2.1 GB a call at (2, 8192, 4352) where ``x``, ``dy`` and
    ``dx`` are 0.43.  With ``acc`` the pre-activation (recomputed from
    ``x``: the residuals are the three inputs)::

        dpre[s]    = dy[s] sigma(acc[s]) (1 + acc[s] (1 - sigma(acc[s])))
        dx[s]      = sum_j kernel[j] dpre[s + (K-1-j)]     zeros past the end
        dkernel[j] = sum_{b,s} dpre[s] x[s - (K-1-j)]      dbias = sum dpre

    ``dx`` is the forward's mirror image, an anti-causal filter.  One
    Pallas kernel makes all three in one pass over ``x`` and ``dy``;
    ``dpre`` is float32 and lives in VMEM only.  The forward is the same
    kernel's first half (a tap is a rotation of lanes in on-chip memory):
    one pass, the sums in the order and precision of the shifted slices
    it replaces."""
    if kernel.shape[0] - 1 > _CONV_HALO:
        raise ValueError(
            f"causal_conv_silu: {kernel.shape[0]} taps reach further back "
            f"than the {_CONV_HALO} tokens a tile sees of its neighbour")
    if telemetry_active():
        (B, S, C), (ts, tc) = x.shape, conv_tiles(*x.shape[1:])
        publish_geometry("conv_geometry", "ssm_conv", {
            "seq": S, "channels": C, "taps": kernel.shape[0],
            "seq_tile": ts, "channel_tile": tc,
            "grid_steps": B * -(-S // ts) * -(-C // tc)},
            backward="one_pass")
    if bias is None:
        bias = jnp.zeros((x.shape[-1],), jnp.float32)
    return _conv_silu(x, kernel, bias)


#: Heads a grid step of the scan's kernels holds at most: a run of heads
#: shares its group's ``B`` and ``C`` blocks and one ``C B^T``, and its
#: state products are one matmul of ``heads x d_head`` rows.
_SSD_HEADS = 16
#: One register of lanes: the side of a tile of a block's ``Q x Q`` matrices.
_SSD_TILE = 128

_HIGHEST = lax.Precision.HIGHEST


def _ssd_vmem(hb, chunk, heads, d_head, d_state, itemsize):
    """VMEM bytes of the backward kernel (the larger of the two) at
    ``hb`` heads a grid step: its blocks twice (the pipeline's two
    buffers), the carried cotangent of every head's state, its scratch,
    and what the compiler keeps of the body's values (the run's state and
    its cotangent, a ``(rows, Q)`` value and eight tiles: fitted from
    above to the limits the kernel compiles under at both cells'
    geometries, PR 31: 13.6 MiB for 13.8 counted at 16 heads of chunk
    256, 5.0 for 6.2 at 8 of chunk 128)."""
    rows, Q, N = hb * d_head, chunk, d_state
    T, nt = _tiles(Q)
    blocks = (3 * rows * Q * itemsize         # x, dy, dx
              + rows * N * 4                  # the block's starting state
              + 4 * N * Q * itemsize          # B, C, dB, dC
              + 4 * hb * Q * 4                # dt, cs, ddt, dcs
              + Q * _SSD_TILE * 4)            # cs, a column a head
    scratch = (heads * d_head * N * 4         # dH of every head
               + 2 * rows * Q * (4 + itemsize)
               + nt * (nt - 1) * rows * T * itemsize
               + 2 * N * Q * 4 + Q * Q * (8 + itemsize))
    body = 2 * rows * N * 4 + rows * Q * 4 + 8 * T * T * 4
    return 2 * blocks + scratch + body


def ssd_tiles(S, chunk, heads, groups, d_head, d_state, dtype):
    """``(heads a grid step, VMEM bytes)`` of the scan's two kernels, from
    the operands' shapes alone: the longest run of a group's heads, at
    most :data:`_SSD_HEADS`, that divides the group and whose blocks,
    scratch and one head's ``Q x Q`` values fit the scoped VMEM a kernel
    gets by default.  Raises where one head does not (a chunk or a state
    too large to hold: nothing smaller to fall back to)."""
    from chainermn_tpu.ops.flash_attention import VMEM_SCOPED_DEFAULT

    if S % chunk:
        raise ValueError(
            f"ssd_scan: the sequence length {S} is no multiple of the "
            f"chunk {chunk}")
    if heads % groups:
        raise ValueError(
            f"ssd_scan: {groups} groups do not divide {heads} heads")
    r, itemsize = heads // groups, jnp.dtype(dtype).itemsize
    for hb in range(min(r, _SSD_HEADS), 0, -1):
        vmem = _ssd_vmem(hb, chunk, heads, d_head, d_state, itemsize)
        if r % hb == 0 and vmem <= VMEM_SCOPED_DEFAULT:
            return hb, vmem
    raise ValueError(
        f"ssd_scan: one head of {d_head} x {d_state} at chunk {chunk} "
        f"needs {vmem} bytes of VMEM, over the {VMEM_SCOPED_DEFAULT} a "
        f"kernel gets")


def _keep(last, N):
    """``exp(cs_Q)``, what a block keeps of the state it started from, as
    a row of ``N`` lanes (Mosaic broadcasts a (1, 1) value along one axis
    at a time)."""
    return jnp.exp(jnp.broadcast_to(last, (1, N)))


_TN = (((0,), (0,)), ((), ()))      # contract the sublanes of both
_NT = (((1,), (1,)), ((), ()))      # contract the lanes of both


def _tiles(Q):
    """A block's ``Q x Q`` matrices as square tiles of one register of
    lanes: ``(tile, tiles a side)``.  Of the tiles only those ON the
    diagonal need a decay matrix (an exponential an entry, under the
    triangle); a tile past it is dead, and one before it is live
    everywhere, where ``exp(cs_t - cs_s) = exp(cs_t - cs_e) exp(cs_e -
    cs_s)`` with ``e`` the last token of the tile's ``s`` — two factors
    in (0, 1], a row each, that scale the product's operands instead of
    its weights."""
    T = _SSD_TILE if Q % _SSD_TILE == 0 else Q
    return T, Q // T


def _span(k, T):
    return slice(k * T, (k + 1) * T)


def _triangle(T, lower):
    """A diagonal tile's mask, [r, c]: ``r >= c`` (``lower``) or ``r <=
    c``."""
    row = lax.broadcasted_iota(jnp.int32, (T, T), 0)
    col = lax.broadcasted_iota(jnp.int32, (T, T), 1)
    return row >= col if lower else row <= col


def _row(ref, h, span=slice(None)):
    """Head ``h``'s row of a (1, 1, heads, Q) block over the tokens
    ``span``, read from the ref: a slice of the ref is aligned, a slice
    of a one-row value past the first register is not."""
    return ref[0, 0, h:h + 1, span]


def _ssd_fwd_kernel(x_ref, dt_ref, cs_ref, csN_ref, bT_ref, cT_ref, d_ref,
                    y_ref, *rest, hb, P, keep):
    """One (batch row, block, group, run of ``hb`` heads) of the forward,
    the tokens on the lanes.  ``cs_ref`` / ``csN_ref``: the block's
    running sums of ``dt A``, a row a head and a column a head (a decay
    tile needs both).  ``h_s`` carries every run's state (heads x P rows,
    N) from block to block; ``cb_s`` holds the group's ``B C^T`` for its
    runs (``cbo_s``: rounded, for the tiles before the diagonal);
    ``off_s`` the state's part of ``y`` and ``us_s`` the decayed ``dt x``
    of all the step's heads, so that the two state products are one
    matmul each."""
    starts_ref = rest[0] if keep else None
    h_s, cb_s, cbo_s, off_s, us_s = rest[-5:]
    f32, op, Q = jnp.float32, x_ref.dtype, x_ref.shape[-1]
    T, nt = _tiles(Q)
    i, j = pl.program_id(1), pl.program_id(3)
    run = pl.program_id(2) * pl.num_programs(3) + j

    @pl.when(i == 0)
    def _():
        h_s[run] = jnp.zeros(h_s.shape[1:], f32)

    @pl.when(j == 0)
    def _():                                        # [s, t] = B_s . C_t
        cb_s[...] = lax.dot_general(bT_ref[0, 0], cT_ref[0, 0], _TN,
                                    preferred_element_type=f32)
        cbo_s[...] = cb_s[...].astype(op)

    upper = _triangle(T, lower=False)
    state = h_s[run]
    if keep:
        starts_ref[0, 0, 0] = state
    off_s[...] = jnp.dot(state.astype(op), cT_ref[0, 0],
                         preferred_element_type=f32)

    for h in range(hb):
        rs = slice(h * P, (h + 1) * P)
        cs = functools.partial(_row, cs_ref, h)
        x = x_ref[0, 0, rs, :].astype(f32)                  # (P, Q)
        xdt = x * _row(dt_ref, h)
        xdt_o = xdt.astype(op)
        for k in range(nt):                                 # tokens t
            tl = _span(k, T)
            decay = jnp.exp(jnp.where(
                upper, cs(tl) - csN_ref[0, 0, tl, h:h + 1], -jnp.inf))
            weights = (cb_s[tl, tl] * decay).astype(op)     # [s, t]
            y = jnp.dot(xdt_o[:, tl], weights, preferred_element_type=f32)
            for m in range(k):                              # tokens s before
                sl, e = _span(m, T), slice((m + 1) * T - 1, (m + 1) * T)
                pre = xdt[:, sl] * jnp.exp(cs(e) - cs(sl))
                y = y + jnp.exp(cs(tl) - cs(e)) * jnp.dot(
                    pre.astype(op), cbo_s[sl, tl],
                    preferred_element_type=f32)
            y = (y + jnp.exp(cs(tl)) * off_s[rs, tl]
                 + d_ref[run * hb + h] * x[:, tl])
            y_ref[0, 0, rs, tl] = y.astype(y_ref.dtype)
        last = cs(slice(Q - 1, Q))
        us_s[rs, :] = (xdt * jnp.exp(last - cs())).astype(op)
        h_s[run, rs, :] = _keep(last, h_s.shape[2]) * state[rs]
    h_s[run] += lax.dot_general(us_s[...], bT_ref[0, 0], _NT,
                                preferred_element_type=f32)


def _ssd_bwd_kernel(x_ref, dt_ref, cs_ref, csN_ref, bT_ref, cT_ref, d_ref,
                    starts_ref, dy_ref,
                    dx_ref, ddt_ref, dcs_ref, dbT_ref, dcT_ref, dd_ref,
                    dh_s, cb_s, cbo_s, dcb_s, db_s, dc_s,
                    off_s, g_s, us_s, dyo_s, pre_s, post_s, *, hb, P):
    """One (batch row, block, group, run of heads) of the backward, the
    blocks in reverse.  ``dh_s`` carries the cotangent of every run's
    state; a head's decay tiles are rebuilt from the running sums in
    VMEM.  Written a block: ``dx``, the part of ``ddt`` that comes through
    ``dt x``, and the running sums' cotangent ``dcs`` (the caller sums it
    back into ``ddt`` and ``dA``).  ``dB`` and ``dC`` sum over a group's
    heads: ``dcb_s`` (the cotangent of ``C B^T``, [t, s]) and ``db_s`` /
    ``dc_s`` gather them over the group's runs, written with its last.
    What ``dcs`` takes from the decay matrix needs no ``Q x Q`` sum: with
    ``W`` the masked weights and ``u = dt x``, ``sum_s dW W = sum_p dy (W
    u)`` and ``sum_t dW W = sum_p u (W^T dy)``, rows over the tokens from
    one more product a head — each with the SAME rounded operands, so
    that the two cancel as the matrix's own sums do."""
    f32, op, Q = jnp.float32, x_ref.dtype, x_ref.shape[-1]
    T, nt = _tiles(Q)
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (
        pl.program_id(2) == 0) & (pl.program_id(3) == 0)
    i, j, rpg = pl.program_id(1), pl.program_id(3), pl.num_programs(3)
    run = pl.program_id(2) * rpg + j

    @pl.when(first)
    def _():
        dd_ref[...] = jnp.zeros(dd_ref.shape, f32)

    @pl.when(i == 0)
    def _():
        dh_s[run] = jnp.zeros(dh_s.shape[1:], f32)

    @pl.when(j == 0)
    def _():                                        # [t, s] = C_t . B_s
        cb_s[...] = lax.dot_general(cT_ref[0, 0], bT_ref[0, 0], _TN,
                                    preferred_element_type=f32)
        cbo_s[...] = cb_s[...].astype(op)
        dcb_s[...] = jnp.zeros(dcb_s.shape, f32)
        db_s[...] = jnp.zeros(db_s.shape, f32)
        dc_s[...] = jnp.zeros(dc_s.shape, f32)

    lower = _triangle(T, lower=True)
    state, dh = starts_ref[0, 0, 0], dh_s[run]
    off_s[...] = jnp.dot(state.astype(op), cT_ref[0, 0],
                         preferred_element_type=f32)
    g_s[...] = jnp.dot(dh.astype(op), bT_ref[0, 0],
                       preferred_element_type=f32)
    at_end = lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    pairs = [(k, m) for k in range(nt) for m in range(k)]   # t after s

    def over_p(v):
        return jnp.sum(v, axis=0, keepdims=True)

    for h in range(hb):
        rs = slice(h * P, (h + 1) * P)
        cs, dt = functools.partial(_row, cs_ref, h), _row(dt_ref, h)
        x = x_ref[0, 0, rs, :].astype(f32)                  # (P, Q)
        dy_o = dy_ref[0, 0, rs, :]
        dy = dy_o.astype(f32)
        xdt = x * dt
        xdt_o = xdt.astype(op)
        xdt_r = xdt_o.astype(f32)
        dxdt, dcs = [None] * nt, [None] * nt    # by s; by token
        for k in range(nt):
            tl = _span(k, T)
            decay = jnp.exp(jnp.where(
                lower, csN_ref[0, 0, tl, h:h + 1] - cs(tl), -jnp.inf))
            weights = (cb_s[tl, tl] * decay).astype(op)     # [t, s]
            dxdt[k] = jnp.dot(dy_o[:, tl], weights,
                              preferred_element_type=f32)
            wu = lax.dot_general(xdt_o[:, tl], weights, _NT,
                                 preferred_element_type=f32)
            dcs[k] = over_p(dy[:, tl] * wu - xdt_r[:, tl] * dxdt[k])
            dcb_s[tl, tl] += decay * lax.dot_general(
                dy_o[:, tl], xdt_o[:, tl], _TN, preferred_element_type=f32)
        for n, (k, m) in enumerate(pairs):
            tl, sl = _span(k, T), _span(m, T)
            e = slice((m + 1) * T - 1, (m + 1) * T)
            before = jnp.exp(cs(e) - cs(sl))                # over s
            after = jnp.exp(cs(tl) - cs(e))                 # over t
            pre = (xdt[:, sl] * before).astype(op)
            post = (dy[:, tl] * after).astype(op)
            pre_s[n, rs, :], post_s[n, rs, :] = pre, post
            back = jnp.dot(post, cbo_s[tl, sl], preferred_element_type=f32)
            fore = lax.dot_general(pre, cbo_s[tl, sl], _NT,
                                   preferred_element_type=f32)
            dxdt[m] = dxdt[m] + before * back
            dcs[k] = dcs[k] + over_p(post.astype(f32) * fore)
            dcs[m] = dcs[m] - over_p(pre.astype(f32) * back)
        dxdt = dxdt[0] if nt == 1 else jnp.concatenate(dxdt, axis=1)
        dcs = dcs[0] if nt == 1 else jnp.concatenate(dcs, axis=1)
        grow = jnp.exp(cs())
        dcs = dcs + grow * over_p(dy * off_s[rs, :])
        dyo_s[rs, :] = (dy * grow).astype(op)
        last = cs(slice(Q - 1, Q))
        to_end, g = jnp.exp(last - cs()), g_s[rs, :]
        us_s[rs, :] = (xdt * to_end).astype(op)
        dxdt = dxdt + g * to_end
        dto_end = over_p(g * xdt) * to_end
        dlast = jnp.sum(dto_end, axis=1, keepdims=True) + jnp.exp(last) * (
            over_p(jnp.sum(state[rs] * dh[rs], axis=1, keepdims=True)))
        dcs_ref[0, 0, h:h + 1, :] = (
            dcs - dto_end + jnp.where(at_end, dlast, 0.0))
        ddt_ref[0, 0, h:h + 1, :] = over_p(dxdt * x)
        dx_ref[0, 0, rs, :] = (
            dxdt * dt + d_ref[run * hb + h] * dy).astype(dx_ref.dtype)
        dd_ref[run, h:h + 1, :] += over_p(
            jnp.sum(dy * x, axis=1, keepdims=True))
        dh_s[run, rs, :] = _keep(last, dh_s.shape[2]) * dh[rs]

    dh_s[run] += lax.dot_general(dyo_s[...], cT_ref[0, 0], _NT,
                                 preferred_element_type=f32)
    dc_s[...] += lax.dot_general(state.astype(op), dyo_s[...], _TN,
                                 preferred_element_type=f32)
    db_s[...] += lax.dot_general(dh.astype(op), us_s[...], _TN,
                                 preferred_element_type=f32)
    for n, (k, m) in enumerate(pairs):      # every head's, one product
        dcb_s[_span(k, T), _span(m, T)] += lax.dot_general(
            post_s[n], pre_s[n], _TN, preferred_element_type=f32)

    @pl.when(j == rpg - 1)
    def _():
        dcb = dcb_s[...].astype(op)
        dbT_ref[0, 0] = (db_s[...] + jnp.dot(
            cT_ref[0, 0], dcb, preferred_element_type=f32)
        ).astype(dbT_ref.dtype)
        dcT_ref[0, 0] = (dc_s[...] + lax.dot_general(
            bT_ref[0, 0], dcb, _NT, preferred_element_type=f32)
        ).astype(dcT_ref.dtype)


def _block_sums(a, chunk, back=False):
    """``a`` (b, S, H) float32 summed along the tokens inside each block
    of ``chunk``: ``cs_t = sum_{s <= t} a_s``, or with ``back`` its
    transpose ``sum_{t >= s} a_t`` (the running sums' cotangent) — a
    product with the triangle at full precision (a ``cumsum`` lowers to
    reduce-windows that lose their scope and cost more)."""
    b, S, H = a.shape
    live = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))  # [t, s]: s <= t
    return jnp.einsum(
        "bnth,ts->bnsh" if back else "bnsh,ts->bnth",
        a.reshape(b, S // chunk, chunk, H), live,
        precision=_HIGHEST).reshape(b, S, H)


def _ssd_layout(x, dt, B, C, A, D, chunk):
    """What both kernels are called with — the tokens on the lanes, a run
    of ``hb`` heads a block: ``x`` (b, R, hb P, S); ``dt`` and the running
    sums of ``dt A`` (b, R, hb, S), the sums the other way round too (b,
    R, S, hb); ``B``, ``C`` (b, G, N, S); ``D`` (H,) scalars — with the
    grid, the block specs' maker and the compiler's parameters."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    hb, _ = ssd_tiles(S, chunk, H, G, P, N, x.dtype)
    R, rpg, n = H // hb, H // G // hb, S // chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    cs = _block_sums(dt * A.astype(f32), chunk).reshape(b, S, R, hb)
    operands = (
        x.reshape(b, S, R, hb * P).transpose(0, 2, 3, 1),
        dt.reshape(b, S, R, hb).transpose(0, 2, 3, 1),
        cs.transpose(0, 2, 3, 1), cs.transpose(0, 2, 1, 3),
        B.transpose(0, 2, 3, 1), C.transpose(0, 2, 3, 1), D.astype(f32))

    def specs(block_of):
        """Block specs with the block axis read through ``block_of``."""
        def run_tokens(rows):
            return pl.BlockSpec(
                (1, 1, rows, chunk),
                lambda bi, i, g, j: (bi, g * rpg + j, 0, block_of(i)))

        return {
            "x": run_tokens(hb * P), "row": run_tokens(hb),
            "col": pl.BlockSpec(
                (1, 1, chunk, hb),
                lambda bi, i, g, j: (bi, g * rpg + j, block_of(i), 0)),
            "group": pl.BlockSpec(
                (1, 1, N, chunk),
                lambda bi, i, g, j: (bi, g, 0, block_of(i))),
            "state": pl.BlockSpec(
                (1, 1, 1, hb * P, N),
                lambda bi, i, g, j: (bi, block_of(i), g * rpg + j, 0, 0)),
            "scalars": pl.BlockSpec(memory_space=pltpu.SMEM)}

    # every axis in order: the state is carried over the blocks, ``C B^T``
    # and the group's sums over a group's runs; the rule's tiles fit the
    # default scoped VMEM, so no limit is asked for
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 4)
    return operands, (b, n, G, rpg), specs, params, (hb, R, n)


#: The wrappers are jitted in their own right: the layers of a model share
#: one lowering of each.
@functools.partial(jax.jit, static_argnames=("chunk", "keep", "interpret"))
def _ssd_fwd_call(x, dt, B, C, A, D, *, chunk, keep, interpret):
    """``y`` (b, S, H, P) and, where ``keep``, the state each block
    started from (b, n, R, hb P, N) float32, for the backward."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    f32 = jnp.float32
    with named_scope("ssd-scan"):
        operands, grid, specs, params, (hb, R, n) = _ssd_layout(
            x, dt, B, C, A, D, chunk)
        s = specs(lambda i: i)
        out_shape = [jax.ShapeDtypeStruct(operands[0].shape, x.dtype)]
        out_specs = [s["x"]]
        if keep:
            out_shape.append(
                jax.ShapeDtypeStruct((b, n, R, hb * P, N), f32))
            out_specs.append(s["state"])
        T = _tiles(chunk)[0]
        out = pl.pallas_call(
            functools.partial(_ssd_fwd_kernel, hb=hb, P=P, keep=keep),
            out_shape=out_shape, grid=grid,
            in_specs=[s["x"], s["row"], s["row"], s["col"], s["group"],
                      s["group"], s["scalars"]],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((R, hb * P, N), f32),            # h_s
                pltpu.VMEM((chunk, chunk), f32),            # cb_s
                pltpu.VMEM((chunk, chunk), x.dtype),        # cbo_s
                pltpu.VMEM((hb * P, chunk), f32),           # off_s
                pltpu.VMEM((hb * P, chunk), x.dtype)],      # us_s
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=2 * b * S * (G * chunk * N + H * P * (
                    (chunk + T) // 2 + 2 * N)),
                transcendentals=b * S * H * T,
                bytes_accessed=2 * x.size * x.dtype.itemsize + (
                    b * n * H * P * N * 4 if keep else 0)),
            interpret=interpret, name="ssd-fwd",
        )(*operands)
        y = out[0].transpose(0, 3, 1, 2).reshape(b, S, H, P)
        return (y, out[1]) if keep else y


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_bwd_call(x, dt, B, C, A, D, starts, dy, *, chunk, interpret):
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    f32 = jnp.float32
    with named_scope("ssd-scan"):
        operands, grid, specs, params, (hb, R, n) = _ssd_layout(
            x, dt, B, C, A, D, chunk)
        s = specs(lambda i: n - 1 - i)
        xT, dtT, _, _, bT = operands[:5]
        dyT = dy.reshape(b, S, R, hb * P).transpose(0, 2, 3, 1)
        rows, (T, nt) = hb * P, _tiles(chunk)
        pairs = max(1, nt * (nt - 1) // 2)
        dxT, ddt, dcs, dbT, dcT, dD = pl.pallas_call(
            functools.partial(_ssd_bwd_kernel, hb=hb, P=P),
            out_shape=[
                jax.ShapeDtypeStruct(xT.shape, x.dtype),
                jax.ShapeDtypeStruct(dtT.shape, f32),
                jax.ShapeDtypeStruct(dtT.shape, f32),
                jax.ShapeDtypeStruct(bT.shape, B.dtype),
                jax.ShapeDtypeStruct(bT.shape, C.dtype),
                jax.ShapeDtypeStruct((R, hb, 1), f32)],
            grid=grid,
            in_specs=[s["x"], s["row"], s["row"], s["col"], s["group"],
                      s["group"], s["scalars"], s["state"], s["x"]],
            out_specs=[s["x"], s["row"], s["row"], s["group"], s["group"],
                       pl.BlockSpec((R, hb, 1),
                                    lambda bi, i, g, j: (0, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((R, rows, N), f32),              # dh_s
                pltpu.VMEM((chunk, chunk), f32),            # cb_s
                pltpu.VMEM((chunk, chunk), x.dtype),        # cbo_s
                pltpu.VMEM((chunk, chunk), f32),            # dcb_s
                pltpu.VMEM((N, chunk), f32),                # db_s
                pltpu.VMEM((N, chunk), f32),                # dc_s
                pltpu.VMEM((rows, chunk), f32),             # off_s
                pltpu.VMEM((rows, chunk), f32),             # g_s
                pltpu.VMEM((rows, chunk), x.dtype),         # us_s
                pltpu.VMEM((rows, chunk), x.dtype),         # dyo_s
                pltpu.VMEM((pairs, rows, T), x.dtype),      # pre_s
                pltpu.VMEM((pairs, rows, T), x.dtype)],     # post_s
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=2 * b * S * (3 * G * chunk * N + H * P * (
                    3 * (chunk + T) // 2 + 5 * N)),
                transcendentals=b * S * H * T,
                bytes_accessed=3 * x.size * x.dtype.itemsize
                + starts.size * 4),
            interpret=interpret, name="ssd-bwd",
        )(*operands, starts, dyT)

        def tokens_first(a):            # (b, R, hb, S) -> (b, S, H)
            return a.transpose(0, 3, 1, 2).reshape(b, S, H)

        # the running sums' cotangent back through the sums, a = dt A
        da = _block_sums(tokens_first(dcs), chunk, back=True)
        return (dxT.transpose(0, 3, 1, 2).reshape(b, S, H, P),
                (da * A.astype(f32) + tokens_first(ddt)).astype(dt.dtype),
                dbT.transpose(0, 3, 1, 2), dcT.transpose(0, 3, 1, 2),
                jnp.sum(da * dt.astype(f32), axis=(0, 1)).astype(A.dtype),
                dD.reshape(H).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, B, C, A, D, chunk):
    """``x`` (b, S, H, P), ``dt`` (b, S, H), ``B``, ``C`` (b, S, G, N),
    ``A``, ``D`` (H,)."""
    return _ssd_fwd_call(x, dt, B, C, A, D, chunk=chunk, keep=False,
                         interpret=default_interpret())


def _ssd_fwd(x, dt, B, C, A, D, chunk):
    y, starts = _ssd_fwd_call(x, dt, B, C, A, D, chunk=chunk, keep=True,
                              interpret=default_interpret())
    return y, (x, dt, B, C, A, D, starts)


def _ssd_bwd(chunk, saved, dy):
    return _ssd_bwd_call(*saved, dy, chunk=chunk,
                         interpret=default_interpret())


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def publish_geometry(event: str, prefix: str, record: dict,
                      **labels) -> None:
    """One geometry record a traced op (at TRACE time, beside
    ``flash_geometry``): a row ``event`` of the StepRecorder,
    ``<prefix>/<field>`` gauges and a ``<prefix>/calls`` counter of the
    Reporter.  ``labels`` are the row's words; a gauge named after each
    one's value reads 1."""
    rec = _step_log.current_recorder()
    if rec is not None:
        rec.record(event, **labels, **record)
    rep = _reporter.get_reporter()
    if rep is not None:
        rep.count(f"{prefix}/calls")
        for field, value in record.items():
            rep.gauge(f"{prefix}/{field}", value)
        for value in labels.values():
            rep.gauge(f"{prefix}/{value}", 1)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int):
    """``y_t = H_t C_t + D x_t`` of the recurrence above, for every head.

    ``x``: (b, S, H, P) activations; ``dt``: (b, S, H) float32, positive
    (after the softplus); ``A``: (H,) float32, negative; ``B``, ``C``:
    (b, S, G, N) with ``G`` dividing ``H`` (head ``h`` reads group ``h //
    (H / G)``); ``D``: (H,).  ``chunk`` tokens a block; ``S`` must be a
    multiple of it.  Returns (b, S, H, P) in ``x.dtype``.  Every sequence
    starts from a zero state: a batch row is one document."""
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    if C.shape != B.shape:
        raise ValueError(f"ssd_scan: B {B.shape} and C {C.shape} must agree")
    hb, vmem = ssd_tiles(S, chunk, H, G, P, N, x.dtype)
    if not default_interpret() and chunk % _SSD_TILE and chunk != S:
        raise ValueError(
            f"ssd_scan: on the chip a block's tokens fill whole registers "
            f"of {_SSD_TILE} lanes; the chunk {chunk} is no multiple")
    if telemetry_active():
        publish_geometry("ssd_geometry", "ssd", {
            "chunk": chunk, "chunks": S // chunk, "heads": H, "d_head": P,
            "d_state": N, "groups": G, "heads_a_step": hb,
            "grid_steps": b * (S // chunk) * (H // hb),
            "vmem_bytes": vmem}, form="kernel")
    return _ssd(x, dt, B, C, A, D, chunk)
