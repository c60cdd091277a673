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

The forward is a loop over the blocks that keeps nothing but the
state each block started from (``S/Q`` states of ``H x P x N`` floats);
the backward walks the blocks in reverse, rebuilds each block's decay
matrix from its inputs and carries the state's cotangent.  The ``Q x Q``
matrices of a block therefore never outlive the block, in either pass:
at S=8192, 64 heads and Q=256 that is 33 MB a block alive instead of
1 GB a layer.
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


def causal_conv_silu(x, kernel, bias):
    """``silu(conv(x) + bias)``: a causal depthwise convolution along the
    sequence.  ``x``: (B, S, C); ``kernel``: (K, C), tap ``K-1`` weighs
    the current token and tap ``j`` the one ``K-1-j`` back (zeros before
    the sequence starts); ``bias``: (C,).  Sums in float32, returns
    ``x.dtype``.

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
    return _conv_silu(x, kernel, bias)


def _block(h_prev, x, dt, B, C, A, D):
    """One block of ``Q`` tokens, every batch row and head at once, heads
    before tokens (the layout the matrix unit wants them in).

    ``h_prev`` (b, G, r, P, N) float32; ``x`` (b, G, r, Q, P); ``dt``
    (b, G, r, Q) float32; ``B``, ``C`` (b, G, Q, N); ``A``, ``D`` (G, r)
    float32 — ``G`` groups of ``r`` heads.  Returns ``(h_next, y)``, ``y``
    float32 (b, G, r, Q, P) with the skip ``D x`` added."""
    f32, op = jnp.float32, x.dtype
    Q = x.shape[3]
    live = jnp.tril(jnp.ones((Q, Q), bool))               # [t, s]: s <= t
    # the running sum as a (tiny) product with the triangle, at full
    # precision: a ``cumsum`` lowers to reduce-windows that lose their
    # scope and cost more
    cs = jnp.einsum("bgrs,ts->bgrt", dt * A[..., None], live.astype(f32),
                    precision=lax.Precision.HIGHEST)
    gap = cs[..., :, None] - cs[..., None, :]             # cs_t - cs_s
    decay = jnp.exp(jnp.where(live, gap, -jnp.inf))       # (b, G, r, Q, Q)
    cb = jnp.einsum("bgqn,bgsn->bgqs", C, B, preferred_element_type=f32)
    weights = (cb[:, :, None] * decay).astype(op)
    xdt = x.astype(f32) * dt[..., None]
    y = jnp.einsum("bgrqs,bgrsp->bgrqp", weights, xdt.astype(op),
                   preferred_element_type=f32)
    carried = jnp.einsum("bgqn,bgrpn->bgrqp", C, h_prev.astype(op),
                         preferred_element_type=f32)
    y = (y + jnp.exp(cs)[..., None] * carried
         + D[..., None, None] * x.astype(f32))
    to_end = jnp.exp(cs[..., -1:] - cs)                   # (b, G, r, Q)
    h_next = (jnp.exp(cs[..., -1])[..., None, None] * h_prev
              + jnp.einsum("bgrqp,bgqn->bgrpn",
                           (xdt * to_end[..., None]).astype(op), B,
                           preferred_element_type=f32))
    return h_next, y


#: the token axis of each operand of :func:`_ssd`, in its order
_TOKEN_AXES = (3, 3, 2, 2)          # x, dt, B, C


def _blocks_of(arrays, i, chunk):
    return tuple(lax.dynamic_slice_in_dim(a, i * chunk, chunk, axis)
                 for a, axis in zip(arrays, _TOKEN_AXES))


def _put_block(a, i, block, chunk, axis):
    return lax.dynamic_update_slice_in_dim(
        a, block.astype(a.dtype), i * chunk, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, B, C, A, D, chunk):
    """``x`` (b, G, r, S, P), ``dt`` (b, G, r, S), ``B``, ``C``
    (b, G, S, N): heads before tokens, so that a block is a run of rows
    of every head."""
    return _ssd_fwd(x, dt, B, C, A, D, chunk)[0]


# Both passes walk the blocks by index over the whole arrays and write each
# block's results in place: handing ``lax.scan`` block-major operands costs
# a transposing copy of every operand and result (PERF.md §6, PR 26).

def _ssd_fwd(x, dt, B, C, A, D, chunk):
    with named_scope("ssd-scan"):
        b, G, r, S, P = x.shape
        n, N = S // chunk, B.shape[-1]

        def step(i, carry):
            h, starts, y = carry
            h_next, y_i = _block(
                h, *_blocks_of((x, dt, B, C), i, chunk), A, D)
            return (h_next, lax.dynamic_update_index_in_dim(starts, h, i, 0),
                    _put_block(y, i, y_i, chunk, 3))

        zero = jnp.zeros((b, G, r, P, N), jnp.float32)
        _, starts, y = lax.fori_loop(0, n, step, (
            zero, jnp.zeros((n,) + zero.shape, jnp.float32),
            jnp.zeros_like(x)))
        return y, (x, dt, B, C, A, D, starts)


def _ssd_bwd(chunk, saved, dy):
    x, dt, B, C, A, D, starts = saved
    with named_scope("ssd-scan"):
        n = x.shape[3] // chunk

        def step(k, carry):
            i = n - 1 - k
            dh, dA, dD, grads = carry
            _, pull = jax.vjp(_block, starts[i],
                              *_blocks_of((x, dt, B, C), i, chunk), A, D)
            dy_i = lax.dynamic_slice_in_dim(dy, i * chunk, chunk, 3)
            dh, *here, dA_i, dD_i = pull((dh, dy_i.astype(jnp.float32)))
            return (dh, dA + dA_i, dD + dD_i, tuple(
                _put_block(g, i, g_i, chunk, axis)
                for g, g_i, axis in zip(grads, here, _TOKEN_AXES)))

        _, dA, dD, grads = lax.fori_loop(0, n, step, (
            jnp.zeros_like(starts[0]), jnp.zeros_like(A), jnp.zeros_like(D),
            tuple(jnp.zeros_like(a) for a in (x, dt, B, C))))
        return (*grads, dA, dD)


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
    if S % chunk:
        raise ValueError(
            f"ssd_scan: the sequence length {S} is no multiple of the "
            f"chunk {chunk}")
    if H % G or C.shape != B.shape:
        raise ValueError(
            f"ssd_scan: B {B.shape} and C {C.shape} must agree, their "
            f"groups ({G}) dividing the heads ({H})")
    if telemetry_active():
        publish_geometry("ssd_geometry", "ssd", {
            "chunk": chunk, "chunks": S // chunk, "heads": H, "d_head": P,
            "d_state": N, "groups": G})
    r = H // G
    with named_scope("ssd-scan"):   # heads before tokens, and back
        heads_first = (
            x.reshape(b, S, G, r, P).transpose(0, 2, 3, 1, 4),
            dt.astype(jnp.float32).reshape(b, S, G, r).transpose(0, 2, 3, 1),
            B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3))
    y = _ssd(*heads_first, A.astype(jnp.float32).reshape(G, r),
             D.astype(jnp.float32).reshape(G, r), chunk)
    with named_scope("ssd-scan"):
        return y.transpose(0, 3, 1, 2, 4).reshape(b, S, H, P)
