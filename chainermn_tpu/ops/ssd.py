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

from chainermn_tpu.observability import reporter as _reporter
from chainermn_tpu.observability import step_log as _step_log
from chainermn_tpu.observability.spans import named_scope, telemetry_active


def causal_conv_silu(x, kernel, bias):
    """``silu(conv(x) + bias)``: a causal depthwise convolution along the
    sequence.  ``x``: (B, S, C); ``kernel``: (K, C), tap ``K-1`` weighs
    the current token and tap ``j`` the one ``K-1-j`` back (zeros before
    the sequence starts); ``bias``: (C,).  Sums in float32, returns
    ``x.dtype``."""
    with named_scope("ssm-conv"):
        K, S = kernel.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        acc = bias.astype(jnp.float32)
        for j in range(K):
            acc = acc + (padded[:, j:j + S].astype(jnp.float32)
                         * kernel[j].astype(jnp.float32))
        return jax.nn.silu(acc).astype(x.dtype)


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


def _publish_geometry(record: dict) -> None:
    """One ``ssd_geometry`` record a traced :func:`ssd_scan` (at TRACE
    time, beside ``flash_geometry``): a row of the StepRecorder,
    ``ssd/<field>`` gauges and an ``ssd/calls`` counter of the
    Reporter."""
    rec = _step_log.current_recorder()
    if rec is not None:
        rec.record("ssd_geometry", **record)
    rep = _reporter.get_reporter()
    if rep is not None:
        rep.count("ssd/calls")
        for field, value in record.items():
            rep.gauge(f"ssd/{field}", value)


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
        _publish_geometry({"chunk": chunk, "chunks": S // chunk,
                           "heads": H, "d_head": P, "d_state": N,
                           "groups": G})
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
