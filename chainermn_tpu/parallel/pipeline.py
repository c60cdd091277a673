"""Microbatched SPMD pipeline parallelism — the performance tier above
``MultiNodeChainList``.

The reference's pipeline story (SURVEY §2.5): ``MultiNodeChainList``'s
send/recv chain is sequential fill-drain per batch — no microbatching, no
overlap.  This module is the TPU-native upgrade: stages are *stacked* along
a mesh axis (device i holds stage i's parameters — genuinely sharded, not
replicated), the batch is split into microbatches, and a ``lax.scan`` over
``M + n - 1`` ticks runs the classic GPipe schedule with a single
``lax.ppermute`` shift per tick.  On a TPU torus each shift is one
ICI-neighbor hop; XLA overlaps the permute with the next tick's stage
compute.  Backward is jax AD through the scan — the reverse-order schedule
the reference would have needed hand-written send/recv pairs for.

Constraint inherited from the stacking trick: all stages share one
``stage_fn`` signature and a common activation shape (the usual
homogeneous-blocks case, e.g. transformer layers).  Heterogeneous chains
(encoder/decoder with different shapes) stay on ``MultiNodeChainList``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..communicators.mesh_utils import axis_size_traced


def spmd_pipeline(
    stage_fn: Callable,
    stage_params,
    x,
    axis_name: str,
    n_microbatches: int,
):
    """Run a GPipe-schedule pipeline inside ``shard_map``.

    ``stage_fn(stage_params, activation) -> activation`` — one stage's
    compute; same activation shape in and out.
    ``stage_params`` — THIS device's stage parameters (shard the stacked
    (n_stages, ...) pytree with ``P(axis_name)`` and squeeze, or build
    per-stage params inside the mapped function).
    ``x`` — (B, ...) the full local batch, meaningful on stage 0.
    Returns (B, ...) final-stage outputs, valid on the LAST stage (zeros
    elsewhere); broadcast if every stage needs them.
    """
    n = axis_size_traced(axis_name)
    idx = lax.axis_index(axis_name)
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(
            f"batch {B} not divisible by n_microbatches {n_microbatches}"
        )
    mb = B // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    perm = [(i, (i + 1) % n) for i in range(n)]
    T = n_microbatches + n - 1

    def tick(state, t):
        # Stage 0 ingests microbatch t (zeros once the batch is drained);
        # other stages consume the activation shifted from their neighbor.
        feed = jnp.where(
            t < n_microbatches,
            lax.dynamic_index_in_dim(
                micro, jnp.minimum(t, n_microbatches - 1), keepdims=False
            ),
            jnp.zeros_like(micro[0]),
        )
        inp = jnp.where(idx == 0, feed, state)
        y = stage_fn(stage_params, inp)
        state = lax.ppermute(y, axis_name, perm)
        # Emit this tick's last-stage output as a scan ys (NOT a carried
        # buffer: a carried (M, ...) output array would be saved per tick
        # by reverse-mode AD, turning O(M) memory into O(M*T)).
        out = jnp.where(idx == n - 1, y, jnp.zeros_like(y))
        return state, out

    state0 = jnp.zeros_like(micro[0])
    _, ys = lax.scan(jax.checkpoint(tick), state0, jnp.arange(T))
    # Microbatch m completes on the last stage at tick m + n - 1.
    outputs = ys[n - 1 :]
    return outputs.reshape(B, *x.shape[1:])


def pipeline_1f1b_loss_and_grads(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x,
    target,
    axis_name: str,
    n_microbatches: int,
    loss_params=None,
    with_input_grads: bool = False,
):
    """1F1B-style pipelined forward AND backward in one scan, with explicit
    vjp bookkeeping — no ``jax.grad`` over the schedule.

    Why it exists: differentiating :func:`spmd_pipeline` gives the GPipe
    schedule — ALL forwards run (saving one residual per tick, ``O(M + n)``
    of them), then all backwards.  This function interleaves two SPMD
    wavefronts instead: at global tick ``t`` stage ``s`` runs the forward
    of microbatch ``t - s`` and the backward of microbatch
    ``t - 2(n-1) + s``.  A microbatch's backward trails its forward on the
    same stage by ``2(n-1-s)`` ticks, so at most ``2n - 1`` saved stage
    *inputs* are live per device (a static ring buffer), independent of the
    microbatch count — the 1F1B memory bound.  Backward recomputes the
    stage forward from the saved input (per-microbatch remat, the same
    trade ``jax.checkpoint`` makes in the GPipe path).

    Timeline: ``M + 2(n-1)`` ticks, each doing one forward plus one
    recompute+backward, versus the GPipe path's ``M + n - 1`` forward
    ticks followed by ``M + n - 1`` recompute+backward ticks — comparable
    bubble, but peak activation memory ``O(n)`` instead of ``O(M + n)``,
    so the microbatch count can grow to shrink the bubble without
    growing memory.

    ``loss_fn(final_activation, target_microbatch) -> scalar`` (mean over
    the microbatch).  Returns ``(mean_loss, stage_grads)`` where ``loss``
    is replicated across stages and ``stage_grads`` matches
    ``stage_params`` — each device holding the gradients of ITS stage, the
    natural sharding for a pipeline-parallel optimizer.

    Composition with surrounding layers (a head above the pipeline, an
    embedding below it):

    - ``loss_params``: when given, ``loss_fn(loss_params, y, target)`` —
      the classifier/head runs INSIDE the schedule (where 1F1B needs it:
      each microbatch's backward starts the tick its forward ends) and its
      gradients are appended to the return:
      ``(loss, stage_grads, loss_param_grads)``, the latter nonzero on the
      last stage (psum over the axis before use).
    - ``with_input_grads=True``: additionally append ``input_grads`` of
      shape ``x.shape`` — the cotangent of the pipeline input, nonzero on
      stage 0 (psum before use) — to feed an embedding's ``jax.vjp``
      outside the schedule.
    """
    n = axis_size_traced(axis_name)
    idx = lax.axis_index(axis_name)
    M = n_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    mb = B // M
    micro = x.reshape(M, mb, *x.shape[1:])
    tmicro = target.reshape(M, mb, *target.shape[1:])

    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [((i + 1) % n, i) for i in range(n)]
    K = 2 * n - 1          # ring slots: fwd/bwd lag is at most 2(n-1) < K
    T = M + 2 * (n - 1)

    def fwd_only(p, xin):
        return stage_fn(p, xin)

    if loss_params is None:
        def loss_and_cotangents(y, tgt):
            mloss, gy = jax.value_and_grad(loss_fn)(y, tgt)
            return mloss, gy, ()
    else:
        def loss_and_cotangents(y, tgt):
            mloss, (ghp, gy) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                loss_params, y, tgt
            )
            return mloss, gy, ghp

    def tick(carry, t):
        fwd_state, bwd_grad, ring, gacc, hacc, lacc = carry

        # ---- forward wavefront: microbatch mf = t - idx ----
        mf = t - idx
        active_f = jnp.logical_and(mf >= 0, mf < M)
        feed = lax.dynamic_index_in_dim(
            micro, jnp.clip(mf, 0, M - 1), keepdims=False
        )
        xin = jnp.where(idx == 0, feed, fwd_state)
        y = stage_fn(stage_params, xin)
        # Save the stage input for this microbatch's backward.  Inactive
        # ticks (fill/drain) must leave the ring untouched: the clipped
        # slot index aliases slot 0 / M-1, whose saved input a trailing
        # backward may not have consumed yet.
        ring = jnp.where(
            active_f,
            lax.dynamic_update_index_in_dim(
                ring, xin, jnp.clip(mf, 0, M - 1) % K, axis=0
            ),
            ring,
        )

        # Last stage: this tick's forward microbatch IS this tick's
        # backward microbatch (mb_idx == mf there); compute the loss and
        # its output-cotangent now.
        tgt = lax.dynamic_index_in_dim(
            tmicro, jnp.clip(mf, 0, M - 1), keepdims=False
        )
        mloss, gy_last, ghp = loss_and_cotangents(y, tgt)
        last_active = jnp.logical_and(active_f, idx == n - 1)
        lacc = lacc + jnp.where(last_active, mloss, 0.0)
        hacc = jax.tree.map(
            lambda a, g: a + jnp.where(last_active, g / M, jnp.zeros_like(g)),
            hacc, ghp,
        )

        # ---- backward wavefront: microbatch mb_idx = t - 2(n-1) + idx ----
        mb_idx = t - 2 * (n - 1) + idx
        active_b = jnp.logical_and(mb_idx >= 0, mb_idx < M)
        x_saved = lax.dynamic_index_in_dim(
            ring, jnp.clip(mb_idx, 0, M - 1) % K, keepdims=False
        )
        _, vjp = jax.vjp(fwd_only, stage_params, x_saved)
        g_in = jnp.where(idx == n - 1, gy_last / M, bwd_grad)
        gp, gx = vjp(g_in)
        gacc = jax.tree.map(
            lambda a, g: a + jnp.where(active_b, g, jnp.zeros_like(g)),
            gacc, gp,
        )

        # ---- shifts for the next tick ----
        gx_masked = jnp.where(active_b, gx, jnp.zeros_like(gx))
        fwd_state = lax.ppermute(y, axis_name, fwd_perm)
        bwd_grad = lax.ppermute(gx_masked, axis_name, bwd_perm)
        # Stage 0's input cotangent, emitted as a scan output (microbatch m
        # completes its stage-0 backward at tick m + 2(n-1)).
        gx_out = jnp.where(idx == 0, gx_masked, jnp.zeros_like(gx_masked))
        return (fwd_state, bwd_grad, ring, gacc, hacc, lacc), gx_out

    carry0 = (
        jnp.zeros_like(micro[0]),                      # fwd activation in
        jnp.zeros_like(micro[0]),                      # bwd cotangent in
        jnp.zeros((K, mb, *x.shape[1:]), x.dtype),     # saved-input ring
        jax.tree.map(jnp.zeros_like, stage_params),    # param grad accum
        () if loss_params is None
        else jax.tree.map(jnp.zeros_like, loss_params),  # head grad accum
        jnp.zeros((), jnp.float32),                    # loss accum
    )
    # No jax.checkpoint here: nothing differentiates *through* this scan —
    # the backward is explicit inside each tick.
    (_, _, _, gacc, hacc, lacc), gx_ys = lax.scan(tick, carry0, jnp.arange(T))
    loss = lax.psum(lacc / M, axis_name)
    out = (loss, gacc)
    if loss_params is not None:
        out = out + (hacc,)
    if with_input_grads:
        out = out + (gx_ys[2 * (n - 1) :].reshape(B, *x.shape[1:]),)
    return out


def pipeline_interleaved_1f1b_loss_and_grads(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x,
    target,
    axis_name: str,
    n_microbatches: int,
    n_chunks: int,
    loss_params=None,
    with_input_grads: bool = False,
):
    """Interleaved (virtual-stage) 1F1B: ``v = n_chunks`` model chunks PER
    DEVICE, explicit-vjp backward — the Megatron-LM interleaved schedule
    in SPMD form.

    Each device holds ``v`` non-adjacent model chunks (device ``d`` owns
    global stages ``d, d+n, ..., d+(v-1)n``; ``stage_params`` leads with a
    ``(v, ...)`` chunk axis, sharded so each device materializes only its
    own chunks' slice).  Microbatches circulate the ring ``v`` laps; on
    lap ``l`` a device applies chunk ``l``.  Admissions happen in rounds
    of ``n`` (``n_microbatches`` must divide by ``n``): round ``r``'s lap
    work tiles the ring exactly until round ``r+1`` is admitted, so
    devices never idle between rounds.  Schedule algebra, with
    ``L = n * v`` global stages, ``m = r*n + j``, ``s = l*n + d``:

        forward  of (m, s) on device d at tick  t = r*v*n + s + j
        backward of (m, s) on device d at tick  t = r*v*n + j + 2(L-1) - s

    Both wavefronts advance one device per tick through the SAME two
    ``ppermute`` shifts as the non-interleaved scheduler; a ring wrap
    (device n-1 -> 0 forward, 0 -> n-1 backward) is a chunk transition.

    Bubble accounting (be precise — each tick here is ONE CHUNK of
    compute, ``1/v`` of a whole stage): total ticks ``T = Mv + nv + n -
    2`` versus the ideal ``Mv``, i.e. a bubble of ``nv + n - 2 =
    (n-1)(v+1) + (v-1)`` chunk-times.  The non-interleaved scheduler's
    bubble is ``2(n-1)`` whole-stage times = ``2v(n-1)`` chunk-times for
    the same total depth, so this round-based schedule cuts the bubble by
    ``~(v+1)/2v`` — a factor approaching 2 at large ``v``, NOT the
    ``1/v`` of Megatron-LM's tighter schedule.

    That residual gap is structural to the COUPLED design: within this
    schedule each device's forward slot stream is GAPLESS over
    ``[idx, Mv + idx)`` and its backward slot stream is gapless over
    ``[2(L-1) - idx, ...)``; the whole bubble is the dependency-forced
    phase offset between the two streams (microbatch 0's stage-0
    backward cannot fire before tick ``2(L-1)``), which a
    fwd+bwd-in-one-tick SPMD program cannot compress — every arrival
    must be served the tick it lands.  DECOUPLING the directions removes
    it: :func:`pipeline_circular_1f1b_loss_and_grads` runs the forward
    as its own ``M*v + n - 1``-tick circular scan and lets AD mirror it
    backward, reaching the Megatron bound ``(n-1)/(v*M)`` — at ``O(M*v)``
    saved activations where this scheduler holds ``O(2L-1)``.  Keep this
    one when the activation footprint binds; use the circular one when
    the bubble does.

    Memory: the saved-input ring holds ``2L - 1`` microbatch activations
    (each chunk's backward recomputes only ITS chunk) versus ``2n - 1``
    whole-stage inputs non-interleaved — the classic interleaving trade:
    less bubble, more in-flight activations.

    Same return contract as :func:`pipeline_1f1b_loss_and_grads`;
    ``stage_grads`` carries the ``(v, ...)`` chunk axis of
    ``stage_params``.
    """
    n = axis_size_traced(axis_name)
    idx = lax.axis_index(axis_name)
    v = n_chunks
    M = n_microbatches
    L = n * v
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    if M % n:
        raise ValueError(
            f"interleaved schedule needs n_microbatches ({M}) divisible "
            f"by the pipeline size ({n}) — admissions happen in rounds"
        )
    if v < 1:
        raise ValueError(f"n_chunks must be >= 1, got {v}")
    mb = B // M
    micro = x.reshape(M, mb, *x.shape[1:])
    tmicro = target.reshape(M, mb, *target.shape[1:])

    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [((i + 1) % n, i) for i in range(n)]
    K = 2 * L - 1          # ring slots: fwd->bwd lag is at most 2(L-1) < K
    T = M * v + n * v + n - 2

    def chunk(tree, l):
        return jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, l, keepdims=False), tree
        )

    def fwd_only(p, xin):
        return stage_fn(p, xin)

    if loss_params is None:
        def loss_and_cotangents(y, tgt):
            mloss, gy = jax.value_and_grad(loss_fn)(y, tgt)
            return mloss, gy, ()
    else:
        def loss_and_cotangents(y, tgt):
            mloss, (ghp, gy) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                loss_params, y, tgt
            )
            return mloss, gy, ghp

    def tick(carry, t):
        fwd_state, bwd_grad, ring, gacc, hacc, lacc = carry

        # ---- forward wavefront ----
        w_f = t - idx
        r_f = w_f // L
        u_f = w_f % L                   # position within the round's laps
        l_f = u_f // n                  # chunk (lap)
        m_f = r_f * n + u_f % n         # microbatch
        active_f = jnp.logical_and(w_f >= 0, m_f < M)
        feed = lax.dynamic_index_in_dim(
            micro, jnp.clip(m_f, 0, M - 1), keepdims=False
        )
        xin = jnp.where(jnp.logical_and(idx == 0, l_f == 0), feed, fwd_state)
        p_f = chunk(stage_params, jnp.clip(l_f, 0, v - 1))
        y = stage_fn(p_f, xin)
        # Save the chunk input for this (microbatch, chunk)'s backward.
        slot_f = jnp.clip(w_f, 0, None) % K
        ring = jnp.where(
            active_f,
            lax.dynamic_update_index_in_dim(ring, xin, slot_f, axis=0),
            ring,
        )

        # Last device, last chunk: 1F1B — loss & output-cotangent now.
        tgt = lax.dynamic_index_in_dim(
            tmicro, jnp.clip(m_f, 0, M - 1), keepdims=False
        )
        mloss, gy_last, ghp = loss_and_cotangents(y, tgt)
        last_active = jnp.logical_and(
            active_f, jnp.logical_and(idx == n - 1, l_f == v - 1)
        )
        lacc = lacc + jnp.where(last_active, mloss, 0.0)
        hacc = jax.tree.map(
            lambda a, g: a + jnp.where(last_active, g / M, jnp.zeros_like(g)),
            hacc, ghp,
        )

        # ---- backward wavefront ----
        w_b = t - 2 * (L - 1) + idx
        j_b = w_b % n
        z_b = (w_b - j_b) // n          # = r*v - l
        r_b = (z_b + v - 1) // v        # ceil(z/v): unique (r, l) solution
        l_b = r_b * v - z_b
        m_b = r_b * n + j_b
        # w_b = r*v*n - l*n + j is legitimately NEGATIVE for high-chunk
        # backwards of round 0 (l > 0 at small t); activity is exactly
        # r >= 0 (equivalently m >= 0) and m < M.
        active_b = jnp.logical_and(m_b >= 0, m_b < M)
        w_f_of_b = r_b * L + l_b * n + j_b   # that unit's forward wavefront
        x_saved = lax.dynamic_index_in_dim(
            ring, jnp.clip(w_f_of_b, 0, None) % K, keepdims=False
        )
        p_b = chunk(stage_params, jnp.clip(l_b, 0, v - 1))
        _, vjp = jax.vjp(fwd_only, p_b, x_saved)
        fresh = jnp.logical_and(idx == n - 1, l_b == v - 1)
        g_in = jnp.where(fresh, gy_last / M, bwd_grad)
        gp, gx = vjp(g_in)
        gacc = jax.tree.map(
            lambda a, g: lax.dynamic_update_index_in_dim(
                a,
                lax.dynamic_index_in_dim(
                    a, jnp.clip(l_b, 0, v - 1), keepdims=False
                ) + jnp.where(active_b, g, jnp.zeros_like(g)),
                jnp.clip(l_b, 0, v - 1),
                axis=0,
            ),
            gacc, gp,
        )

        # ---- shifts for the next tick ----
        gx_masked = jnp.where(active_b, gx, jnp.zeros_like(gx))
        fwd_state = lax.ppermute(y, axis_name, fwd_perm)
        bwd_grad = lax.ppermute(gx_masked, axis_name, bwd_perm)
        # Stage-0-chunk-0 input cotangent (microbatch m=rn+j completes at
        # tick r*v*n + j + 2(L-1) on device 0).
        gx_out = jnp.where(
            jnp.logical_and(idx == 0, l_b == 0),
            gx_masked, jnp.zeros_like(gx_masked),
        )
        return (fwd_state, bwd_grad, ring, gacc, hacc, lacc), gx_out

    carry0 = (
        jnp.zeros_like(micro[0]),                      # fwd activation in
        jnp.zeros_like(micro[0]),                      # bwd cotangent in
        jnp.zeros((K, mb, *x.shape[1:]), x.dtype),     # saved-input ring
        jax.tree.map(jnp.zeros_like, stage_params),    # (v, ...) grad accum
        () if loss_params is None
        else jax.tree.map(jnp.zeros_like, loss_params),  # head grad accum
        jnp.zeros((), jnp.float32),                    # loss accum
    )
    (_, _, _, gacc, hacc, lacc), gx_ys = lax.scan(tick, carry0, jnp.arange(T))
    loss = lax.psum(lacc / M, axis_name)
    out = (loss, gacc)
    if loss_params is not None:
        out = out + (hacc,)
    if with_input_grads:
        # Emission ticks are round-strided, not contiguous: m = r*n + j
        # finishes stage-0-chunk-0 backward at tick r*v*n + j + 2(L-1).
        import numpy as _np

        ticks = _np.array([
            (m // n) * v * n + (m % n) + 2 * (L - 1) for m in range(M)
        ])
        out = out + (gx_ys[ticks].reshape(B, *x.shape[1:]),)
    return out


def circular_schedule_ticks(n: int, n_microbatches: int, n_chunks: int) -> int:
    """Total forward ticks of the circular (buffered-admission) schedule:
    ``M*v + n - 1`` — each device is gapless for its ``M*v`` chunk units,
    offset by its ring position.  The backward (AD mirror) adds the same,
    so the whole step's bubble is ``2(n-1)`` chunk-times against an ideal
    ``2Mv`` — the Megatron-LM interleaved bound ``(n-1)/(v*M)``."""
    return n_microbatches * n_chunks + n - 1


def spmd_pipeline_circular(
    stage_fn: Callable,
    stage_params,
    x,
    axis_name: str,
    n_microbatches: int,
    n_chunks: int,
):
    """Circular (virtual-stage) pipeline FORWARD with round-buffered
    admissions — the Megatron-tight interleaved schedule.

    Device ``d`` holds ``v = n_chunks`` model chunks (global stage
    ``s = l*n + d``; ``stage_params`` leads with the ``(v, ...)`` chunk
    axis).  Microbatches are admitted in rounds of ``n`` and each round is
    pushed through ALL ``v`` laps before the next round is admitted:
    device ``d`` at tick ``t`` works local time ``u = t - d`` with

        r = u // (n*v)   (admission round)
        l = (u % (n*v)) // n   (chunk / lap)
        m = r*n + u % n        (microbatch)

    Every device's work stream is gapless over ``[d, d + M*v)`` and every
    handoff lands exactly one tick before its consumption — including the
    ring wrap ``n-1 → 0`` between laps — so the single ``ppermute`` shift
    register IS the arrival buffer (the role MaxText's ``circ_storage``
    plays for its all-at-once admission order; round admission makes the
    buffer depth exactly 1).  Total ticks :func:`circular_schedule_ticks`
    = ``M*v + n - 1``: bubble ``n - 1`` chunk-times forward.

    Backward is jax AD through the scan (each tick ``jax.checkpoint``-ed:
    backward recomputes the chunk forward from its saved input).  The
    reverse scan mirrors the schedule tick for tick, so the combined
    bubble is ``2(n-1)`` chunk-times against an ideal ``2*M*v`` — the
    Megatron-LM interleaved bound ``(n-1)/(v*M)``, v times tighter than
    :func:`pipeline_interleaved_1f1b_loss_and_grads`'s coupled-wavefront
    ``~n(v+1)``.  The price is memory: AD saves one in-flight activation
    per tick, ``O(M*v)`` microbatch activations, versus the coupled
    scheduler's ``O(2nv - 1)`` ring — choose by whether the bubble or the
    activation footprint binds.

    Returns ``(B, ...)`` final-stage outputs in microbatch order, valid on
    the LAST device (zeros elsewhere).
    """
    n = axis_size_traced(axis_name)
    idx = lax.axis_index(axis_name)
    M = n_microbatches
    v = n_chunks
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    if M % n:
        raise ValueError(
            f"circular schedule needs n_microbatches ({M}) divisible by "
            f"the pipeline size ({n}) — admissions happen in rounds"
        )
    if v < 1:
        raise ValueError(f"n_chunks must be >= 1, got {v}")
    mb = B // M
    micro = x.reshape(M, mb, *x.shape[1:])
    perm = [(i, (i + 1) % n) for i in range(n)]
    T = circular_schedule_ticks(n, M, v)

    def tick(shift, t):
        u = t - idx
        r = u // (n * v)
        q = u % (n * v)
        l = q // n
        m = r * n + q % n
        active = jnp.logical_and(u >= 0, u < M * v)
        feed = lax.dynamic_index_in_dim(
            micro, jnp.clip(m, 0, M - 1), keepdims=False
        )
        xin = jnp.where(
            jnp.logical_and(idx == 0, l == 0), feed, shift
        )
        p = jax.tree.map(
            lambda pp: lax.dynamic_index_in_dim(
                pp, jnp.clip(l, 0, v - 1), keepdims=False
            ),
            stage_params,
        )
        y = stage_fn(p, xin)
        out = jnp.where(
            jnp.logical_and(
                active, jnp.logical_and(idx == n - 1, l == v - 1)
            ),
            y, jnp.zeros_like(y),
        )
        return lax.ppermute(y, axis_name, perm), out

    _, ys = lax.scan(
        jax.checkpoint(tick), jnp.zeros_like(micro[0]), jnp.arange(T)
    )
    # Microbatch m = r*n + j exits the last global stage (device n-1,
    # lap v-1) at tick (n-1) + r*n*v + (v-1)*n + j.
    import numpy as _np

    exit_ticks = _np.array([
        (n - 1) + (m // n) * n * v + (v - 1) * n + (m % n) for m in range(M)
    ])
    return ys[exit_ticks].reshape(B, *x.shape[1:])


def pipeline_circular_1f1b_loss_and_grads(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x,
    target,
    axis_name: str,
    n_microbatches: int,
    n_chunks: int,
    loss_params=None,
    with_input_grads: bool = False,
):
    """Loss + grads over :func:`spmd_pipeline_circular` — the
    Megatron-tight interleaved schedule with the same return contract as
    :func:`pipeline_interleaved_1f1b_loss_and_grads` (``stage_grads``
    carries the ``(v, ...)`` chunk axis; head grads live on the last
    stage, input cotangents on stage 0 — psum both before use).

    The backward here is jax AD through the circular scan (mirrored
    schedule, per-tick remat), not an explicit-vjp wavefront: bubble
    ``(n-1)/(v*M)`` at ``O(M*v)`` saved activations.  Use the coupled
    explicit-vjp scheduler when the activation footprint binds instead.
    """
    n = axis_size_traced(axis_name)
    idx = lax.axis_index(axis_name)
    M = n_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    mb = B // M
    tmicro = target.reshape(M, mb, *target.shape[1:])

    def local_loss(sp, lp, xx):
        # Device-LOCAL masked loss — deliberately not psum'd: seeding
        # every device's local output with cotangent 1 differentiates
        # their sum (= the last device's loss, others are hard zeros),
        # with cotangents routed by the transposed ppermutes.  A psum
        # here would transpose to another psum under AD (replication
        # tracking is off inside these schedules), inflating every
        # gradient by the axis size.
        outs = spmd_pipeline_circular(
            stage_fn, sp, xx, axis_name, M, n_chunks
        )
        om = outs.reshape(M, mb, *outs.shape[1:])
        if lp is None:
            per = jax.vmap(loss_fn)(om, tmicro)
        else:
            per = jax.vmap(loss_fn, in_axes=(None, 0, 0))(lp, om, tmicro)
        return jnp.where(idx == n - 1, per.mean(), 0.0)

    if loss_params is None:
        argnums = (0, 2) if with_input_grads else (0,)
        local, grads = jax.value_and_grad(local_loss, argnums=argnums)(
            stage_params, None, x
        )
        out = (lax.psum(local, axis_name), grads[0])
        if with_input_grads:
            out = out + (grads[1],)
        return out
    argnums = (0, 1, 2) if with_input_grads else (0, 1)
    local, grads = jax.value_and_grad(local_loss, argnums=argnums)(
        stage_params, loss_params, x
    )
    out = (lax.psum(local, axis_name), grads[0], grads[1])
    if with_input_grads:
        out = out + (grads[2],)
    return out


def pipeline_forward_and_loss(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x,
    target,
    axis_name: str,
    n_microbatches: int,
):
    """Pipeline forward + last-stage loss, broadcast to every stage.

    ``loss_fn(final_activation, target) -> scalar`` runs on the last
    stage's outputs; the masked psum makes the mean loss available (and
    differentiable) on every device, so one ``jax.grad`` over this function
    trains all stages — each device materializing gradients only for ITS
    stage parameters.
    """
    n = axis_size_traced(axis_name)
    idx = lax.axis_index(axis_name)
    out = spmd_pipeline(stage_fn, stage_params, x, axis_name, n_microbatches)
    local = jnp.where(idx == n - 1, loss_fn(out, target), 0.0)
    return lax.psum(local, axis_name)


# ---------------------------------------------------------------------
# serving-side composition: decode microbatching for tp×pp shard groups
# ---------------------------------------------------------------------

def decode_microbatches(n_rows: int, n_stages: int):
    """Contiguous split of a decode batch's row range ``[0, n_rows)``
    into at most ``n_stages`` microbatches — the serving analogue of
    this module's microbatch axis.  Returns ``[(start, stop), ...]`` in
    dispatch order (GPipe fill order: stage 0's rows first), sized as
    evenly as possible with the remainder on the leading stages, so the
    split is a pure function of ``(n_rows, n_stages)`` and two shard
    groups given the same batch dispatch identical steps.

    Splitting is bit-exact for the serving stack by construction:
    paged attention is per-sequence and sampling counter-based, so a
    row's logits (and its sampled token) never depend on which other
    rows share its step.
    """
    n_rows = int(n_rows)
    n_stages = max(1, int(n_stages))
    if n_rows <= 0:
        return []
    k = min(n_rows, n_stages)
    base, rem = divmod(n_rows, k)
    spans = []
    start = 0
    for s in range(k):
        stop = start + base + (1 if s < rem else 0)
        spans.append((start, stop))
        start = stop
    return spans


def serve_pipeline_order(n_micro: int, n_stages: int):
    """Dispatch order of ``(stage, microbatch)`` ticks for a serving
    decode iteration pipelined over ``n_stages`` stage subgroups — the
    same fill-drain wavefront :func:`spmd_pipeline` executes, viewed
    from the host dispatcher: microbatch ``m`` enters stage ``s`` at
    tick ``m + s``, so total latency is ``n_micro + n_stages - 1``
    stage-times against ``n_micro * n_stages`` sequential (the GPipe
    bubble).  Pinned by unit test and called by nothing else;
    the leader's own dispatch loop only needs the microbatch order
    (:func:`decode_microbatches`) because follower stages replay
    asynchronously."""
    n_micro = max(0, int(n_micro))
    n_stages = max(1, int(n_stages))
    order = []
    for tick in range(n_micro + n_stages - 1):
        for s in range(n_stages):
            m = tick - s
            if 0 <= m < n_micro:
                order.append((tick, s, m))
    return order
