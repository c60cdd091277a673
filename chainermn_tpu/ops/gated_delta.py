"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), chunked.

A linear-attention layer whose fast-weight state is a MATRIX a head,
``S`` (``d_k x d_v``), updated by a delta rule under a scalar decay::

    S_t = e^{g_t} S_(t-1) + k_t (beta_t (v_t - e^{g_t} S_(t-1)^T k_t))^T
    o_t = S_t^T q_t

(``g_t <= 0`` the log of the decay, ``beta_t`` in (0, 1) the writing
strength, one number a head a token).  ``ops/ssd.py``'s scan computes
``S <- a S + x B^T``, a rank-one ADD; here what is written depends on what
the state already answers for ``k_t``, and the chunked form needs a
triangular solve a chunk.

The chunked form (:func:`gated_delta_rule`), ``C`` tokens a chunk.  Inside
a chunk, with ``G_i`` the running sum of ``g`` from the chunk's start and
``S`` the state the chunk starts from: the new values ``u_i = beta_i (v_i
- e^{g_i} S_(i-1)^T k_i)`` solve ``(I + A) u = beta v - (beta e^G k) S``
with the strictly lower-triangular ``A_ij = beta_i (k_i . k_j) e^{G_i -
G_j}``.  So, a chunk: ``T = (I + A)^-1``, ``W = T (beta e^G k)``, ``U = T
(beta v)``; and from chunk to chunk, over the carried state::

    v_new = U - W S
    o     = (q e^G) S + tril(q k^T e^{G_i - G_j}) v_new
    S    <- e^{G_C} S + (k e^{G_C - G})^T v_new

Precision: the decays, the solve (``A``'s assembly from the float32
product, ``T``, ``W``, ``U``) and the carried state are float32; the other
matrix products — ``k k^T``, ``q k^T``, ``W S``, ``(q e^G) S``, ``tril(..)
v_new``, ``(k e^{G_C - G})^T v_new`` and their transposes in the backward
— take operands in the activations' type and accumulate in float32.

Each pass is ONE Mosaic kernel (``gdn-fwd``, ``gdn-bwd``), both under the
scope ``gdn-scan``, whose grid walks (batch row, key head, tile of
tokens), the tiles in order: a grid step holds several chunks and all the
value heads of its key head (``k k^T`` and ``q k^T`` are made once a key
head; ``q`` and ``k`` are never repeated).  The kernels hold the TOKENS on
the lanes and a head's channels on the sublanes — the layout the
convolution's kernels hand ``q``, ``k``, ``v`` in and the compiler keeps
a mixer's activations in, where splitting the channels into heads moves
nothing — so every matrix above is worked as its transpose (``T^T``,
``W^T = K_b^T T^T``, the state as ``S^T``) and no transposing copy
stands around the calls.  The chunks are worked in GROUPS of two side by
side on the lanes (two chunks of 64 fill a register): a group's ``C x
C`` matrices lie side by side, a product with them is one product with
the group's block-diagonal matrix, and a product with the carried state
is made over the group's width and kept on its chunk's lanes.  The state
of the step's value heads (in backward its cotangent) is carried from
tile to tile in VMEM.  ``T^T`` is made in the kernel by substitution, a
column a step (:func:`_unit_upper_inverse`: what substitution computes,
in the order that fills registers).  The forward writes ``o`` and, kept
only for a backward pass, the state each TILE started from.  The backward
is written by hand: a tile first walks its groups forward from the kept
state (``T^T``, ``W^T``, ``U^T``, ``v_new^T`` and the chunks' starting
states stay in VMEM), then in reverse with the state's cotangent; the
cotangent passes through the solve as ``dA = -T^T dT T^T = -(T^T dW) W^T
- (T^T dU) U^T`` under the triangle.  The running sums of ``g`` and
their cotangent's way back are products with the triangle beside the
calls.  :func:`gdn_tiles` is the one rule for the tokens a grid step
holds, from the operands' shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability.spans import named_scope, telemetry_active
from chainermn_tpu.ops.flash_attention import (
    VMEM_SCOPED_DEFAULT,
    default_interpret,
)
from chainermn_tpu.ops.ssd import _block_sums, publish_geometry

_HIGHEST = lax.Precision.HIGHEST

#: The name (``jax.ad_checkpoint.checkpoint_name``) of what the backward
#: kernel takes from the forward one — ``o`` and the state each tile
#: started from — put on them inside the ``custom_vjp``'s forward rule: a
#: rematerialised layer whose policy saves the name recomputes ``q``,
#: ``k``, ``v``, ``g``, ``beta`` and not the kernel (``remat_names``).
GDN_RESIDUALS = "gdn-residuals"

#: Tokens a grid step holds at most: eight chunks of 64.  A grid step has
#: a fixed cost, and the backward keeps a tile's ``T^T``, ``W^T``,
#: ``U^T``, ``v_new^T`` and chunk states in VMEM.
_GDN_TOKENS = 512
#: Rows of a register of sublanes (float32): the substitution leaves the
#: row blocks that are already final alone.
_SUB = 8
#: Rows the substitution finishes a column a step before the rows above
#: take them in one product (a multiple of :data:`_SUB`).
_SOLVE_ROWS = 32

_NT = (((1,), (1,)), ((), ()))      # contract the lanes of both
_TN = (((0,), (0,)), ((), ()))      # contract the sublanes of both


def _dot(x, y, dims=(((1,), (0,)), ((), ())), precision=None):
    return lax.dot_general(x, y, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _by_chunk(cols, C):
    """``cols[c]`` (rows, 1) spread over chunk ``c``'s ``C`` lanes of a
    (rows, chunks x C) value."""
    rows, w = cols[0].shape[0], len(cols)
    lane = lax.broadcasted_iota(jnp.int32, (rows, w * C), 1)
    out = jnp.broadcast_to(cols[-1], lane.shape)
    for c in range(w - 2, -1, -1):
        out = jnp.where(lane < (c + 1) * C, cols[c], out)
    return out


def _within_chunk(shape, C):
    """``(j, i)`` of a (rows, chunks x C) value: the row, and the lane
    inside its chunk."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = col = lax.broadcasted_iota(jnp.int32, shape, 1)
    for c in range(1, shape[1] // C):
        col = jnp.where(lane >= c * C, lane - c * C, col)
    return row, col


def _in_chunk(shape, c, C):
    """The lanes of chunk ``c`` of a (rows, chunks x C) value."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= c * C) & (lane < (c + 1) * C)


def _diagonal(full, C):
    """The ``C x C`` blocks on the diagonal of ``full`` (w C, w C), side
    by side: (C, w C)."""
    w = full.shape[0] // C
    lane = lax.broadcasted_iota(jnp.int32, (C, w * C), 1)
    out = full[(w - 1) * C:]
    for c in range(w - 2, -1, -1):
        out = jnp.where(lane < (c + 1) * C, full[c * C:(c + 1) * C], out)
    return out


def _block_diagonal(side, C):
    """(C, w C) blocks side by side -> (w C, w C) with them on the
    diagonal and zeros elsewhere: one matrix product then serves the
    ``w`` chunks of a group."""
    w = side.shape[1] // C
    if w == 1:
        return side
    return jnp.concatenate(
        [jnp.where(_in_chunk(side.shape, c, C), side, jnp.zeros_like(side))
         for c in range(w)], axis=0)


def _unit_upper_inverse(a_ref, C, w):
    """``(I + A_c^T)^-1 = T_c^T`` of the ``w`` chunks of a group, side by
    side: ``a_ref`` (C, w C) float32 holds the strictly UPPER-triangular
    ``A_c^T`` (zeros on and under each diagonal).  Substitution on the
    right-hand side ``I`` from the last row, in blocks of
    :data:`_SOLVE_ROWS` rows: inside a block a COLUMN a step (after step
    ``i`` row ``i`` is final and ``A^T_ji x_i`` has left the block's rows
    above it: a gather along the lanes, a multiply and a subtract a
    register); then every row above the block takes the block's final
    rows at once, ``x_j -= sum_i A^T_ji x_i``, one float32 product on
    the matrix unit.  The multiplications and additions of the row-by-row
    substitution in another order, backward-stable as it is (the Neumann
    product ``(I - A)(I + A^2)...`` is not: its terms cancel from 1e10 at
    64 rows)."""
    f32, L = jnp.float32, w * C
    row, col = _within_chunk((C, L), C)
    first = lax.broadcasted_iota(jnp.int32, row.shape, 1) - col
    edges = list(range(0, C, _SUB))
    x = [(row == col).astype(f32)[r:r + _SUB] for r in edges]
    a = [a_ref[r:min(r + _SUB, C), :] for r in edges]
    for lo in range((C - 1) // _SOLVE_ROWS * _SOLVE_ROWS, -1, -_SOLVE_ROWS):
        hi = min(lo + _SOLVE_ROWS, C)
        for i in range(hi - 1, lo, -1):
            at = i // _SUB
            x_i = x[at][i % _SUB:i % _SUB + 1, :]
            for r in range(lo // _SUB, at + 1):
                # column ``i`` of each chunk over the chunk's lanes
                a_i = jnp.take_along_axis(
                    a[r], first[:a[r].shape[0]] + i, axis=1)
                x[r] = x[r] - a_i * x_i
        if lo:
            done = jnp.concatenate(x[lo // _SUB:-(-hi // _SUB)], axis=0)
            # the block's rows at the rows of ``A^T``'s columns they
            # meet, a chunk's on its own lanes: zeros elsewhere
            taken = jnp.concatenate([
                part for c in range(w) for part in (
                    jnp.zeros((lo, L), f32),
                    jnp.where(_in_chunk(done.shape, c, C), done, 0.0),
                    jnp.zeros((C - hi, L), f32)) if part.shape[0]], axis=0)
            above = _dot(a_ref[0:lo, :], taken, precision=_HIGHEST)
            for r in range(lo // _SUB):
                x[r] = x[r] - above[edges[r]:edges[r] + x[r].shape[0]]
    return x[0] if len(x) == 1 else jnp.concatenate(x, axis=0)


def _group(q_ref, k_ref, m, C, w):
    """What the value heads of a key head share in group ``m`` of ``w``
    chunks: ``q^T``, ``k^T`` (d_k, w C); ``k_j . k_i`` and ``k_j . q_i``
    of each chunk, side by side (C, w C) float32."""
    L = w * C
    lanes = pl.ds(pl.multiple_of(m * L, L), L)
    qT, kT = q_ref[0, 0, :, lanes], k_ref[0, 0, :, lanes]
    return dict(
        m=m, lanes=lanes, qT=qT, kT=kT, q32=qT.astype(jnp.float32),
        k32=kT.astype(jnp.float32), kk=_diagonal(_dot(kT, kT, _TN), C),
        qk=_diagonal(_dot(kT, qT, _TN), C))


def _decays(p, h, g_ref, gc_ref, b_ref, C, w):
    """Head ``h``'s decays in group ``p``: the rows ``G``, ``beta``,
    ``e^G``, ``e^{G_C - G}`` (1, w C); ``G_C`` of each chunk (1, 1); and
    ``e^{G_i - G_j}`` for ``j <= i`` (else 0), [j, (c, i)]."""
    L = w * C
    G = g_ref[0, 0, h:h + 1, p["lanes"]]
    beta = b_ref[0, 0, h:h + 1, p["lanes"]]
    cols = [gc_ref[0, 0, pl.ds(pl.multiple_of(p["m"] * L + c * C, C), C),
                   h:h + 1] for c in range(w)]
    last = [G[:, (c + 1) * C - 1:(c + 1) * C] for c in range(w)]
    j, i = _within_chunk((C, L), C)
    decay = jnp.exp(jnp.where(j <= i, G - _by_chunk(cols, C), -jnp.inf))
    return dict(beta=beta, last=last, decay=decay, grow=jnp.exp(G),
                to_end=jnp.exp(_by_chunk(last, C) - G), above=j < i)


def _solve(p, s, a_s, C, w):
    """``T_c^T`` of the group's chunks for one head, side by side: ``A^T``
    is put together from the float32 ``k k^T`` in ``a_s`` (a head's own:
    the heads' substitutions are independent chains the scheduler may
    interleave)."""
    a_s[...] = jnp.where(s["above"], s["beta"] * p["kk"] * s["decay"], 0.0)
    return _unit_upper_inverse(a_s, C, w)


def _solved(p, s, TT, vT, C):
    """``W^T = K_b^T T^T`` over ``U^T = V_b^T T^T`` (d_k + d_v, w C),
    float32, with ``K_b^T = k^T beta e^G`` and ``V_b^T = v^T beta``: one
    product."""
    KbT = p["k32"] * (s["beta"] * s["grow"])
    VbT = vT.astype(jnp.float32) * s["beta"]
    return _dot(jnp.concatenate([KbT, VbT], axis=0),
                _block_diagonal(TT, C), precision=_HIGHEST)


def _scale_state(last, state):
    """``e^{G_C}`` times a (d_v, d_k) state: the (1, 1) exponent as a row
    of lanes first (Mosaic broadcasts along one axis at a time)."""
    return jnp.exp(jnp.broadcast_to(last, (1, state.shape[1]))) * state


def _walk(p, s, WU, state, C, w, op):
    """The group's chunks in order from ``state`` (d_v, d_k: the state's
    transpose): ``v_new^T`` (d_v, w C) float32, the state each chunk
    started from, and the state after the last.  A product with the state
    is made over the group's width and kept on its chunk's lanes."""
    dk = p["k32"].shape[0]
    W, UT = WU[:dk].astype(op), WU[dk:]
    kG = (p["k32"] * s["to_end"]).astype(op)
    v_new, starts = None, []
    for c in range(w):
        starts.append(state)
        mine = _in_chunk(UT.shape, c, C)
        here = UT - _dot(state.astype(op), W)
        v_new = here if c == 0 else jnp.where(mine, here, v_new)
        state = _scale_state(s["last"][c], state) + _dot(
            jnp.where(mine, here, 0.0).astype(op), kG, _NT)
    return v_new, starts, state


def _head(p, h, refs, a_s, state, C, w):
    """Head ``h`` through group ``p`` from ``state``: its decays, ``T^T``,
    ``W^T`` over ``U^T``, ``v_new^T``, the state each chunk started from
    and the state after the group.  ``refs``: the blocks of ``v^T``, the
    rows ``G`` and ``beta`` and the columns ``G``."""
    v_ref, g_ref, b_ref, gc_ref = refs
    dv = v_ref.shape[2] // g_ref.shape[2]
    s = _decays(p, h, g_ref, gc_ref, b_ref, C, w)
    TT = _solve(p, s, a_s.at[h], C, w)
    WU = _solved(p, s, TT, v_ref[0, 0, h * dv:(h + 1) * dv, p["lanes"]], C)
    v_new, starts, state = _walk(p, s, WU, state, C, w, v_ref.dtype)
    return s, TT, WU, v_new, starts, state


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, gc_ref, o_ref,
                    *rest, C, w, rep, keep):
    """One (batch row, key head, tile of tokens) of the forward: the
    tile's groups of ``w`` chunks in order, ``s_s`` the (transposed)
    state of the key head's ``rep`` value heads, carried from tile to
    tile."""
    starts_ref = rest[0] if keep else None
    s_s, a_s = rest[-2:]
    f32, op = jnp.float32, v_ref.dtype
    dv, L = v_ref.shape[2] // rep, w * C

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_s[...] = jnp.zeros(s_s.shape, f32)

    if keep:
        starts_ref[0, 0, 0] = s_s[...]

    def group(m, carry):
        p = _group(q_ref, k_ref, m, C, w)
        for h in range(rep):
            s, _, _, v_new, starts, s_s[h] = _head(
                p, h, (v_ref, g_ref, b_ref, gc_ref), a_s, s_s[h], C, w)
            qG = (p["q32"] * s["grow"]).astype(op)
            o = _dot(v_new.astype(op), _block_diagonal(
                (p["qk"] * s["decay"]).astype(op), C))
            for c, state in enumerate(starts):
                o = o + jnp.where(_in_chunk(o.shape, c, C),
                                  _dot(state.astype(op), qG), 0.0)
            o_ref[0, 0, h * dv:(h + 1) * dv, p["lanes"]] = o.astype(
                o_ref.dtype)
        return carry

    lax.fori_loop(0, q_ref.shape[3] // L, group, 0)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, gc_ref, starts_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dgc_ref,
                    ds_s, s_s, a_s, t_c, wu_c, vn_c, s_c, *, C, w, rep):
    """One (batch row, key head, tile of tokens) of the backward, the
    tiles in reverse.  First the tile's groups forward from the kept
    state (``s_s``), leaving in VMEM a group's ``T^T`` (``t_c``) and a
    head's ``W^T`` over ``U^T``, ``v_new^T`` and chunk states (``wu_c``,
    ``vn_c``, ``s_c``); then the groups in reverse with
    ``ds_s``, the cotangent of the value heads' state, carried from tile
    to tile.  Written a group: ``dq^T`` and ``dk^T`` summed over the key
    head's value heads, ``dv^T``, ``dbeta`` and the cotangent of the
    running sums ``G`` in two parts the caller adds and sums back into
    ``dg``: a row a head (``dg_ref``) and, what a decay matrix ``e^{G_i -
    G_j}`` hands to its ``G_j``, a column a head (``dgc_ref``: the row
    sums of ``dA A + dP P`` strictly off the diagonal: the diagonal holds
    no ``G`` and would cancel only to rounding)."""
    f32, op = jnp.float32, v_ref.dtype
    dk_, dv, L = q_ref.shape[2], v_ref.shape[2] // rep, w * C
    ng = q_ref.shape[3] // L

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_s[...] = jnp.zeros(ds_s.shape, f32)

    s_s[...] = starts_ref[0, 0, 0]

    def forward(m, carry):
        p = _group(q_ref, k_ref, m, C, w)
        for h in range(rep):
            _, t_c[m, h], wu_c[m, h], vn_c[m, h], starts, s_s[h] = _head(
                p, h, (v_ref, g_ref, b_ref, gc_ref), a_s, s_s[h], C, w)
            for c, state in enumerate(starts):
                s_c[m * w + c, h] = state
        return carry

    lax.fori_loop(0, ng, forward, 0)

    def over_rows(x):               # (1, w C)
        return jnp.sum(x, axis=0, keepdims=True)

    def total(x):                   # (1, 1)
        return jnp.sum(over_rows(x), axis=1, keepdims=True)

    def backward(step, carry):
        m = ng - 1 - step
        p = _group(q_ref, k_ref, m, C, w)
        dq = jnp.zeros(p["q32"].shape, f32)
        dk = jnp.zeros(p["k32"].shape, f32)
        dqk = jnp.zeros((C, L), f32)
        dkk = jnp.zeros((C, L), f32)
        lane = lax.broadcasted_iota(jnp.int32, (1, L), 1)
        for h in range(rep):
            rows = slice(h * dv, (h + 1) * dv)
            s = _decays(p, h, g_ref, gc_ref, b_ref, C, w)
            beta, grow, to_end, D = (s["beta"], s["grow"], s["to_end"],
                                     s["decay"])
            TT, WU, v_new = t_c[m, h], wu_c[m, h], vn_c[m, h]
            v32 = v_ref[0, 0, rows, p["lanes"]].astype(f32)
            do = do_ref[0, 0, rows, p["lanes"]]
            W, v_in = WU[:dk_].astype(op), v_new.astype(op)
            PT = _block_diagonal((p["qk"] * D).astype(op), C)
            qG = (p["q32"] * grow).astype(op)
            kG = (p["k32"] * to_end).astype(op)
            kG32 = kG.astype(f32)
            Kh = p["k32"] * grow                             # e^G k
            # o = S^T qG + v_new P^T;  S' = e^{G_C} S + v_new kG^T
            dv_intra = _dot(do, PT, _NT)
            dP = _diagonal(_dot(v_in, do, _TN), C) * D       # dqk^T
            dstate = ds_s[h]
            dv_new = jnp.zeros(v32.shape, f32)
            dqG = jnp.zeros(p["q32"].shape, f32)
            dkG = jnp.zeros(p["k32"].shape, f32)
            dW = jnp.zeros(p["k32"].shape, f32)
            at_ends = jnp.zeros((1, L), f32)
            for c in range(w - 1, -1, -1):
                state = s_c[m * w + c, h]
                held, dnext = state.astype(op), dstate.astype(op)
                mine = _in_chunk(v32.shape, c, C)
                here = jnp.where(mine, dv_intra + _dot(dnext, kG), 0.0)
                dv_new = dv_new + here
                here = here.astype(op)
                do_c = jnp.where(mine, do, jnp.zeros_like(do))
                moved = _dot(dnext, jnp.where(mine, v_in, jnp.zeros_like(
                    v_in)), _TN)
                dqG = dqG + _dot(held, do_c, _TN)
                dkG = dkG + moved
                # v_new = U - S^T W
                dW = dW - _dot(held, here, _TN)
                dheld = _dot(do_c, qG, _NT) - _dot(here, W, _NT)
                at_ends = at_ends + jnp.where(
                    lane == (c + 1) * C - 1,
                    total(moved * kG32) + jnp.exp(s["last"][c]) * total(
                        state * dstate), 0.0)
                dstate = _scale_state(s["last"][c], dstate) + dheld
            ds_s[h] = dstate
            # W^T = K_b^T T^T, U^T = V_b^T T^T, T^T = (I + A^T)^-1
            dKV = _dot(jnp.concatenate([dW, dv_new], axis=0),
                       _block_diagonal(TT, C), _NT, precision=_HIGHEST)
            dKb, dVb = dKV[:dk_], dKV[dk_:]
            dA = -_diagonal(_dot(WU, dKV, _TN, precision=_HIGHEST), C)
            M = jnp.where(s["above"], dA * D, 0.0)
            dqk = dqk + dP
            dkk = dkk + beta * M
            M = M * p["kk"]                                  # dA kk decay
            db_ref[0, 0, h:h + 1, p["lanes"]] = (
                over_rows(M) + over_rows(dKb * Kh) + over_rows(dVb * v32))
            # what the decay matrices hand to G: + to G_i, - to G_j
            N = beta * M + jnp.where(s["above"], dP * p["qk"], 0.0)
            dg_ref[0, 0, h:h + 1, p["lanes"]] = (
                over_rows(N) + over_rows(dKb * (beta * Kh))
                + over_rows(dqG * qG.astype(f32)) - over_rows(dkG * kG32)
                + at_ends)
            for c in range(w):
                dgc_ref[0, 0, pl.ds(pl.multiple_of(m * L + c * C, C), C),
                        h:h + 1] = -jnp.sum(
                    jnp.where(_in_chunk(N.shape, c, C), N, 0.0), axis=1,
                    keepdims=True)
            dv_ref[0, 0, rows, p["lanes"]] = (beta * dVb).astype(dv_ref.dtype)
            dq = dq + dqG * grow
            dk = dk + dKb * (beta * grow) + dkG * to_end
        dqk = _block_diagonal(dqk.astype(op), C)
        dkk = _block_diagonal(dkk.astype(op), C)
        dq_ref[0, 0, :, p["lanes"]] = (
            dq + _dot(p["kT"], dqk)).astype(dq_ref.dtype)
        dk_ref[0, 0, :, p["lanes"]] = (
            dk + _dot(p["qT"], dqk, _NT) + _dot(p["kT"], dkk)
            + _dot(p["kT"], dkk, _NT)).astype(dk_ref.dtype)
        return carry

    lax.fori_loop(0, ng, backward, 0)


def _pad(n, to):
    return -(-int(n) // to) * to


def _side_by_side(nc):
    """Chunks worked side by side on the lanes: two where a tile's
    chunks pair up (two chunks of 64 fill a register of lanes)."""
    return 2 if nc % 2 == 0 else 1


def _gdn_vmem(tokens, C, rep, dk, dv, itemsize):
    """VMEM bytes of the backward kernel (the larger of the two) at
    ``tokens`` a grid step: its blocks twice (the pipeline's two
    buffers; a block's lanes padded to whole registers), its scratch,
    and what the compiler keeps of a group's values (two dozen ``d x w
    C`` float32 values and the ``w C x w C`` products)."""
    nc = tokens // C
    w = _side_by_side(nc)
    T, L = _pad(tokens, 128), _pad(w * C, 128)
    state = rep * dv * _pad(dk, 128) * 4
    blocks = (T * itemsize * (4 * dk + 3 * rep * dv)      # q k dq dk; v do dv
              + 4 * _pad(rep, 8) * T * 4                  # G beta dG dbeta
              + 2 * tokens * 128 * 4                      # G, dG columns
              + state)                                    # the tile's state
    scratch = (2 * state + rep * C * L * 4                # ds_s, s_s; a_s
               + nc // w * rep * L * 4 * (C + dk + 2 * dv)  # T; W, U, v_new
               + nc * state)                              # the chunks' states
    body = (24 * max(dk, dv) + 4 * w * C) * L * 4
    return 2 * blocks + scratch + body


def gdn_tiles(S, chunk, H_k, H_v, d_k, d_v, dtype):
    """``(tokens a grid step, value heads a grid step, VMEM bytes)`` of
    the rule's two kernels, from the operands' shapes alone: the value
    heads of one key head; the most whole chunks, at most
    :data:`_GDN_TOKENS` tokens, that divide the (padded) sequence and
    whose blocks and scratch fit the scoped VMEM a kernel gets by
    default.  On the chip a tile's tokens and the chunks worked side by
    side fill whole registers of 128 lanes, and a head's channels whole
    registers of sublanes.  Raises where nothing fits."""
    if H_v % H_k:
        raise ValueError(
            f"gated_delta_rule: {H_k} key heads do not divide {H_v} value "
            f"heads")
    C = min(chunk, S)
    n, rep, itemsize = -(-S // C), H_v // H_k, jnp.dtype(dtype).itemsize
    chip = not default_interpret()
    if chip and (d_k % (32 // itemsize) or d_v % (32 // itemsize) or C % 8):
        raise ValueError(
            f"gated_delta_rule: on the chip a head's channels and a chunk's "
            f"rows fill whole registers of sublanes; d_k {d_k}, d_v {d_v}, "
            f"chunk {C} do not")
    vmem = None
    for nc in range(min(n, max(1, _GDN_TOKENS // C)), 0, -1):
        if n % nc or (chip and (_side_by_side(nc) * C) % 128):
            continue
        vmem = _gdn_vmem(nc * C, C, rep, d_k, d_v, itemsize)
        if vmem <= VMEM_SCOPED_DEFAULT:
            return nc * C, rep, vmem
    raise ValueError(
        f"gated_delta_rule: no tile of whole chunks of {C} tokens over "
        f"{rep} value heads of {d_k} x {d_v} divides {n} chunks inside the "
        f"{VMEM_SCOPED_DEFAULT} bytes of VMEM a kernel gets"
        + (f" (the smallest needs {vmem})" if vmem else ""))


def _gdn_layout(q, k, v, g, beta, C):
    """What both kernels are called with — the tokens on the lanes, a
    head's channels on the sublanes: ``q^T``, ``k^T`` (b, H_k, d_k, S')
    and ``v^T`` (b, H_k, rep d_v, S'), the sequence padded to whole
    chunks with tokens that write nothing (``beta`` 0) and decay nothing
    (``g`` 0); the running sums ``G`` and ``beta`` a row a value head (b,
    H_k, rep, S'), and ``G`` a column a head too (b, H_k, S', rep: a
    decay matrix needs both) — with the grid, the block specs' maker and
    the sizes."""
    b, S, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    rep, n = Hv // Hk, -(-S // C)
    Sp, f32 = n * C, jnp.float32
    tokens, _, _ = gdn_tiles(S, C, Hk, Hv, dk, dv, v.dtype)
    nc, nt = tokens // C, Sp // tokens

    def padded(x):
        return jnp.pad(x, [(0, 0), (0, Sp - S)] + [(0, 0)] * (x.ndim - 2))

    def tokens_last(x):             # (b, S', H, d) -> (b, H_k, H/H_k d, S')
        return x.transpose(0, 2, 3, 1).reshape(b, Hk, -1, Sp)

    def by_key_head(x):             # (b, S', H_v) -> (b, S', H_k, rep)
        return x.reshape(b, Sp, Hk, rep)

    G = by_key_head(_block_sums(padded(g.astype(f32)), C))
    operands = (
        tokens_last(padded(q.astype(v.dtype))),
        tokens_last(padded(k.astype(v.dtype))),
        tokens_last(padded(v)),
        G.transpose(0, 2, 3, 1),
        by_key_head(padded(beta.astype(f32))).transpose(0, 2, 3, 1),
        G.transpose(0, 2, 1, 3))

    def specs(tile_of):
        """Block specs with the tile axis read through ``tile_of``."""
        def tokens_by(rows):
            return pl.BlockSpec(
                (1, 1, rows, tokens),
                lambda bi, h, i: (bi, h, 0, tile_of(i)))

        return {
            "key": tokens_by(dk), "value": tokens_by(rep * dv),
            "row": tokens_by(rep),
            "col": pl.BlockSpec(
                (1, 1, tokens, rep),
                lambda bi, h, i: (bi, h, tile_of(i), 0)),
            "state": pl.BlockSpec(
                (1, 1, 1, rep, dv, dk),
                lambda bi, h, i: (bi, h, tile_of(i), 0, 0, 0))}

    # the state is carried over the tiles: every axis in order; the
    # rule's tiles fit the default scoped VMEM, so no limit is asked for
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3)
    return operands, (b, Hk, nt), specs, params, (rep, nc, nt, Sp)


def _tokens_first(xT, S, H):
    """(b, H_k, rep d, S') -> (b, S, H, d)"""
    b, _, _, Sp = xT.shape
    return xT.reshape(b, H, -1, Sp).transpose(0, 3, 1, 2)[:, :S]


#: The wrappers are jitted in their own right: the layers of a model share
#: one lowering of each.
@functools.partial(jax.jit, static_argnames=("C", "keep", "interpret"))
def _gdn_fwd_call(q, k, v, g, beta, *, C, keep, interpret):
    """``o`` (b, S, H_v, d_v) and, where ``keep``, the (transposed) state
    each tile started from (b, H_k, tiles, rep, d_v, d_k) float32, for
    the backward."""
    b, S, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    f32 = jnp.float32
    with named_scope("gdn-scan"):
        operands, grid, specs, params, (rep, nc, nt, Sp) = _gdn_layout(
            q, k, v, g, beta, C)
        s, w = specs(lambda i: i), _side_by_side(nc)
        out_shape = [jax.ShapeDtypeStruct(operands[2].shape, v.dtype)]
        out_specs = [s["value"]]
        if keep:
            out_shape.append(
                jax.ShapeDtypeStruct((b, Hk, nt, rep, dv, dk), f32))
            out_specs.append(s["state"])
        out = pl.pallas_call(
            functools.partial(_gdn_fwd_kernel, C=C, w=w, rep=rep, keep=keep),
            out_shape=out_shape, grid=grid,
            in_specs=[s["key"], s["key"], s["value"], s["row"], s["row"],
                      s["col"]],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((rep, dv, dk), f32),             # s_s
                pltpu.VMEM((rep, C, w * C), f32)],          # a_s
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=2 * b * Sp * (2 * Hk * C * dk + Hv * (
                    C * (dk + dv) + C * C // 2 + C * dv // 2
                    + 3 * dk * dv)),
                transcendentals=b * Sp * Hv * C,
                bytes_accessed=b * Sp * v.dtype.itemsize * (
                    2 * Hk * dk + 2 * Hv * dv) + (
                    b * nt * Hv * dk * dv * 4 if keep else 0)),
            interpret=interpret, name="gdn-fwd",
        )(*operands)
        o = _tokens_first(out[0], S, Hv)
        return (o, out[1]) if keep else o


@functools.partial(jax.jit, static_argnames=("C", "interpret"))
def _gdn_bwd_call(q, k, v, g, beta, starts, do, *, C, interpret):
    b, S, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    f32 = jnp.float32
    with named_scope("gdn-scan"):
        operands, grid, specs, params, (rep, nc, nt, Sp) = _gdn_layout(
            q, k, v, g, beta, C)
        s, w = specs(lambda i: nt - 1 - i), _side_by_side(nc)
        qT, _, vT, rows, _, cols = operands
        doT = jnp.pad(do.astype(v.dtype), (
            (0, 0), (0, Sp - S), (0, 0), (0, 0))).transpose(
            0, 2, 3, 1).reshape(vT.shape)
        L, ng = w * C, nc // w
        dqT, dkT, dvT, dG, dbeta, dG_cols = pl.pallas_call(
            functools.partial(_gdn_bwd_kernel, C=C, w=w, rep=rep),
            out_shape=[
                jax.ShapeDtypeStruct(qT.shape, q.dtype),
                jax.ShapeDtypeStruct(qT.shape, k.dtype),
                jax.ShapeDtypeStruct(vT.shape, v.dtype),
                jax.ShapeDtypeStruct(rows.shape, f32),
                jax.ShapeDtypeStruct(rows.shape, f32),
                jax.ShapeDtypeStruct(cols.shape, f32)],
            grid=grid,
            in_specs=[s["key"], s["key"], s["value"], s["row"], s["row"],
                      s["col"], s["state"], s["value"]],
            out_specs=[s["key"], s["key"], s["value"], s["row"], s["row"],
                       s["col"]],
            scratch_shapes=[
                pltpu.VMEM((rep, dv, dk), f32),             # ds_s
                pltpu.VMEM((rep, dv, dk), f32),             # s_s
                pltpu.VMEM((rep, C, L), f32),               # a_s
                pltpu.VMEM((ng, rep, C, L), f32),           # t_c
                pltpu.VMEM((ng, rep, dk + dv, L), f32),     # wu_c
                pltpu.VMEM((ng, rep, dv, L), f32),          # vn_c
                pltpu.VMEM((nc, rep, dv, dk), f32)],        # s_c
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=2 * b * Sp * (6 * Hk * C * dk + Hv * (
                    4 * C * (dk + dv) + 3 * C * C // 2 + 3 * C * dv // 2
                    + 9 * dk * dv)),
                transcendentals=2 * b * Sp * Hv * C,
                bytes_accessed=b * Sp * v.dtype.itemsize * (
                    4 * Hk * dk + 3 * Hv * dv) + starts.size * 4),
            interpret=interpret, name="gdn-bwd",
        )(*operands, starts, doT)

        def heads_last(a):          # (b, H_k, rep, S') -> (b, S', H_v)
            return a.transpose(0, 3, 1, 2).reshape(b, Sp, Hv)

        # the running sums' cotangent, its two parts, back through the sums
        dG = heads_last(dG) + dG_cols.transpose(0, 2, 1, 3).reshape(
            b, Sp, Hv)
        dg = _block_sums(dG, C, back=True)
        return (_tokens_first(dqT, S, Hk), _tokens_first(dkT, S, Hk),
                _tokens_first(dvT, S, Hv), dg[:, :S].astype(g.dtype),
                heads_last(dbeta)[:, :S].astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(q, k, v, g, beta, C):
    """The chunked rule, chunks of ``C`` tokens: ``q``, ``k`` (b, S, H_k,
    d_k), ``v`` (b, S, H_v, d_v), ``g``, ``beta`` (b, S, H_v)."""
    return _gdn_fwd_call(q, k, v, g, beta, C=C, keep=False,
                         interpret=default_interpret())


def _chunked_fwd(q, k, v, g, beta, C):
    o, starts = _gdn_fwd_call(q, k, v, g, beta, C=C, keep=True,
                              interpret=default_interpret())
    o, starts = (checkpoint_name(x, GDN_RESIDUALS) for x in (o, starts))
    return o, (q, k, v, g, beta, starts)


def _chunked_bwd(C, saved, do):
    return _gdn_bwd_call(*saved, do, C=C, interpret=default_interpret())


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """``o_t = S_t^T q_t`` of the recurrence above, for every head.

    ``q``, ``k``: (b, S, H_k, d_k), already normalised and scaled; ``v``:
    (b, S, H_v, d_v) with ``H_k`` dividing ``H_v`` (value head ``j`` reads
    key head ``j // (H_v / H_k)``); ``g`` (the log of the decay, <= 0) and
    ``beta``: (b, S, H_v) float32.  ``chunk`` tokens a chunk; a sequence
    that is no multiple of it is padded with tokens that write nothing
    (``beta`` 0) and decay nothing (``g`` 0).  Returns (b, S, H_v, d_v) in
    ``v.dtype``.  Every sequence starts from a zero state: a batch row is
    one document."""
    b, S, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    if k.shape != q.shape or Hv % Hk or g.shape != (b, S, Hv) or (
            beta.shape != g.shape):
        raise ValueError(
            f"gated_delta_rule: q {q.shape}, k {k.shape}, v {v.shape}, "
            f"g {g.shape}, beta {beta.shape} do not fit together")
    C = min(chunk, S)
    tokens, heads, vmem = gdn_tiles(S, C, Hk, Hv, dk, dv, v.dtype)
    if telemetry_active():
        n = -(-S // C)
        publish_geometry("gdn_geometry", "gdn", {
            "chunk": C, "chunks": n, "key_heads": Hk, "value_heads": Hv,
            "d_k": dk, "d_v": dv, "tokens_a_step": tokens,
            "heads_a_step": heads,
            "grid_steps": b * Hk * (n * C // tokens),
            "vmem_bytes": vmem}, form="kernel")
    return _chunked(q, k, v, g, beta, C)
