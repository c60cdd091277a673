"""Kimi Delta Attention's rule (Kimi Linear, arXiv:2510.26692), chunked:
the delta rule of :mod:`chainermn_tpu.ops.gated_delta` under a decay that
is a VECTOR over a head's key channels.

A head's state ``S`` (``d_k x d_v``), a token::

    S'_t = Diag(e^{g_t}) S_(t-1)
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

(``g_t <= 0`` the log of the decay, one number a head, token and KEY
CHANNEL; ``beta_t`` in (0, 1) one number a head and token).  With ``g``
equal across a head's channels this is ``gated_delta_rule``'s recurrence.

The chunked form (:func:`kda_rule`), ``C`` tokens a chunk, ``G`` the
running sum of ``g`` from the chunk's start, ``S`` the state the chunk
starts from::

    A_ij  = beta_i sum_c k_ic k_jc e^{G_ic - G_jc}        (i > j)
    T     = (I + A)^-1
    W, U  = T (beta k e^G),  T (beta v)
    v_new = U - W S
    o     = (q e^G) S + tril(sum_c q_ic k_jc e^{G_ic - G_jc}) v_new
    S    <- Diag(e^{G_C}) S + (k e^{G_C - G})^T v_new

Under a scalar decay ``e^{G_i - G_j}`` comes out of ``k k^T`` and ``q
k^T``; a decay a channel sits INSIDE the contraction and has to be split
over the two operands, ``(k_i e^{G_i - r}) . (k_j e^{r - G_j})`` around a
reference ``r``.  One reference a chunk would need ``e^{|G|}`` over 64
tokens, past float32's range; here a chunk's rows are worked in
sub-blocks of :data:`SUB` tokens, each around ITS first token's ``G``: a
row's factor ``e^{G_i - r_I}`` is at most 1, a column's ``e^{r_I - G_j}``
is at most 1 for every earlier sub-block (``G`` only falls) and at most
``e^{(SUB - 1) |g|_max}`` inside the row's own — ``e^75`` at the
``Ling-3.0`` family's lower bound of -5, which float32 (and bfloat16:
the same exponent) holds; columns of later sub-blocks, all above the
diagonal, get a factor of 0.  Every other exponent of the form is <= 0.

Precision, as the scalar rule's: the decays, the solve (``A``'s assembly
from the float32 product, ``T``, ``W``, ``U``) and the carried state are
float32; the other products take operands in the activations' type and
accumulate in float32.

The heads' float32 side is the kernels' own (PR 52): they take what the
mixer's convolution and projections hand over — ``q``, ``k``, ``f`` in
the activations' type, a head's ``e^{A_log}`` and ``dt_bias`` — and make
in VMEM, float32, ``q / |q| / sqrt(d_k)`` and ``k / |k|`` (``|x| =
sqrt(sum_c x_c^2 + 1e-6)``: with a head's channels on the sublanes the
sum is register adds), each rounded to the activations' type where a
mixer would round them; the log-decay ``g = lower_bound sigmoid(e^{A_log}
(f + dt_bias))``; and its running sums ``G`` inside each chunk, one
float32 product at full precision of the (d_k, w C) tile with the
group's block-diagonal triangle.  No float32 array a token, head and
channel stands beside the calls.

Each pass is ONE Mosaic kernel (``kda-fwd``, ``kda-bwd``), both under the
scope ``kda-scan``, built as ``gated_delta.py``'s are (PR 37) and from
its helpers: the grid walks (batch row, pair of heads, tile of tokens),
the tiles in order (no key head is shared: the pair is two independent
chains for the scheduler to interleave, worth 4% of the forward call and
9% of the backward at the ``ling3flash`` cell's shape); the TOKENS lie
on the lanes and a head's channels on the sublanes, so every matrix
above is worked as its transpose and ``G`` is a float32 (d_k, w C)
tile; a grid step holds several chunks, worked two side by side on the
lanes (two chunks of 64 fill a register).  What
is the vector decay's: a sub-block is 16 lanes, and everything is worked
on whole tiles under lane masks — ONE tile of row factors ``e^{G -
r_own}`` serves every sub-block, each sub-block ``I`` has a tile of
column factors ``e^{r_I - G}`` (0 on the lanes of later sub-blocks), and
``k k^T`` and ``q k^T`` of a group are one product whose contraction
runs over (sub-block, channel): the keys' scaled copies stacked on the
sublanes against the rows' operands cut to their sub-block's lanes.  The
state is kept as ``S`` (d_k, d_v), not its transpose: a chunk's
``e^{G_C}`` is then a column that scales rows, and what the state's
decay hands back to ``G_C`` is a sum along the lanes.  The heads' states
(in backward their cotangents) are carried from tile to tile in VMEM.  The
forward writes ``o`` and, kept only for a backward pass, the state each
TILE started from.  The backward is written by hand: a tile first walks
its groups forward from the kept state (``T^T``, ``W^T``, ``U^T``,
``v_new^T`` and the chunks' starting states stay in VMEM), then in
reverse with the state's cotangent; the cotangent passes through the
solve as ``dA = -(T^T dW) W^T - (T^T dU) U^T`` under the triangle.  The
cotangent of ``G`` is one float32 number a token, head and channel,
summed in the kernel from every factor ``G`` enters (the rows' and the
columns' factors, ``e^G``, ``e^{G_C - G}``, ``e^{G_C}``); the reference
points carry none (``e^{G_i - r} e^{r - G_j}`` does not depend on ``r``:
what differentiation through them would add cancels to rounding).  It
goes back through the sums (the triangle transposed), the sigmoid and —
with the cotangents of the normalised ``q`` and ``k`` — the norms in
float32, UNROUNDED, before ``dq``, ``dk`` and ``df`` are written in the
activations' type; the cotangents of ``e^{A_log}`` and ``dt_bias`` are
summed from the unrounded terms, a lane, into one block a (batch row,
pair of heads) that the tiles of tokens add into.  :func:`kda_tiles` is
the one rule for the tokens a grid step holds, from the operands'
shapes.  The XLA form these kernels replaced (PR 43) lives on as the
comparison in ``benchmarks/kda_probe.py``.
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
from chainermn_tpu.ops.gated_delta import (
    _HIGHEST,
    _NT,
    _TN,
    _block_diagonal,
    _by_chunk,
    _diagonal,
    _dot,
    _in_chunk,
    _pad,
    _side_by_side,
    _unit_upper_inverse,
    _within_chunk,
)
from chainermn_tpu.ops.ssd import publish_geometry

#: Tokens a sub-block of a chunk: the distance between the reference
#: points the decays inside ``k k^T`` and ``q k^T`` are taken around (a
#: power of two: a lane's place in its sub-block is a mask of its bits).
#: ``(SUB - 1) x 5 = 75 < 88``: float32 holds ``e^75``.
SUB = 16

#: The name (``jax.ad_checkpoint.checkpoint_name``) of what the backward
#: kernel takes from the forward one — ``o`` and the state each tile
#: started from — put on them inside the ``custom_vjp``'s forward rule: a
#: rematerialised layer whose policy saves the name recomputes ``q``,
#: ``k``, ``v``, ``f``, ``beta`` and not the kernel (``remat_names``).
KDA_RESIDUALS = "kda-residuals"

#: Tokens a grid step holds at most: eight chunks of 64 (``gdn_tiles``'
#: bound: a grid step has a fixed cost, and the backward keeps a tile's
#: ``T^T``, ``W^T``, ``U^T``, ``v_new^T`` and chunk states in VMEM).
_KDA_TOKENS = 512


def _sub_block(C):
    """Tokens a sub-block of a chunk of ``C``: :data:`SUB`, or the whole
    chunk where that does not divide it."""
    return SUB if C % SUB == 0 else C


def _lanes(dk, C, w):
    """What a group's tiles are masked and gathered by, from shapes alone
    (made once a grid step, outside the loop over the groups): for a
    (d_k, w C) tile a lane (``lane``), its place in its chunk (``col``),
    its chunk's first lane (``start``) and its sub-block's (``own``);
    for a (C, w C) matrix ``[j, (c, i)]`` the triangles ``j < i`` and
    ``j <= i``; for the (d_k, 2 w C) rows' operands ``[k | q]`` the lanes
    of each sub-block (``cut``); and the group's block-diagonal triangle
    ``[s, t]`` (w C, w C) float32, 1 where ``s <= t`` in one chunk: a
    product with it sums along the lanes inside each chunk (``tri``)."""
    sub = _sub_block(C)
    _, col = _within_chunk((dk, w * C), C)
    lane = lax.broadcasted_iota(jnp.int32, col.shape, 1)
    start = lane - col
    j, i = _within_chunk((C, w * C), C)
    _, both = _within_chunk((dk, 2 * w * C), C)     # [k rows | q rows]
    return dict(lane=lane, col=col, start=start, above=j < i, upto=j <= i,
                tri=_block_diagonal(jnp.where(j <= i, 1.0, 0.0), C),
                cut=[(both >= I * sub) & (both < (I + 1) * sub)
                     for I in range(C // sub)],
                own=lane - (col & (sub - 1)) if sub < C else start)


def _unit(x):
    """``x`` (d_k, lanes) float32 over its length a lane, ``sqrt(sum_c
    x_c^2 + 1e-6)`` — the sum down the sublanes — and one over that
    length (1, lanes)."""
    r = lax.rsqrt(jnp.sum(jnp.square(x), axis=0, keepdims=True) + 1e-6)
    return x * r, r


def _unit_back(y, r, dy):
    """The cotangent of ``x`` from that of ``y = x r`` (:func:`_unit`)."""
    return r * (dy - y * jnp.sum(y * dy, axis=0, keepdims=True))


def _group(refs, at, h, m, how, G=None):
    """Group ``m`` of ``w`` chunks of the step's head ``h``.  First the
    head's float32 side, made here from the convolution's ``q^T``,
    ``k^T`` and the projection's ``f^T`` (d_k, w C) as ``KDAMixer`` made
    it beside the calls: ``q / |q| / sqrt(d_k)`` and ``k / |k|`` ROUNDED to
    the activations' type (what the products take), the log-decay ``g =
    floor sigmoid(e^{A_log} (f + dt_bias))`` — 0 on the lanes past the
    sequence's last token, which decay nothing — and its running sums
    ``G`` inside each chunk, one float32 product with the triangle (not
    made again where the caller kept ``G``).  Then, from them and
    ``beta`` (1, w C): the decays' tiles (the rows' factors, each
    sub-block's columns' factors, ``e^G``, ``e^{G_C - G}``, ``G_C`` a
    column a chunk); and ``k_j . k_i``, ``k_j . q_i`` under ``e^{G_i -
    G_j}`` of each chunk, side by side (C, w C) float32: right where
    sub-block(j) <= sub-block(i), 0 past it.  ``how``: the statics (``C``,
    ``w``, ``floor``, the sequence's length where it was padded, the
    token the tile starts at)."""
    q_ref, k_ref, f_ref, b_ref, par_ref = refs
    C, w, floor, live, first = how
    f32, L, sub = jnp.float32, w * C, _sub_block(C)
    lanes = pl.ds(pl.multiple_of(m * L, L), L)
    op = q_ref.dtype
    unit_q = _unit(q_ref[0, h, :, lanes].astype(f32))
    unit_k = _unit(k_ref[0, h, :, lanes].astype(f32))
    q32 = (unit_q[0] * q_ref.shape[2] ** -0.5).astype(op).astype(f32)
    k32 = unit_k[0].astype(op).astype(f32)
    par = par_ref[h]                                # dt_bias | e^{A_log}
    bias, rate = par[:, 0:1], par[:, 1:2]
    shifted = f_ref[0, h, :, lanes].astype(f32) + bias
    sig = jax.nn.sigmoid(rate * shifted)
    valid = None if live is None else first + m * L + at["lane"] < live
    if G is None:
        g = floor * sig
        G = _dot(g if live is None else jnp.where(valid, g, 0.0),
                 at["tri"], precision=_HIGHEST)
    beta = b_ref[0, h, :, lanes]
    col, start = at["col"], at["start"]

    def ref(idx):                       # G at lane idx[., l], on lane l
        return jnp.take_along_axis(G, idx, axis=1)

    rows = jnp.exp(G - ref(at["own"]))                      # <= 1
    rowed = jnp.concatenate([k32 * rows, q32 * rows], axis=1)
    cols, scaled, cut = [], [], []
    for I in range(C // sub):
        cols.append(jnp.exp(jnp.where(
            col < (I + 1) * sub, ref(start + I * sub) - G, -jnp.inf)))
        scaled.append((k32 * cols[I]).astype(op))
        cut.append(jnp.where(at["cut"][I], rowed, 0.0).astype(op))
    scaled_all = jnp.concatenate(scaled, axis=0)            # (nb d_k, L)
    cut_all = jnp.concatenate(cut, axis=0)                  # (nb d_k, 2 L)
    both = _dot(scaled_all, cut_all, _TN)                   # (L, 2 L)
    last = [G[:, (c + 1) * C - 1:(c + 1) * C] for c in range(w)]
    return dict(
        lanes=lanes, q32=q32, k32=k32, beta=beta, rows=rows, cols=cols,
        scaled=scaled, cut_all=cut_all, last=last, G=G,
        unit_q=unit_q, unit_k=unit_k, shifted=shifted, rate=rate, sig=sig,
        valid=valid,
        kk=_diagonal(both[:, :L], C), qk=_diagonal(both[:, L:], C),
        grow=jnp.exp(G), to_end=jnp.exp(_by_chunk(last, C) - G))


def _solved(p, at, vT, a_s, C, w):
    """``T_c^T`` of the group's chunks side by side (C, w C), and ``W^T =
    K_b^T T^T`` over ``U^T = V_b^T T^T`` (d_k + d_v, w C), float32, with
    ``K_b^T = k^T beta e^G`` and ``V_b^T = v^T beta``: ``A^T`` is put
    together from the float32 product in ``a_s``, inverted by
    substitution, and meets both right-hand sides in one product."""
    a_s[...] = jnp.where(at["above"], p["beta"] * p["kk"], 0.0)
    TT = _unit_upper_inverse(a_s, C, w)
    KbT = p["k32"] * (p["beta"] * p["grow"])
    VbT = vT.astype(jnp.float32) * p["beta"]
    return TT, _dot(jnp.concatenate([KbT, VbT], axis=0),
                    _block_diagonal(TT, C), precision=_HIGHEST)


def _walk(p, WU, state, C, w, op, read=None):
    """The group's chunks in order from ``state`` (d_k, d_v): ``v_new^T``
    (d_v, w C) float32, the state each chunk started from, and the state
    after the last.  A product with the state is made over the group's
    width and kept on its chunk's lanes; with ``read`` (d_k, w C) also
    ``S^T read`` of each chunk on its lanes (the same product, wider)."""
    dk, L = p["k32"].shape[0], w * C
    W, UT = WU[:dk].astype(op), WU[dk:]
    kG = (p["k32"] * p["to_end"]).astype(op)
    against = W if read is None else jnp.concatenate([W, read], axis=1)
    v_new, answer, starts = None, None, []
    for c in range(w):
        starts.append(state)
        mine = _in_chunk(UT.shape, c, C)
        seen = _dot(state.astype(op), against, _TN)         # S^T [W | read]
        here = UT - seen[:, :L]
        v_new = here if c == 0 else jnp.where(mine, here, v_new)
        if read is not None:
            answer = seen[:, L:] if c == 0 else jnp.where(
                mine, seen[:, L:], answer)
        state = jnp.exp(p["last"][c]) * state + _dot(
            kG, jnp.where(mine, here, 0.0).astype(op), _NT)
    return v_new, answer, starts, state


def _kda_fwd_kernel(q_ref, k_ref, v_ref, f_ref, b_ref, par_ref, o_ref,
                    *rest, C, w, floor, live, keep):
    """One (batch row, heads, tile of tokens) of the forward: the tile's
    groups of ``w`` chunks in order, ``s_s`` the heads' states, carried
    from tile to tile.  The step's heads are independent chains the
    scheduler may interleave (a substitution is 63 dependent steps)."""
    starts_ref = rest[0] if keep else None
    s_s, a_s = rest[-2:]
    f32, op = jnp.float32, v_ref.dtype
    L, heads = w * C, q_ref.shape[1]
    at = _lanes(q_ref.shape[2], C, w)
    refs = (q_ref, k_ref, f_ref, b_ref, par_ref)
    how = (C, w, floor, live, pl.program_id(2) * q_ref.shape[3])

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_s[...] = jnp.zeros(s_s.shape, f32)

    if keep:
        starts_ref[0, :, 0] = s_s[...]

    def group(m, carry):
        for h in range(heads):
            p = _group(refs, at, h, m, how)
            _, WU = _solved(p, at, v_ref[0, h, :, p["lanes"]], a_s.at[h],
                            C, w)
            v_new, inter, _, s_s[h] = _walk(
                p, WU, s_s[h], C, w, op,
                read=(p["q32"] * p["grow"]).astype(op))
            PT = jnp.where(at["upto"], p["qk"], 0.0).astype(op)
            o = inter + _dot(v_new.astype(op), _block_diagonal(PT, C))
            o_ref[0, h, :, p["lanes"]] = o.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, q_ref.shape[3] // L, group, 0)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, f_ref, b_ref, par_ref, starts_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, df_ref, db_ref,
                    dbias_ref, drate_ref,
                    ds_s, s_s, a_s, t_c, wu_c, vn_c, s_c, g_c, *, C, w,
                    floor, live):
    """One (batch row, heads, tile of tokens) of the backward, the tiles in
    reverse.  First the tile's groups forward from the kept state
    (``s_s``), leaving in VMEM a group's ``T^T`` (``t_c``), ``W^T`` over
    ``U^T`` (``wu_c``), ``v_new^T`` (``vn_c``), running sums ``G``
    (``g_c``) and the chunks' starting states (``s_c``); then the groups
    in reverse with ``ds_s``, the cotangent of the state, carried from
    tile to tile.  A group's cotangents of the normalised ``q``, ``k`` and
    of ``G`` (d_k, w C) are taken on, float32 and unrounded, through the
    norms, the sums (the triangle transposed) and the sigmoid.  Written a
    group: ``dq^T``, ``dk^T``, ``df^T``, ``dv^T``, ``dbeta``; summed a
    lane over the tiles of a (batch row, heads), float32: the cotangents
    of ``dt_bias`` (``dbias_ref``) and of ``e^{A_log}`` a channel
    (``drate_ref``), which the caller sums up."""
    f32, op = jnp.float32, v_ref.dtype
    dk_, L, heads = q_ref.shape[2], w * C, q_ref.shape[1]
    ng = q_ref.shape[3] // L
    refs = (q_ref, k_ref, f_ref, b_ref, par_ref)
    at = _lanes(dk_, C, w)
    tile = pl.num_programs(2) - 1 - pl.program_id(2)
    how = (C, w, floor, live, tile * q_ref.shape[3])

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_s[...] = jnp.zeros(ds_s.shape, f32)
        dbias_ref[...] = jnp.zeros(dbias_ref.shape, f32)
        drate_ref[...] = jnp.zeros(drate_ref.shape, f32)

    s_s[...] = starts_ref[0, :, 0]

    def forward(m, carry):
        for h in range(heads):
            p = _group(refs, at, h, m, how)
            g_c[m, h] = p["G"]
            t_c[m, h], wu_c[m, h] = _solved(
                p, at, v_ref[0, h, :, p["lanes"]], a_s.at[h], C, w)
            vn_c[m, h], _, starts, s_s[h] = _walk(
                p, wu_c[m, h], s_s[h], C, w, op)
            for c, state in enumerate(starts):
                s_c[m * w + c, h] = state
        return carry

    lax.fori_loop(0, ng, forward, 0)

    def over_rows(x):               # (1, w C)
        return jnp.sum(x, axis=0, keepdims=True)

    def along(x):                   # (rows, 1)
        return jnp.sum(x, axis=1, keepdims=True)

    def backward(step, carry):
        for h in range(heads):
            head(ng - 1 - step, h)
        return carry

    def head(m, h):
        p = _group(refs, at, h, m, how, G=g_c[m, h])
        q32, k32, beta, rows = p["q32"], p["k32"], p["beta"], p["rows"]
        grow, to_end = p["grow"], p["to_end"]
        TT, WU, v_new = t_c[m, h], wu_c[m, h], vn_c[m, h]
        v32 = v_ref[0, h, :, p["lanes"]].astype(f32)
        do = do_ref[0, h, :, p["lanes"]]
        W, v_in = WU[:dk_].astype(op), v_new.astype(op)
        PT = _block_diagonal(
            jnp.where(at["upto"], p["qk"], 0.0).astype(op), C)
        qG32, kG32 = q32 * grow, k32 * to_end
        qG, kG = qG32.astype(op), kG32.astype(op)
        Kh = k32 * grow                                      # e^G k
        # o = S^T qG + v_new P^T;  S' = e^{G_C} S + kG v_new^T
        dv_intra = _dot(do, PT, _NT)
        dP = jnp.where(at["upto"], _diagonal(_dot(v_in, do, _TN), C), 0.0)
        dstate = ds_s[h]
        dv_new = jnp.zeros(v32.shape, f32)
        dqG = jnp.zeros(q32.shape, f32)
        dkG = jnp.zeros(k32.shape, f32)
        dW = jnp.zeros(k32.shape, f32)
        at_ends = jnp.zeros(k32.shape, f32)
        for c in range(w - 1, -1, -1):
            state = s_c[m * w + c, h]
            held, dnext = state.astype(op), dstate.astype(op)
            mine = _in_chunk(v32.shape, c, C)
            here = jnp.where(mine, dv_intra + _dot(dnext, kG, _TN), 0.0)
            dv_new = dv_new + here
            here = here.astype(op)
            do_c = jnp.where(mine, do, jnp.zeros_like(do))
            moved = _dot(dnext, jnp.where(mine, v_in, jnp.zeros_like(v_in)))
            dqG = dqG + _dot(held, do_c)
            dkG = dkG + moved
            # v_new = U - S^T W
            dW = dW - _dot(held, here)
            dheld = _dot(qG, do_c, _NT) - _dot(W, here, _NT)
            decay = jnp.exp(p["last"][c])                    # (d_k, 1)
            at_ends = at_ends + jnp.where(
                at["lane"] == (c + 1) * C - 1,
                along(moved * kG32) + decay * along(state * dstate), 0.0)
            dstate = decay * dstate + dheld
        ds_s[h] = dstate
        # W^T = K_b^T T^T, U^T = V_b^T T^T, T^T = (I + A^T)^-1
        dKV = _dot(jnp.concatenate([dW, dv_new], axis=0),
                   _block_diagonal(TT, C), _NT, precision=_HIGHEST)
        dKb, dVb = dKV[:dk_], dKV[dk_:]
        dA = -_diagonal(_dot(WU, dKV, _TN, precision=_HIGHEST), C)
        M = jnp.where(at["above"], dA, 0.0)                  # A^T = beta kk
        db_ref[0, h, :, p["lanes"]] = (
            over_rows(M * p["kk"]) + over_rows(dKb * Kh)
            + over_rows(dVb * v32))
        # kk, qk = sum_I scaled_I^T [cut_I(k rows) | cut_I(q rows)]
        D = jnp.concatenate(
            [_block_diagonal((beta * M).astype(op), C),
             _block_diagonal(dP.astype(op), C)], axis=1)     # (L_j, 2 L_i)
        dscaled = _dot(p["cut_all"], D, _NT)                 # (nb d_k, L_j)
        dk_cols = jnp.zeros(k32.shape, f32)
        dcut = None
        for I, (cols, scaled) in enumerate(zip(p["cols"], p["scaled"])):
            dk_cols = dk_cols + dscaled[I * dk_:(I + 1) * dk_] * cols
            here = _dot(scaled, D)                           # (d_k, 2 L_i)
            dcut = here if I == 0 else jnp.where(at["cut"][I], here, dcut)
        dKR, dQR = dcut[:, :L], dcut[:, L:]
        dv_ref[0, h, :, p["lanes"]] = (beta * dVb).astype(dv_ref.dtype)
        # the norms: the cotangents of the rounded ``q`` and ``k`` taken
        # on as they are
        dq_ref[0, h, :, p["lanes"]] = _unit_back(
            *p["unit_q"], (dqG * grow + dQR * rows) * dk_ ** -0.5
        ).astype(dq_ref.dtype)
        dk_ref[0, h, :, p["lanes"]] = _unit_back(
            *p["unit_k"], dKb * (beta * grow) + dkG * to_end + dKR * rows
            + dk_cols).astype(dk_ref.dtype)
        # what each factor hands to G: the rows' +, the columns' -; the
        # reference points none.  Then back through the sums (the
        # triangle transposed) and the sigmoid
        dG = (rows * (dKR * k32 + dQR * q32) - k32 * dk_cols
              + dKb * (beta * Kh) + dqG * qG32 - dkG * kG32 + at_ends)
        dg = _dot(dG, at["tri"], _NT, precision=_HIGHEST)
        if live is not None:
            dg = jnp.where(p["valid"], dg, 0.0)
        sig = p["sig"]
        dpre = dg * (floor * sig * (1.0 - sig))
        df = dpre * p["rate"]
        df_ref[0, h, :, p["lanes"]] = df.astype(df_ref.dtype)
        dbias_ref[0, h] += df
        drate_ref[0, h] += dpre * p["shifted"]

    lax.fori_loop(0, ng, backward, 0)


def _kda_vmem(tokens, C, heads, dk, dv, itemsize):
    """VMEM bytes of the backward kernel (the larger of the two) at
    ``tokens`` and ``heads`` a grid step: its blocks twice (the
    pipeline's two buffers; a block's lanes padded to whole registers)
    and its scratch a head, and what the compiler keeps of a group's
    values (three and a half dozen ``d x w C`` float32 values, the
    sub-blocks' stacked operands, the ``w C x 2 w C`` products and the
    triangle)."""
    nc = tokens // C
    w, nb = _side_by_side(nc), C // _sub_block(C)
    T, L = _pad(tokens, 128), _pad(w * C, 128)
    state = dk * _pad(dv, 128) * 4
    blocks = (T * itemsize * (6 * dk + 3 * dv)    # q k f dq dk df; v do dv
              + 2 * 8 * T * 4                     # beta, dbeta
              + dk * 128 * 4 + 2 * dk * L * 4     # dt_bias | e^A; their sums
              + state)                            # the tile's state
    scratch = (2 * state + C * L * 4                      # ds_s, s_s; a_s
               + nc // w * L * 4 * (C + 2 * dk + 2 * dv)  # T; W, U; G; v_new
               + nc * state)                              # the chunks' states
    body = (42 * max(dk, dv) + 6 * nb * dk + 8 * w * C) * L * 4
    return heads * (2 * blocks + scratch) + body


def kda_tiles(S, chunk, H, d_k, d_v, dtype):
    """``(tokens a grid step, heads a grid step, VMEM bytes)`` of the
    rule's two kernels, from the operands' shapes alone: the most whole
    chunks, at most :data:`_KDA_TOKENS` tokens, that divide the (padded)
    sequence, and two heads where the heads pair up (no key head is
    shared: the pair is there for the scheduler, two independent chains
    of substitution steps to interleave), one where not or where two do
    not fit — whose blocks and scratch fit the scoped VMEM a kernel gets
    by default.  On the chip a tile's tokens and the chunks worked side by
    side fill whole registers of 128 lanes, and a head's channels whole
    registers of sublanes.  Raises where nothing fits."""
    C = min(chunk, S)
    n, itemsize = -(-S // C), jnp.dtype(dtype).itemsize
    chip = not default_interpret()
    if chip and (d_k % (32 // itemsize) or d_v % (32 // itemsize) or C % 8):
        raise ValueError(
            f"kda_rule: on the chip a head's channels and a chunk's rows "
            f"fill whole registers of sublanes; d_k {d_k}, d_v {d_v}, "
            f"chunk {C} do not")
    vmem = None
    for nc in range(min(n, max(1, _KDA_TOKENS // C)), 0, -1):
        if n % nc or (chip and (_side_by_side(nc) * C) % 128):
            continue
        for heads in (2, 1) if H % 2 == 0 else (1,):
            vmem = _kda_vmem(nc * C, C, heads, d_k, d_v, itemsize)
            if vmem <= VMEM_SCOPED_DEFAULT:
                return nc * C, heads, vmem
    raise ValueError(
        f"kda_rule: no tile of whole chunks of {C} tokens over a head of "
        f"{d_k} x {d_v} divides {n} chunks inside the {VMEM_SCOPED_DEFAULT} "
        f"bytes of VMEM a kernel gets"
        + (f" (the smallest needs {vmem})" if vmem else ""))


def _kda_layout(q, k, v, f, beta, rate, bias, C):
    """What both kernels are called with — the tokens on the lanes, a
    head's channels on the sublanes: ``q^T``, ``k^T``, ``f^T`` (b, H,
    d_k, S') and ``v^T`` (b, H, d_v, S') in the activations' type, the
    sequence padded to whole chunks with tokens that write nothing
    (``beta`` 0; the kernels give them no decay); ``beta`` (b, H, 1, S')
    and a head's ``dt_bias`` beside its ``e^{A_log}`` (H, d_k, 2)
    float32 — with the grid, the block specs' maker and the sizes."""
    b, S, H, dk = q.shape
    dv = v.shape[3]
    n, f32 = -(-S // C), jnp.float32
    Sp = n * C
    tokens, heads, _ = kda_tiles(S, C, H, dk, dv, v.dtype)
    nc, nt = tokens // C, Sp // tokens
    L = _side_by_side(nc) * C

    def tokens_last(x):             # (b, S, H, d) -> (b, H, d, S')
        x = jnp.pad(x, [(0, 0), (0, Sp - S), (0, 0), (0, 0)])
        return x.transpose(0, 2, 3, 1)

    operands = (
        tokens_last(q.astype(v.dtype)), tokens_last(k.astype(v.dtype)),
        tokens_last(v), tokens_last(f.astype(v.dtype)),
        tokens_last(beta.astype(f32)[..., None]),
        jnp.stack([bias.astype(f32).reshape(H, dk), jnp.broadcast_to(
            rate.astype(f32)[:, None], (H, dk))], axis=-1))

    def specs(tile_of):
        """Block specs with the tile axis read through ``tile_of``."""
        def tokens_by(rows):
            return pl.BlockSpec(
                (1, heads, rows, tokens),
                lambda bi, h, i: (bi, h, 0, tile_of(i)))

        return {
            "key": tokens_by(dk), "value": tokens_by(dv),
            "row": tokens_by(1),
            "head": pl.BlockSpec((heads, dk, 2), lambda bi, h, i: (h, 0, 0)),
            # one block a (batch row, heads): the tiles add into it
            "sum": pl.BlockSpec(
                (1, heads, dk, L), lambda bi, h, i: (bi, h, 0, 0)),
            "state": pl.BlockSpec(
                (1, heads, 1, dk, dv),
                lambda bi, h, i: (bi, h, tile_of(i), 0, 0))}

    # the state is carried over the tiles: every axis in order; the
    # rule's tiles fit the default scoped VMEM, so no limit is asked for
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3)
    live = S if Sp > S else None
    return operands, (b, H // heads, nt), specs, params, (
        heads, nc, nt, Sp, live)


def _tokens_first(xT, S):
    """(b, H, d, S') -> (b, S, H, d)"""
    return xT.transpose(0, 3, 1, 2)[:, :S]


def _matrix_flops(C, dk, dv):
    """Matrix FLOPs a token and head of the forward kernel: the
    sub-blocks' product, the solve's right-hand sides, the state's read,
    write and answer, ``tril(q k^T) v_new``."""
    return 2 * (2 * C // _sub_block(C) * dk * C + C * (dk + dv)
                + 3 * dk * dv + C * dv)


#: The wrappers are jitted in their own right: the layers of a model share
#: one lowering of each.
@functools.partial(jax.jit,
                   static_argnames=("C", "floor", "keep", "interpret"))
def _kda_fwd_call(q, k, v, f, beta, rate, bias, *, C, floor, keep,
                  interpret):
    """``o`` (b, S, H, d_v) and, where ``keep``, the state each tile
    started from (b, H, tiles, d_k, d_v) float32, for the backward."""
    b, S, H, dk = q.shape
    dv = v.shape[3]
    f32 = jnp.float32
    with named_scope("kda-scan"):
        operands, grid, specs, params, (heads, nc, nt, Sp, live) = (
            _kda_layout(q, k, v, f, beta, rate, bias, C))
        s, w = specs(lambda i: i), _side_by_side(nc)
        out_shape = [jax.ShapeDtypeStruct(operands[2].shape, v.dtype)]
        out_specs = [s["value"]]
        if keep:
            out_shape.append(jax.ShapeDtypeStruct((b, H, nt, dk, dv), f32))
            out_specs.append(s["state"])
        out = pl.pallas_call(
            functools.partial(_kda_fwd_kernel, C=C, w=w, floor=floor,
                              live=live, keep=keep),
            out_shape=out_shape, grid=grid,
            in_specs=[s["key"], s["key"], s["value"], s["key"], s["row"],
                      s["head"]],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((heads, dk, dv), f32),           # s_s
                pltpu.VMEM((heads, C, w * C), f32)],        # a_s
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=b * Sp * H * (
                    _matrix_flops(C, dk, dv) + 2 * dk * w * C),
                transcendentals=b * Sp * H * dk * (
                    4 + C // _sub_block(C)),
                bytes_accessed=b * Sp * H * (
                    v.dtype.itemsize * (3 * dk + 2 * dv) + 4) + (
                    b * nt * H * dk * dv * 4 if keep else 0)),
            interpret=interpret, name="kda-fwd",
        )(*operands)
        o = _tokens_first(out[0], S)
        return (o, out[1]) if keep else o


@functools.partial(jax.jit, static_argnames=("C", "floor", "interpret"))
def _kda_bwd_call(q, k, v, f, beta, rate, bias, starts, do, *, C, floor,
                  interpret):
    b, S, H, dk = q.shape
    dv = v.shape[3]
    f32 = jnp.float32
    with named_scope("kda-scan"):
        operands, grid, specs, params, (heads, nc, nt, Sp, live) = (
            _kda_layout(q, k, v, f, beta, rate, bias, C))
        s, w = specs(lambda i: nt - 1 - i), _side_by_side(nc)
        qT, _, vT, _, rows, _ = operands
        doT = jnp.pad(do.astype(v.dtype), (
            (0, 0), (0, Sp - S), (0, 0), (0, 0))).transpose(0, 2, 3, 1)
        L, ng = w * C, nc // w
        sums = jax.ShapeDtypeStruct((b, H, dk, L), f32)
        dqT, dkT, dvT, dfT, dbeta, dbias, drate = pl.pallas_call(
            functools.partial(_kda_bwd_kernel, C=C, w=w, floor=floor,
                              live=live),
            out_shape=[
                jax.ShapeDtypeStruct(qT.shape, q.dtype),
                jax.ShapeDtypeStruct(qT.shape, k.dtype),
                jax.ShapeDtypeStruct(vT.shape, v.dtype),
                jax.ShapeDtypeStruct(qT.shape, f.dtype),
                jax.ShapeDtypeStruct(rows.shape, f32), sums, sums],
            grid=grid,
            in_specs=[s["key"], s["key"], s["value"], s["key"], s["row"],
                      s["head"], s["state"], s["value"]],
            out_specs=[s["key"], s["key"], s["value"], s["key"], s["row"],
                       s["sum"], s["sum"]],
            scratch_shapes=[
                pltpu.VMEM((heads, dk, dv), f32),           # ds_s
                pltpu.VMEM((heads, dk, dv), f32),           # s_s
                pltpu.VMEM((heads, C, L), f32),             # a_s
                pltpu.VMEM((ng, heads, C, L), f32),         # t_c
                pltpu.VMEM((ng, heads, dk + dv, L), f32),   # wu_c
                pltpu.VMEM((ng, heads, dv, L), f32),        # vn_c
                pltpu.VMEM((nc, heads, dk, dv), f32),       # s_c
                pltpu.VMEM((ng, heads, dk, L), f32)],       # g_c
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=b * Sp * H * (
                    4 * _matrix_flops(C, dk, dv) + 4 * dk * L),
                transcendentals=2 * b * Sp * H * dk * (
                    4 + C // _sub_block(C)),
                bytes_accessed=b * Sp * H * (
                    v.dtype.itemsize * (6 * dk + 3 * dv) + 8)
                + starts.size * 4 + 2 * b * H * dk * L * 4),
            interpret=interpret, name="kda-bwd",
        )(*operands, starts, doT)
        # a lane's sums over the tiles: the lanes, and the batch rows, here
        return (_tokens_first(dqT, S), _tokens_first(dkT, S),
                _tokens_first(dvT, S), _tokens_first(dfT, S),
                _tokens_first(dbeta, S)[..., 0].astype(beta.dtype),
                jnp.sum(drate, axis=(0, 2, 3)).astype(rate.dtype),
                jnp.sum(dbias, axis=(0, 3)).reshape(bias.shape).astype(
                    bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _chunked(q, k, v, f, beta, rate, bias, C, floor):
    """The chunked rule, chunks of ``C`` tokens: ``q``, ``k``, ``f`` (b,
    S, H, d_k), ``v`` (b, S, H, d_v), ``beta`` (b, S, H), ``rate`` (H,),
    ``bias`` (H, d_k)."""
    return _kda_fwd_call(q, k, v, f, beta, rate, bias, C=C, floor=floor,
                         keep=False, interpret=default_interpret())


def _chunked_fwd(q, k, v, f, beta, rate, bias, C, floor):
    o, starts = _kda_fwd_call(q, k, v, f, beta, rate, bias, C=C,
                              floor=floor, keep=True,
                              interpret=default_interpret())
    o, starts = (checkpoint_name(x, KDA_RESIDUALS) for x in (o, starts))
    return o, (q, k, v, f, beta, rate, bias, starts)


def _chunked_bwd(C, floor, saved, do):
    return _kda_bwd_call(*saved, do, C=C, floor=floor,
                         interpret=default_interpret())


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def kda_rule(q, k, v, f, beta, rate, dt_bias, *, lower_bound: float,
             chunk: int = 64):
    """``o_t = S_t^T q_t`` of the recurrence above, for every head, from
    what the mixer's convolution and projections hand over: the kernels
    make the heads' float32 side themselves.

    ``q``, ``k``: (b, S, H, d_k), NOT normalised — the rule runs on ``q /
    |q| / sqrt(d_k)`` and ``k / |k|``, ``|x| = sqrt(sum x^2 + 1e-6)``,
    each rounded to ``v``'s type; ``v``: (b, S, H, d_v); ``f``: (b, S, H,
    d_k), ``rate`` (H,) positive (``exp(A_log)``) and ``dt_bias`` (H,
    d_k), which give the log of the decay ``g = lower_bound *
    sigmoid(rate * (f + dt_bias))`` a head, token and key channel;
    ``beta``: (b, S, H).  ``chunk`` tokens a chunk (a multiple of
    :data:`SUB`, or one sub-block); a sequence that is no multiple of it
    is padded with tokens that write nothing (``beta`` 0) and decay
    nothing (``g`` 0).  ``lower_bound`` no lower than ``-88 / (SUB - 1)``
    keeps every factor inside float32 (the family's is -5).  ``q``,
    ``k`` and ``f`` are read in ``v``'s type; the norms, ``g``, its
    running sums and ``beta`` are float32, and so are the cotangents on
    their way back through them.  Returns (b, S, H, d_v) in ``v.dtype``.
    Every sequence starts from a zero state: a batch row is one
    document."""
    b, S, H, dk = q.shape
    dv = v.shape[3]
    if k.shape != q.shape or v.shape[:3] != q.shape[:3] or (
            f.shape != q.shape or beta.shape != q.shape[:3]
            or rate.shape != (H,) or dt_bias.shape != (H, dk)):
        raise ValueError(
            f"kda_rule: q {q.shape}, k {k.shape}, v {v.shape}, f {f.shape}, "
            f"beta {beta.shape}, rate {rate.shape}, dt_bias {dt_bias.shape} "
            f"do not fit together")
    if not -88.0 / (SUB - 1) <= lower_bound < 0:
        raise ValueError(
            f"kda_rule: lower_bound {lower_bound} is outside "
            f"[-88 / (SUB - 1), 0): a column's factor would leave float32")
    C = min(chunk, S)
    tokens, heads, vmem = kda_tiles(S, C, H, dk, dv, v.dtype)
    if telemetry_active():
        n = -(-S // C)
        publish_geometry("kda_geometry", "kda", {
            "chunk": C, "chunks": n, "heads": H, "d_k": dk, "d_v": dv,
            "sub_block": _sub_block(C), "tokens_a_step": tokens,
            "heads_a_step": heads,
            "grid_steps": b * (H // heads) * (n * C // tokens),
            "vmem_bytes": vmem,
            # the forward's: q, k, f, v as handed over and the beta row
            "operand_bytes_a_step": heads * tokens * (
                v.dtype.itemsize * (3 * dk + dv) + 4)},
            form="kernel", gate_side="kernel")
    return _chunked(q, k, v, f, beta.astype(jnp.float32),
                    rate.astype(jnp.float32), dt_bias.astype(jnp.float32),
                    C, float(lower_bound))
