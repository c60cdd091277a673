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

XLA ops under the scope ``kda-scan`` (the scalar rule came so in PR 36
and became two Mosaic kernels in PR 37): ``T`` by substitution on 16-row
diagonal blocks joined pairwise, the batch on the lanes; a ``lax.scan``
step a chunk over the carried state; autodiff's backward.  Every head
given is worked at once: a caller with many heads and a long sequence
works them in groups (:func:`heads_a_group`; ``KDAMixer`` does, with its
float32 gate side inside the group, so that one group's chunk matrices
and float32 copies are live at a time).  ``gated_delta.py``'s helpers are the bodies of its
kernels (refs, transposed tiles) and none fits here: this file shares its
geometry record and nothing else, and leaves ``gdn-fwd`` / ``gdn-bwd``
as they are.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from chainermn_tpu.observability.spans import named_scope, telemetry_active
from chainermn_tpu.ops.ssd import publish_geometry

_HIGHEST = lax.Precision.HIGHEST

#: Tokens a sub-block of a chunk: the distance between the reference
#: points the decays inside ``k k^T`` and ``q k^T`` are taken around.
#: ``(SUB - 1) x 5 = 75 < 88``: float32 holds ``e^75``.
SUB = 16

#: Side of the diagonal blocks inverted by substitution, a row a step;
#: larger blocks are put together from their halves.
_BASE = 16

#: Tokens x heads a group of heads holds at most (16,384 tokens: 4 heads,
#: about 0.7 GB of chunk matrices between the passes).
_GROUP_TOKEN_HEADS = 16384 * 4


def heads_a_group(tokens: int, heads: int) -> int:
    """Heads a caller works together: the most that divide ``heads`` with
    ``tokens x heads`` within :data:`_GROUP_TOKEN_HEADS` (at least one)."""
    return max([h for h in range(1, heads + 1)
                if heads % h == 0 and tokens * h <= _GROUP_TOKEN_HEADS],
               default=1)


def _substitute(a):
    """``(I + a)^-1`` by forward substitution, a row a step: row ``i`` is
    ``e_i - sum_{j<i} a_ij row_j``.  ``a``: (m, m, N), strictly lower
    triangular in its first two axes, the batch LAST (on the lanes)."""
    m, _, N = a.shape
    eye = jnp.eye(m, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0][:, None], (m, N))]
    for i in range(1, m):
        done = jnp.stack(rows)                              # (i, m, N)
        rows.append(eye[i][:, None]
                    - jnp.sum(a[i, :i, None, :] * done, axis=0))
    return jnp.stack(rows)


def _mm(x, y):
    """``x @ y`` over the first two axes, the batch last: float32
    multiplies and adds, no matrix unit (the blocks are 16 or 32 wide)."""
    return jnp.sum(x[:, :, None, :] * y[None, :, :, :], axis=1)


def _inverse(a):
    n, _, N = a.shape
    if n <= _BASE or n % 2:
        return _substitute(a)
    h = n // 2
    # Both halves' diagonal blocks side by side on the batch axis; then
    # [[T11, 0], [-T22 A21 T11, T22]].
    both = _inverse(jnp.concatenate([a[:h, :h], a[h:, h:]], axis=-1))
    t11, t22 = both[..., :N], both[..., N:]
    t21 = -_mm(_mm(t22, a[h:, :h]), t11)
    top = jnp.concatenate([t11, jnp.zeros_like(t21)], axis=1)
    return jnp.concatenate(
        [top, jnp.concatenate([t21, t22], axis=1)], axis=0)


def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., n, n) strictly lower triangular
    (what lies on or above the diagonal is NOT read as zero: the caller
    masks it), float32.  Substitution on the diagonal blocks of 16 rows,
    the blocks joined pairwise by ``-T22 A21 T11``: backward-stable as
    substitution is.  Worked with the batch on the last axis, so that a
    step's small rows fill whole registers of lanes."""
    lead, n = a.shape[:-2], a.shape[-1]
    flat = jnp.moveaxis(a.reshape((-1, n, n)), 0, -1)
    return jnp.moveaxis(_inverse(flat), -1, 0).reshape(lead + (n, n))


def _chunked(q, k, v, g, beta, C):
    """The chunked rule for heads that all fit at once: ``q``, ``k`` (b,
    S, H, d_k), ``v`` (b, S, H, d_v), ``g`` (b, S, H, d_k) and ``beta``
    (b, S, H) float32, chunks of ``C`` tokens."""
    b, S, H, dk = q.shape
    dv = v.shape[-1]
    n = -(-S // C)
    f32, dt = jnp.float32, v.dtype
    pad = n * C - S
    sub = SUB if C % SUB == 0 else C
    nb = C // sub

    def chunks(x):
        """(b, S, H, ...) -> (b, H, n, C, ...)"""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta))
    q32, k32 = qc.astype(f32), kc.astype(f32)

    G = jnp.cumsum(gc, axis=3)                        # (b, H, n, C, dk)
    row = jnp.arange(C)
    below = row[:, None] > row[None, :]
    upto = row[:, None] >= row[None, :]

    def blocks(x):
        """(b, H, n, C, dk) -> (b, H, n, nb, sub, dk)"""
        return x.reshape(x.shape[:3] + (nb, sub, dk))

    Gb = blocks(G)
    ref = Gb[..., :1, :]                              # (b, H, n, nb, 1, dk)
    rows = jnp.exp(Gb - ref)                          # <= 1
    # e^{r_I - G_j} for the columns of sub-blocks up to I, 0 past them
    seen = (row[None, :] // sub <= jnp.arange(nb)[:, None])[..., None]
    cols = jnp.where(seen, jnp.exp(jnp.where(
        seen, ref - G[:, :, :, None], 0.0)), 0.0)     # (b, H, n, nb, C, dk)
    k_cols = (k32[:, :, :, None] * cols).astype(dt)

    def against_the_columns(x32):
        out = jnp.einsum("bhnIic,bhnIjc->bhnIij",
                         (blocks(x32) * rows).astype(dt), k_cols,
                         preferred_element_type=f32)
        return out.reshape(out.shape[:3] + (C, C))

    A = jnp.where(below, bc[..., None] * against_the_columns(k32), 0.0)
    P = jnp.where(upto, against_the_columns(q32), 0.0).astype(dt)
    T = unit_lower_inverse(A)
    eG = jnp.exp(G)
    rhs = jnp.concatenate(
        [bc[..., None] * eG * k32, bc[..., None] * vc.astype(f32)], axis=-1)
    WU = jnp.einsum("bhnij,bhnjd->bhnid", T, rhs, precision=_HIGHEST)
    W, U = WU[..., :dk].astype(dt), WU[..., dk:]
    qG = (q32 * eG).astype(dt)
    last = G[..., -1, :]                              # (b, H, n, dk)
    kG = (k32 * jnp.exp(last[..., None, :] - G)).astype(dt)

    def step(state, now):
        W_c, U_c, qG_c, kG_c, P_c, keep = now
        held = state.astype(dt)
        v_new = U_c - jnp.einsum("bhck,bhkv->bhcv", W_c, held,
                                 preferred_element_type=f32)
        v_in = v_new.astype(dt)
        o = jnp.einsum("bhck,bhkv->bhcv", qG_c, held,
                       preferred_element_type=f32) + jnp.einsum(
            "bhij,bhjv->bhiv", P_c, v_in, preferred_element_type=f32)
        state = keep[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", kG_c, v_in, preferred_element_type=f32)
        return state, o.astype(dt)

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    _, o = lax.scan(
        step, jnp.zeros((b, H, dk, dv), f32),
        tuple(by_chunk(x) for x in (W, U, qG, kG, P, jnp.exp(last))))
    # (n, b, H, C, d_v) -> (b, S, H, d_v)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * C, H, dv)
    return o[:, :S]


def kda_rule(q, k, v, g, beta, *, chunk: int = 64):
    """``o_t = S_t^T q_t`` of the recurrence above, for every head.

    ``q``, ``k``: (b, S, H, d_k), already normalised and scaled; ``v``:
    (b, S, H, d_v); ``g`` (the log of the decay, <= 0): (b, S, H, d_k);
    ``beta``: (b, S, H).  ``chunk`` tokens a chunk (a multiple of
    :data:`SUB`, or one sub-block); a sequence that is no multiple of it
    is padded with tokens that write nothing (``beta`` 0) and decay
    nothing (``g`` 0).  ``g`` no lower than ``-88 / (SUB - 1)`` keeps
    every factor inside float32 (the family's gate is bounded at -5).
    Returns (b, S, H, d_v) in ``v.dtype``.  Every sequence starts from a
    zero state: a batch row is one document."""
    b, S, H, dk = q.shape
    dv = v.shape[3]
    if k.shape != q.shape or v.shape[:3] != q.shape[:3] or (
            g.shape != q.shape or beta.shape != q.shape[:3]):
        raise ValueError(
            f"kda_rule: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape} do not fit together")
    C = min(chunk, S)
    if telemetry_active():
        publish_geometry("kda_geometry", "kda", {
            "chunk": C, "chunks": -(-S // C), "heads": H, "d_k": dk,
            "d_v": dv, "sub_block": SUB if C % SUB == 0 else C},
            form="xla_chunked")
    with named_scope("kda-scan"):
        return _chunked(q, k, v, g.astype(jnp.float32),
                        beta.astype(jnp.float32), C)
