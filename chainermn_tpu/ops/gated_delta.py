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
G_j}``.  So, for all chunks at once: ``T = (I + A)^-1``
(:func:`unit_lower_inverse`), ``W = T (beta e^G k)``, ``U = T (beta v)``;
and across the chunks, one ``lax.scan`` step a chunk over the carried
state::

    v_new = U - W S
    o     = (q e^G) S + tril(q k^T e^{G_i - G_j}) v_new
    S    <- e^{G_C} S + (k e^{G_C - G})^T v_new

Precision: the decays, the solve (``A``'s assembly from the float32
product, ``T``, ``W``, ``U``) and the carried state are float32; the other
matrix products — ``k k^T``, ``q k^T``, ``W S``, ``(q e^G) S``, ``tril(..)
v_new``, ``(k e^{G_C - G})^T v_new`` — take operands in the activations'
type and accumulate in float32.

The backward is DERIVED: every line is plain ``jax.numpy``, so autodiff
transposes the chunked form itself (the scan's backward walks the chunks
in reverse, holding one state a chunk).  What it holds between the passes
— a chunk's ``T``, ``W``, ``U``, the decays, a state a chunk: some 0.2 GB
a value head at 2 x 8192 tokens — is bounded by working the value heads
in GROUPS, one after another (``lax.map``), each group rematerialised
(:func:`heads_a_group`): a group's backward recomputes its forward.
Written in XLA ops first, under the scope ``gdn-scan``; the benchmark's
roofline share of that scope counts the rule's needed work whatever
implements it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.observability.spans import named_scope, telemetry_active
from chainermn_tpu.ops.ssd import publish_geometry

_HIGHEST = lax.Precision.HIGHEST

#: Side of the diagonal blocks inverted by substitution, a row a step;
#: larger blocks are put together from their halves.
_BASE = 16

#: Tokens x value heads a group of heads holds at most (2 x 8192 tokens:
#: 8 heads, about 1.5 GB between the passes).
_GROUP_TOKEN_HEADS = 2 * 8192 * 8


def heads_a_group(tokens: int, heads: int) -> int:
    """Value heads worked together: the most that divide ``heads`` with
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


def _inverse(a, base):
    n, _, N = a.shape
    if n <= base or n % 2:
        return _substitute(a)
    h = n // 2
    # Both halves' diagonal blocks side by side on the batch axis; then
    # [[T11, 0], [-T22 A21 T11, T22]].
    both = _inverse(
        jnp.concatenate([a[:h, :h], a[h:, h:]], axis=-1), base)
    t11, t22 = both[..., :N], both[..., N:]
    t21 = -_mm(_mm(t22, a[h:, :h]), t11)
    top = jnp.concatenate([t11, jnp.zeros_like(t21)], axis=1)
    return jnp.concatenate(
        [top, jnp.concatenate([t21, t22], axis=1)], axis=0)


def unit_lower_inverse(a, base: int = _BASE):
    """``(I + a)^-1`` for ``a`` (..., n, n) strictly lower triangular
    (what lies on or above the diagonal is NOT read as zero: the caller
    masks it), float32.  Substitution on the diagonal blocks of ``base``
    rows, the blocks joined pairwise by ``-T22 A21 T11``: backward-stable
    as substitution is, which the Neumann product ``(I - a)(I + a^2)(I +
    a^4)...`` is not (its terms cancel from 1e10 at 64 rows and entries
    near 0.5).  Worked with the batch on the last axis, so that a step's
    small rows fill whole registers of lanes."""
    lead, n = a.shape[:-2], a.shape[-1]
    flat = jnp.moveaxis(a.reshape((-1, n, n)), 0, -1)
    out = _inverse(flat, base)
    return jnp.moveaxis(out, -1, 0).reshape(lead + (n, n))


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
    n = -(-S // C)
    hg = heads_a_group(b * S, Hv)
    if telemetry_active():
        publish_geometry("gdn_geometry", "gdn", {
            "chunk": C, "chunks": n, "key_heads": Hk, "value_heads": Hv,
            "d_k": dk, "d_v": dv, "solve_base": min(_BASE, C),
            "scan_steps": n, "heads_a_group": hg,
            "head_groups": Hv // hg}, form="xla_chunked_scan")
    with named_scope("gdn-scan"):
        rep = Hv // Hk
        if rep > 1:
            q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        if hg == Hv:
            return _chunked(q, k, v, g, beta, C)

        def groups(x):
            """(b, S, H, ...) -> (H / hg, b, S, hg, ...)"""
            x = x.reshape(x.shape[:2] + (Hv // hg, hg) + x.shape[3:])
            return jnp.moveaxis(x, 2, 0)

        o = lax.map(
            jax.checkpoint(lambda xs: _chunked(*xs, C)),
            tuple(groups(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 2).reshape(b, S, Hv, dv)


def _chunked(q, k, v, g, beta, C):
    """The chunked rule for heads that all fit at once: ``q``, ``k``
    (b, S, H, d_k), ``v`` (b, S, H, d_v), ``g``, ``beta`` (b, S, H)
    float32, chunks of ``C`` tokens."""
    b, S, Hv, dk = q.shape
    dv = v.shape[-1]
    n = -(-S // C)
    f32, dt = jnp.float32, v.dtype
    pad = n * C - S

    def chunks(x):
        """(b, S, H, ...) -> (b, H, n, C, ...)"""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta))

    G = jnp.cumsum(gc, axis=-1)                       # (b, H, n, C)
    row = jnp.arange(C)
    below = row[:, None] > row[None, :]
    upto = row[:, None] >= row[None, :]
    # e^{G_i - G_j} where j <= i (the exponent is <= 0 there), else 0
    decay = jnp.where(upto, jnp.exp(jnp.where(
        upto, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    kk = jnp.einsum("bhnid,bhnjd->bhnij", kc, kc,
                    preferred_element_type=f32)
    A = jnp.where(below, bc[..., None] * kk * decay, 0.0)
    T = unit_lower_inverse(A)
    eG = jnp.exp(G)
    rhs = jnp.concatenate(
        [(bc * eG)[..., None] * kc.astype(f32),
         bc[..., None] * vc.astype(f32)], axis=-1)
    WU = jnp.einsum("bhnij,bhnjd->bhnid", T, rhs, precision=_HIGHEST)
    W, U = WU[..., :dk].astype(dt), WU[..., dk:]
    qk = jnp.einsum("bhnid,bhnjd->bhnij", qc, kc,
                    preferred_element_type=f32)
    P = (qk * decay).astype(dt)
    qG = (qc.astype(f32) * eG[..., None]).astype(dt)
    last = G[..., -1]                                 # (b, H, n)
    kG = (kc.astype(f32)
          * jnp.exp(last[..., None] - G)[..., None]).astype(dt)

    def step(state, now):
        W_c, U_c, qG_c, kG_c, P_c, keep = now
        held = state.astype(dt)
        v_new = U_c - jnp.einsum("bhck,bhkv->bhcv", W_c, held,
                                 preferred_element_type=f32)
        v_in = v_new.astype(dt)
        o = jnp.einsum("bhck,bhkv->bhcv", qG_c, held,
                       preferred_element_type=f32) + jnp.einsum(
            "bhij,bhjv->bhiv", P_c, v_in, preferred_element_type=f32)
        state = keep[..., None, None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", kG_c, v_in, preferred_element_type=f32)
        return state, o.astype(dt)

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    _, o = lax.scan(
        step, jnp.zeros((b, Hv, dk, dv), f32),
        tuple(by_chunk(x) for x in (W, U, qG, kG, P, jnp.exp(last))))
    # (n, b, H, C, d_v) -> (b, S, H, d_v)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * C, Hv, dv)
    return o[:, :S]
