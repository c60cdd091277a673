"""Memory-efficient (chunked) softmax cross-entropy against a tied
embedding — the LM loss head.

The reference framework computed ``softmax_cross_entropy`` on fully
materialized logits (Chainer's ``F.softmax_cross_entropy`` over a
``(B*S, V)`` array — REF:chainermn examples seq2seq loss path).  That is
fine at seq2seq scale; at long-context LM scale the logits are the
single largest tensor in the step: B=8, S=4096, V=32768 is 4 GiB in
fp32 — more than the activations of the entire transformer stack — and
the autodiff residual doubles it.

TPU-native design: never materialize the full logit matrix.  Tokens are
processed in row chunks; each chunk's logits live only inside the chunk
computation (bf16 MXU matmul, fp32 accumulation), reduced immediately to
the scalar loss contribution plus a per-token log-sum-exp.  Peak extra
memory is ``chunk x V`` fp32 (default 64 MiB at V=32k) instead of
``N x V``.

Two gradient rules share the chunk helpers below, chosen by what the
caller's function returns:

* :func:`fused_cross_entropy` returns the loss alone, so the cotangent
  that reaches it is one scalar and ``dlogits = g * (p - onehot)`` is
  known up to that scalar while the chunk's logits exist.  Its forward
  rule makes ``d hidden`` and ``d embedding`` in the SAME scan (the
  embedding gradient in the fp32 carry) and its backward rule scales
  them by ``g``: three vocabulary matmuls a chunk, **no logits
  recomputed**, and neither ``hidden`` nor the per-token log-sum-exp
  kept for backward.  Called without differentiation it runs the
  forward scan alone (one matmul a chunk).
* :func:`fused_cross_entropy_with_lse` also hands out the per-token
  log-sum-exp as a differentiable output (z-loss); its ``dlogits`` has a
  term ``g_lse_i * p`` whose per-token weights exist only in backward, so
  its backward scan **recomputes each chunk's logits** from the saved
  ``lse`` (one fp32 scalar per token — the flash-attention residual
  trick applied to the vocabulary axis): four matmuls a chunk.

The same per-chunk (max, sum-exp) reduction is the building block of the
vocab-parallel (tensor-parallel) cross-entropy in
``chainermn_tpu.parallel.sharding``: there the V axis is sharded, the
two reductions become ``psum``/``pmax`` over the model axis, and the
gradient rule is the recomputing one (:func:`ce_scan_fwd` /
:func:`ce_scan_bwd` with its own strategy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chainermn_tpu.observability import reporter as _reporter
from chainermn_tpu.observability import step_log as _step_log
from chainermn_tpu.observability.spans import named_scope, telemetry_active

#: rows per scan tile when the caller passes no ``chunk``.
DEFAULT_CHUNK = 512


def _pick_chunk(n: int, chunk: int) -> int:
    """Largest divisor of ``n`` that is <= chunk (scan needs equal-size
    chunks; a ragged tail would need masking for no benefit since callers
    control N = B*S)."""
    chunk = min(chunk, n)
    while n % chunk:
        chunk -= 1
    return chunk


def _chunk_logits(h_c, emb):
    """(C, D) x (V, D) -> (C, V) fp32 logits: bf16 operands on the MXU,
    fp32 accumulation."""
    return jax.lax.dot_general(
        h_c.astype(jnp.bfloat16),
        emb.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


class LocalVocabStrategy:
    """Reduction strategy for a FULL vocabulary on one device: every
    merge is the identity and every label row is locally resolvable.

    The vocab-parallel cross-entropy
    (``parallel.sharding.vocab_parallel_cross_entropy``) swaps in a
    strategy whose merges are ``pmax``/``psum`` over the model axis and
    whose label resolution is ownership-masked — same math, one
    implementation of the chunked scan to maintain."""

    def merge_max(self, m):
        return m

    def merge_sum(self, s):
        return s

    def merge_pick(self, p):
        return p

    def reduce_dh(self, dh):
        return dh

    def label_local(self, labels):
        """(local row index, ownership mask).  Locally every valid label
        is owned; invalid (< 0) labels are owned nowhere."""
        return jnp.maximum(labels, 0), labels >= 0


def _chunk_lse(logits, strat):
    """Per-token log-sum-exp of one ``(C, V_local)`` fp32 logit tile."""
    m = strat.merge_max(jnp.max(logits, axis=-1))
    se = strat.merge_sum(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    return m + jnp.log(se)


def _chunk_loss(carry, logits, lse_c, l_c, strat, w_c=None):
    """``(loss_sum, n_valid)`` carry plus this chunk's valid tokens'
    ``lse - picked``, each times its weight where the call has weights
    (``w_c``, float32 a row)."""
    loss_sum, n_valid = carry
    valid = l_c >= 0
    idx, owner = strat.label_local(l_c)
    picked_s = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
    picked = strat.merge_pick(jnp.where(owner, picked_s, 0.0))
    tok_loss = lse_c - picked
    if w_c is not None:
        tok_loss = tok_loss * w_c
    tok_loss = jnp.where(valid, tok_loss, 0.0)
    return (loss_sum + tok_loss.sum(),
            n_valid + valid.sum().astype(jnp.float32))


def _chunk_softmax_onehot(logits, lse_c, l_c, strat):
    """``(p, onehot, valid[:, None])`` of one logit tile: the softmax
    (local shard), the owned labels' one-hot rows and the valid mask."""
    p = jnp.exp(logits - lse_c[:, None])
    idx, owner = strat.label_local(l_c)
    onehot = jax.nn.one_hot(
        idx, logits.shape[1], dtype=p.dtype
    ) * owner[:, None]
    return p, onehot, (l_c >= 0)[:, None]


def _chunk_grads(dlogits, h_c, embedding, strat):
    """``(dh_c, d_emb contribution)`` of one chunk, both fp32: bf16
    operands on the MXU, fp32 accumulation."""
    dlogits = dlogits.astype(jnp.bfloat16)
    dh_c = strat.reduce_dh(jnp.dot(
        dlogits, embedding.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ))
    d_emb_c = jax.lax.dot_general(
        dlogits, h_c.astype(jnp.bfloat16),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dh_c, d_emb_c


def _chunked(hidden, labels, chunk, weights=None):
    """``(h_chunks (n, C, D), l_chunks (n, C))`` for the row scan, and
    the weights' ``(n, C)`` after them where the call has weights."""
    N = hidden.shape[0]
    C = _pick_chunk(N, chunk)
    chunks = (hidden.reshape(N // C, C, hidden.shape[1]),
              labels.reshape(N // C, C))
    if weights is None:
        return chunks
    return chunks + (weights.astype(jnp.float32).reshape(N // C, C),)


def ce_scan_fwd(hidden, embedding, labels, chunk, strat, weights=None):
    """Chunked CE forward: sum over valid tokens of ``lse - picked`` plus
    the valid count and per-token lse, never holding more than one
    ``(chunk, V_local)`` logit tile.  ``strat`` supplies the cross-shard
    merges (identity for the local case).  ``weights``: a float32 weight
    a row on its term of the sum, or None."""
    chunks = _chunked(hidden, labels, chunk, weights)

    def body(carry, chunk_c):
        h_c, l_c, *w_c = chunk_c
        logits = _chunk_logits(h_c, embedding)  # (C, V_local) fp32
        lse_c = _chunk_lse(logits, strat)
        return _chunk_loss(carry, logits, lse_c, l_c, strat, *w_c), lse_c

    with named_scope("fused-ce"):
        (loss_sum, n_valid), lse = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0)), chunks
        )
    return loss_sum, n_valid, lse.reshape(hidden.shape[0])


def ce_scan_bwd(hidden, embedding, labels, lse, g_loss, g_lse, chunk,
                strat):
    """Chunked CE backward, the recomputing rule: remake each chunk's
    logits from the saved lse (remat), assemble ``dlogits = g*(p -
    onehot) + g_lse*p``, and accumulate ``d embedding`` in the scan
    carry.  Returns (dh, d_emb) in the input dtypes."""
    N, D = hidden.shape
    h_chunks, l_chunks = _chunked(hidden, labels, chunk)
    lse_chunks = lse.reshape(l_chunks.shape)
    g_lse_chunks = g_lse.reshape(l_chunks.shape)

    def body(d_emb, args):
        h_c, l_c, lse_c, g_lse_c = args
        logits = _chunk_logits(h_c, embedding)  # recompute (remat)
        p, onehot, valid = _chunk_softmax_onehot(logits, lse_c, l_c, strat)
        # d loss_sum / d logits = (p - onehot) per valid token;
        # d lse / d logits = p (lse is an output in its own right).
        dlogits = jnp.where(
            valid, g_loss * (p - onehot), 0.0
        ) + g_lse_c[:, None] * p
        dh_c, d_emb_c = _chunk_grads(dlogits, h_c, embedding, strat)
        return d_emb + d_emb_c, dh_c

    with named_scope("fused-ce"):
        d_emb, dh = jax.lax.scan(
            body,
            jnp.zeros(embedding.shape, jnp.float32),
            (h_chunks, l_chunks, lse_chunks, g_lse_chunks),
        )
    return (
        dh.reshape(N, D).astype(hidden.dtype),
        d_emb.astype(embedding.dtype),
    )


def ce_scan_fwd_grads(hidden, embedding, labels, chunk, weights=None):
    """Chunked CE forward that also makes the gradients of ``loss_sum``
    (a cotangent of 1) while each chunk's logits exist: one scan, three
    matmuls a chunk, ``d embedding`` in the fp32 carry.  Full vocabulary
    on one device only.  ``weights``: a float32 weight a row on its term
    of the sum and so on its row of ``dlogits``, or None.  Returns
    (loss_sum, n_valid, dh, d_emb), the gradients in the input dtypes."""
    N, D = hidden.shape
    strat = LocalVocabStrategy()
    chunks = _chunked(hidden, labels, chunk, weights)

    def body(carry, chunk_c):
        loss_carry, d_emb = carry
        h_c, l_c, *w_c = chunk_c
        logits = _chunk_logits(h_c, embedding)  # (C, V) fp32, made once
        lse_c = _chunk_lse(logits, strat)
        loss_carry = _chunk_loss(loss_carry, logits, lse_c, l_c, strat,
                                 *w_c)
        p, onehot, valid = _chunk_softmax_onehot(logits, lse_c, l_c, strat)
        dlogits = p - onehot
        if w_c:
            dlogits = dlogits * w_c[0][:, None]
        # Made once, in bf16, for both gradient matmuls (as the
        # recomputing rule's backward has it): left free, the compiler
        # redoes the softmax inside each matmul's operand and reads the
        # fp32 tile twice — +2.1 ms a call at V=50257 on a v5e, -0.2 at
        # V=25088 where the tile stays on-chip (PERF.md §6, PR 27).
        dlogits = jax.lax.optimization_barrier(
            jnp.where(valid, dlogits, 0.0).astype(jnp.bfloat16))
        dh_c, d_emb_c = _chunk_grads(dlogits, h_c, embedding, strat)
        return (loss_carry, d_emb + d_emb_c), dh_c

    with named_scope("fused-ce"):
        ((loss_sum, n_valid), d_emb), dh = jax.lax.scan(
            body,
            ((jnp.float32(0.0), jnp.float32(0.0)),
             jnp.zeros(embedding.shape, jnp.float32)),
            chunks,
        )
    return (
        loss_sum, n_valid,
        dh.reshape(N, D).astype(hidden.dtype),
        d_emb.astype(embedding.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ce_sum(hidden, embedding, labels, chunk):
    """Sum over valid tokens of ``lse(logits_i) - logits_i[label_i]`` and
    the valid-token count.  ``labels < 0`` are ignored (0 loss, 0 grad).

    hidden: (N, D); embedding: (V, D); labels: (N,) int32.
    Returns (loss_sum fp32, n_valid fp32, lse (N,) fp32).
    """
    return ce_scan_fwd(hidden, embedding, labels, chunk,
                       LocalVocabStrategy())


def _fused_ce_vjp_fwd(hidden, embedding, labels, chunk):
    out = ce_scan_fwd(hidden, embedding, labels, chunk,
                      LocalVocabStrategy())
    return out, (hidden, embedding, labels, out[2])


def _fused_ce_vjp_bwd(chunk, res, cots):
    hidden, embedding, labels, lse = res
    g_loss, _g_nvalid, g_lse = cots
    dh, d_emb = ce_scan_bwd(
        hidden, embedding, labels, lse, g_loss, g_lse, chunk,
        LocalVocabStrategy(),
    )
    return dh, d_emb, None


_fused_ce_sum.defvjp(_fused_ce_vjp_fwd, _fused_ce_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ce_loss(hidden, embedding, labels, chunk):
    """:func:`_fused_ce_sum` without the lse output: (loss_sum, n_valid).
    With no per-token output the cotangent is one scalar, so the rule
    below makes the gradients in the forward scan and recomputes
    nothing."""
    loss_sum, n_valid, _lse = ce_scan_fwd(
        hidden, embedding, labels, chunk, LocalVocabStrategy())
    return loss_sum, n_valid


def _fused_ce_loss_vjp_fwd(hidden, embedding, labels, chunk):
    loss_sum, n_valid, dh, d_emb = ce_scan_fwd_grads(
        hidden, embedding, labels, chunk)
    return (loss_sum, n_valid), (dh, d_emb)


def _fused_ce_loss_vjp_bwd(chunk, res, cots):
    g_loss, _g_nvalid = cots
    with named_scope("fused-ce"):
        dh, d_emb = (
            (g_loss * g.astype(jnp.float32)).astype(g.dtype) for g in res
        )
    return dh, d_emb, None


_fused_ce_loss.defvjp(_fused_ce_loss_vjp_fwd, _fused_ce_loss_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_ce_loss_weighted(hidden, embedding, labels, weights, chunk):
    """:func:`_fused_ce_loss` with a float32 weight a row inside the scan,
    forward and backward: ``(sum_i w_i (lse_i - picked_i), n_valid)``,
    the gradients made in the forward scan as there.  The weights are
    data (a noise level's ``1 / t``): their cotangent is zero."""
    loss_sum, n_valid, _lse = ce_scan_fwd(
        hidden, embedding, labels, chunk, LocalVocabStrategy(), weights)
    return loss_sum, n_valid


def _fused_ce_weighted_vjp_fwd(hidden, embedding, labels, weights, chunk):
    loss_sum, n_valid, dh, d_emb = ce_scan_fwd_grads(
        hidden, embedding, labels, chunk, weights)
    return (loss_sum, n_valid), (dh, d_emb, weights)


def _fused_ce_weighted_vjp_bwd(chunk, res, cots):
    *grads, weights = res
    dh, d_emb, _ = _fused_ce_loss_vjp_bwd(chunk, grads, cots)
    return dh, d_emb, None, jnp.zeros_like(weights)


_fused_ce_loss_weighted.defvjp(_fused_ce_weighted_vjp_fwd,
                               _fused_ce_weighted_vjp_bwd)


def _publish_geometry(h2, embedding, chunk, form: str) -> None:
    """One ``ce_geometry`` record a traced loss head (at TRACE time,
    beside ``flash_geometry`` and ``ssd_geometry``): a row of the
    StepRecorder, ``fused_ce/<field>`` gauges and a ``fused_ce/calls``
    counter of the Reporter.  ``form`` names the gradient rule the call
    carries: ``grad_in_forward`` or ``recompute`` (the gauge
    ``fused_ce/grad_in_forward`` reads 1 or 0)."""
    rows = h2.shape[0]
    tile = _pick_chunk(rows, chunk)
    record = {"rows": rows, "vocab": embedding.shape[0], "d": h2.shape[1],
              "chunk": tile, "chunks": rows // tile}
    rec = _step_log.current_recorder()
    if rec is not None:
        rec.record("ce_geometry", form=form, **record)
    rep = _reporter.get_reporter()
    if rep is not None:
        rep.count("fused_ce/calls")
        rep.gauge("fused_ce/grad_in_forward", form == "grad_in_forward")
        for field, value in record.items():
            rep.gauge(f"fused_ce/{field}", value)


def fused_cross_entropy(hidden, embedding, labels, *, chunk=None,
                        weights=None, normaliser=None):
    """Mean softmax cross-entropy of ``hidden @ embedding.T`` against
    ``labels``, computed without materializing the ``(N, V)`` logit
    matrix (peak extra memory ``chunk x V`` fp32).  With ``weights``:
    ``sum_i w_i (lse_i - picked_i) / normaliser``.

    * ``hidden`` — ``(..., D)`` final hidden states (any float dtype; the
      logit matmuls run bf16 on the MXU with fp32 accumulation).
    * ``embedding`` — ``(V, D)`` tied output embedding (``nn.Embed``'s
      ``embedding`` table — the ``embed.attend`` weight).
    * ``labels`` — ``(...,)`` int32; negative labels are ignored
      (0 loss, 0 grad) — the packed/padded-sequence convention shared
      with the flash kernels' segment masks.

    Returns the scalar mean over valid tokens (0.0 when none are valid).
    Differentiable in ``hidden`` and ``embedding``.  Under
    differentiation the forward pass makes both gradients chunk by chunk
    in its one scan (the output is a scalar, so they are known up to the
    scalar cotangent) and keeps them, in the inputs' dtypes, as the only
    residuals; the backward pass scales them.  No logits are recomputed
    (:func:`fused_cross_entropy_with_lse` is the path that recomputes).
    Without differentiation only the loss scan runs.

    ``chunk`` — rows per scan tile; None is :data:`DEFAULT_CHUNK`.

    ``weights`` — ``(...,)`` like ``labels``, a float32 weight on each
    row's term of the sum, applied inside the chunked scan, forward and
    backward (a masked-diffusion loss: ``1 / t`` on the rows whose token
    was masked at level ``t``, 0 on the others, so that every step runs
    the head over the same rows).  Data, not differentiated.  None is
    the unweighted program, to the letter.

    ``normaliser`` — what the weighted sum is divided by (a masked
    -diffusion loss divides by the document's length, not by the count
    of kept rows); None divides by the count of rows with a label >= 0,
    as the unweighted mean does.  Only with ``weights``.
    """
    h2, l2, chunk = _prepare(
        hidden, embedding, labels, chunk, "grad_in_forward")
    if weights is None:
        if normaliser is not None:
            raise ValueError("normaliser divides a weighted sum: pass "
                             "weights (ones for the plain sum)")
        loss_sum, n_valid = _fused_ce_loss(h2, embedding, l2, chunk)
        return loss_sum / jnp.maximum(n_valid, 1.0)
    w2 = jnp.asarray(weights).reshape(-1)
    if w2.shape != l2.shape:
        raise ValueError(f"weights {w2.shape[0]} != labels {l2.shape[0]}")
    loss_sum, n_valid = _fused_ce_loss_weighted(h2, embedding, l2, w2, chunk)
    return loss_sum / (jnp.maximum(n_valid, 1.0) if normaliser is None
                       else normaliser)


def fused_cross_entropy_with_lse(hidden, embedding, labels, *, chunk=None):
    """:func:`fused_cross_entropy` variant also returning the per-token
    log-sum-exp ``(N,)`` — the z-loss / logit-scale diagnostic, and the
    merge quantity for vocab-sharded composition.  The lse's cotangent
    is a per-token vector known only in backward, so this path keeps
    ``hidden`` and the lse (4 bytes/token) and its backward scan
    recomputes each chunk's logits."""
    h2, l2, chunk = _prepare(hidden, embedding, labels, chunk, "recompute")
    loss_sum, n_valid, lse = _fused_ce_sum(h2, embedding, l2, chunk)
    return loss_sum / jnp.maximum(n_valid, 1.0), lse


def _prepare(hidden, embedding, labels, chunk, form):
    """``(hidden (N, D), labels (N,), resolved chunk)`` of a public call,
    its geometry published when telemetry is on."""
    h2, l2 = _validate_and_flatten(hidden, embedding, labels, chunk)
    chunk = DEFAULT_CHUNK if chunk is None else int(chunk)
    if telemetry_active():
        _publish_geometry(h2, embedding, chunk, form)
    return h2, l2, chunk


def _validate_and_flatten(hidden, embedding, labels, chunk):
    if chunk is not None and int(chunk) < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    D = hidden.shape[-1]
    h2 = hidden.reshape(-1, D)
    l2 = labels.reshape(-1)
    if h2.shape[0] != l2.shape[0]:
        raise ValueError(
            f"hidden rows {h2.shape[0]} != labels {l2.shape[0]}"
        )
    if embedding.shape[-1] != D:
        raise ValueError(
            f"embedding dim {embedding.shape[-1]} != hidden dim {D}"
        )
    return h2, l2


def naive_cross_entropy(hidden, embedding, labels):
    """Materialized-logits oracle (tests only): same math, full ``(N, V)``
    fp32 logits."""
    logits = _chunk_logits(hidden.reshape(-1, hidden.shape[-1]), embedding)
    l2 = labels.reshape(-1)
    valid = l2 >= 0
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(l2, 0)[:, None], axis=-1
    )[:, 0]
    tok = jnp.where(valid, lse - picked, 0.0)
    return tok.sum() / jnp.maximum(valid.sum().astype(jnp.float32), 1.0)
