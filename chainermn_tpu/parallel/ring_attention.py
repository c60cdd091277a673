"""Ring attention — blockwise sequence-parallel attention over ICI.

Net-new capability (SURVEY §5.7): the reference predates long-context
techniques; its only related primitives are the differentiable
``alltoall``/``allgather``.  This module implements the ring form: the
sequence dimension is sharded across a mesh axis, queries stay put, and
K/V blocks rotate around the ring via ``lax.ppermute`` while an online
(flash-style) softmax accumulates partial results — O(S/n) memory per chip
and bandwidth-optimal on a TPU torus, where ``ppermute`` neighbors are
physical ICI neighbors.

Causality across blocks is handled with global position indices: after
``j`` rotations a chip holds the block originating at rank ``(r - j) mod
n``, so block-level masks are computed from source-rank offsets, not
locally.  Accumulation runs in fp32 regardless of input dtype (bf16-safe).

Differentiation: the body is a composition of linear collectives and
pointwise ops; ``jax.checkpoint`` on the scan body keeps backward memory at
one block — rematerialization instead of activation stash, the TPU way to
trade FLOPs for HBM.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..communicators.mesh_utils import axis_size_traced


def _block_attn(q, k, v, mask, scale):
    """One q-block × kv-block attention with unnormalized accumulators.

    q: (B, Sq, H, D); k/v: (B, Sk, Hk, D) with ``Hk`` dividing ``H``
    (GQA/MQA: the group's query heads share one kv head — grouped einsum,
    no materialized repeat, so the ring rotates only the REDUCED kv
    blocks); mask: broadcastable to (B, H, Sq, Sk) boolean with a size-1
    head axis.  Returns (scores_max, exp_sums, weighted_v) shaped with
    the full ``H``."""
    B, Sq, H, D = q.shape
    Hk = k.shape[2]
    if Hk != H:
        if H % Hk:
            raise ValueError(
                f"kv heads ({Hk}) must divide query heads ({H})"
            )
        G = H // Hk
        qg = q.reshape(B, Sq, Hk, G, D)
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk",
            qg.astype(jnp.float32), k.astype(jnp.float32),
        ) * scale
        if mask is not None:
            # Callers build masks with a size-1 head axis; add a size-1
            # group axis so it broadcasts over (Hk, G).
            logits = jnp.where(mask[:, :, None], logits, -jnp.inf)
        m = jnp.max(logits, axis=-1)
        safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        l = jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        return (
            m.reshape(B, H, Sq),
            l.reshape(B, H, Sq),
            pv.reshape(B, Sq, H, D),
        )
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                      # (B, H, Sq)
    # Guard fully-masked rows: exp(-inf - (-inf)) → use where.
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - safe_m[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)                           # (B, H, Sq)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m, l, pv


def _online_merge(stats, blk, gate=None):
    """Merge one block's (m, l, pv) into running online-softmax stats.

    NaN-safe at the -inf edges (fully-masked rows, untouched accumulators):
    the ``isfinite`` guards zero the dead branch instead of producing
    ``exp(-inf - -inf)``.  ``gate`` (bool) drops the block entirely when
    False — used by the zigzag schedule's data-selected blocks.
    """
    m_run, l_run, acc = stats
    m_blk, l_blk, pv_blk = blk
    if gate is not None:
        m_blk = jnp.where(gate, m_blk, -jnp.inf)
        l_blk = jnp.where(gate, l_blk, 0.0)
        pv_blk = jnp.where(gate, pv_blk, 0.0)
    m_new = jnp.maximum(m_run, m_blk)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_safe), 0.0)
    beta = jnp.where(jnp.isfinite(m_blk), jnp.exp(m_blk - m_safe), 0.0)
    l_new = l_run * alpha + l_blk * beta
    acc_new = (
        acc * alpha.transpose(0, 2, 1)[..., None]
        + pv_blk * beta.transpose(0, 2, 1)[..., None]
    )
    return (m_new, l_new, acc_new)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
):
    """Sequence-parallel attention; call inside ``shard_map`` with the
    sequence dimension sharded over ``axis_name``.

    q, k, v: (B, S_local, H, D) — this chip's sequence shard.  GQA/MQA:
    k/v may carry fewer heads (dividing H) — only the REDUCED kv blocks
    rotate around the ring, so sequence-parallel wire drops by the group
    factor, GQA's whole point at long context.
    ``q_segment_ids``/``kv_segment_ids``: optional (B, S_local) int32
    LOCAL shards of packed-sequence segment ids — the KV ids rotate
    around the ring with their K/V blocks, so attention never crosses a
    segment boundary even when the boundary crosses a shard boundary.
    ``window``: optional sliding-window size (causal only) — the ring
    already masks every rotated block by GLOBAL positions, so the band
    ``q_pos - k_pos < window`` composes exactly even when it crosses
    shard boundaries.  (Blocks wholly outside the band still rotate —
    the uniform scan stays static — but contribute nothing.)
    Returns (B, S_local, H, D) attention output for the local queries,
    numerically identical (up to fp32 accumulation order) to full
    attention over the gathered sequence.
    """
    n = axis_size_traced(axis_name)
    my = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D**0.5)
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if kv_segment_ids is not None and q_segment_ids is None:
        raise ValueError(
            "kv_segment_ids without q_segment_ids would be silently "
            "ignored; pass q_segment_ids (optionally alone — kv defaults "
            "to it)"
        )
    if kv_segment_ids is None:
        kv_segment_ids = q_segment_ids

    q_pos = my * S + jnp.arange(S)  # global positions of local queries

    perm = [(i, (i + 1) % n) for i in range(n)]
    segmented = q_segment_ids is not None

    def body(carry, j):
        # Segment ids ride the carry ONLY when segmented — a dead zeros
        # tensor would still be saved/rematerialized by jax.checkpoint.
        if segmented:
            k_blk, v_blk, seg_blk, acc, m_run, l_run = carry
        else:
            k_blk, v_blk, acc, m_run, l_run = carry
            seg_blk = None
        src = (my - j) % n                   # originating rank of this block
        k_pos = src * S + jnp.arange(S)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
            mask = mask[None, None]
        else:
            mask = None
        if segmented:
            from chainermn_tpu.ops.flash_attention import segment_mask

            seg_mask = segment_mask(q_segment_ids, seg_blk)[:, None]
            mask = seg_mask if mask is None else (mask & seg_mask)
        blk = _block_attn(q, k_blk, v_blk, mask, scale)
        m_new, l_new, acc_new = _online_merge((m_run, l_run, acc), blk)

        # Rotate K/V (and their segment ids) to the next chip (skipped
        # after the last block's use would be wasted, but a uniform scan
        # keeps the program static).
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        tail = (acc_new, m_new, l_new)
        if segmented:
            seg_nxt = lax.ppermute(seg_blk, axis_name, perm)
            return (k_nxt, v_nxt, seg_nxt) + tail, None
        return (k_nxt, v_nxt) + tail, None

    acc0 = jnp.zeros((B, S, H, D), jnp.float32)
    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)

    carry0 = (k, v) + (
        (kv_segment_ids.astype(jnp.int32),) if segmented else ()
    ) + (acc0, m0, l0)
    out_carry, _ = lax.scan(jax.checkpoint(body), carry0, jnp.arange(n))
    acc, l = out_carry[-3], out_carry[-1]

    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def zigzag_indices(seq_len: int, n_shards: int):
    """Permutation putting a global sequence into zigzag layout.

    The sequence is cut into ``2n`` chunks; shard ``r`` holds chunks
    ``(r, 2n-1-r)`` — one early, one late.  Under causal attention this
    balances work perfectly: plain contiguous sharding gives shard ``r``
    ``r+1`` live block-pairs (the last shard does ``n`` while the first
    idles); zigzag gives every shard exactly 2 live half-block pairs per
    ring step.  Apply to the sequence axis BEFORE sharding
    (``x[:, zigzag_indices(S, n)]``), and :func:`inverse_zigzag_indices`
    to outputs.
    """
    import numpy as np

    if seq_len % (2 * n_shards):
        raise ValueError(f"seq_len {seq_len} must divide by 2*{n_shards}")
    c = seq_len // (2 * n_shards)
    idx = []
    for r in range(n_shards):
        idx.extend(range(r * c, (r + 1) * c))
        idx.extend(range((2 * n_shards - 1 - r) * c, (2 * n_shards - r) * c))
    return np.asarray(idx)


def inverse_zigzag_indices(seq_len: int, n_shards: int):
    import numpy as np

    idx = zigzag_indices(seq_len, n_shards)
    inv = np.empty_like(idx)
    inv[idx] = np.arange(seq_len)
    return inv


def _flash_block_stats(q, k, v, causal, scale, block, interpret,
                       qseg=None, kseg=None):
    """Block stats from the Pallas flash kernel, in `_online_merge`'s
    (m, l, pv) convention: any (m', l', pv') with the same normalized
    output pv/l and the same m + log l is equivalent, so the kernel's
    (o, lse) maps to (lse, 1, o).  Differentiable (the LSE cotangent folds
    into the kernel backward's residual).  ``qseg``/``kseg``: optional
    (B, S) segment ids — the segmented kernel variant masks the block."""
    from chainermn_tpu.ops.flash_attention import (
        flash_attention_with_lse,
        flash_attention_with_lse_seg,
        from_bh,
        seg_to_bh,
        to_bh,
    )

    B, S, H, D = q.shape
    Hk = k.shape[2]   # GQA: the kernel groups q rows onto kv rows itself
    if qseg is None:
        o, lse = flash_attention_with_lse(
            to_bh(q), to_bh(k), to_bh(v), scale, causal, block, block,
            interpret,
        )
    else:
        o, lse = flash_attention_with_lse_seg(
            to_bh(q), to_bh(k), to_bh(v),
            seg_to_bh(qseg, H), seg_to_bh(kseg, Hk),
            scale, causal, block, block, interpret,
        )
    o4 = from_bh(o, B, H).astype(jnp.float32)
    lse3 = lse[..., 0].reshape(B, H, S)
    return lse3, jnp.ones_like(lse3), o4


def zigzag_ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    segment_ids: Optional[jax.Array] = None,
):
    """Causal ring attention over zigzag-sharded sequences — half the FLOPs
    of :func:`ring_attention` at perfect load balance.

    Inputs are this chip's zigzag shard (see :func:`zigzag_indices`):
    ``(B, S_local, H, D)`` where the first half is chunk ``r`` (early) and
    the second half chunk ``2n-1-r`` (late).  Per ring step each chip runs
    exactly TWO half-chunk block attentions (plain causal ring attention
    computes the full masked S_local² block every step, half of it dead):

    * its late chunk attends the received early chunk (always live);
    * its early chunk attends the received early chunk when the source is
      behind it, OTHERWISE its late chunk attends the received late chunk
      — exactly one of the two is causally live, selected by data, so the
      program stays uniform while no chip computes a dead block.

    ``segment_ids``: optional (B, S_local) int32 packed-sequence ids IN
    ZIGZAG LAYOUT (apply the same :func:`zigzag_indices` permutation as
    the activations); they rotate with the K/V blocks, on both the dense
    inner path and the flash inner (the segmented flash-with-LSE kernel).
    """
    n = axis_size_traced(axis_name)
    my = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    if S % 2:
        raise ValueError("zigzag shard length must be even (two chunks)")
    C = S // 2
    if scale is None:
        scale = 1.0 / (D**0.5)

    qa, qb = q[:, :C], q[:, C:]          # chunk ids: a = my, b = 2n-1-my
    tri = jnp.tril(jnp.ones((C, C), bool))[None, None]

    # Per-block compute: the Pallas flash kernel when shapes allow (the
    # "ring outside, flash inside" composition), dense einsum otherwise.
    from chainermn_tpu.ops.flash_attention import (
        default_interpret,
        flash_block_plan,
    )

    interpret = default_interpret()
    flash_ok, flash_blk = flash_block_plan(C, q.shape[-1], q.dtype, interpret)
    segmented = segment_ids is not None
    if use_flash is None:
        use_flash = flash_ok and not interpret   # off-TPU interpret is slow
    elif use_flash and not flash_ok:
        raise ValueError(
            f"use_flash=True but the kernel block plan refused chunk shape "
            f"(C={C}, D={q.shape[-1]}): either it violates the compiled "
            f"kernel's tiling constraints (D > 128, or C has no aligned "
            f"divisor), or — in interpreter mode off-TPU — no block size "
            f"both divides C and keeps the interpreter grid tractable; "
            f"pass use_flash=False (or None) to use the XLA path"
        )

    def block_stats(qc, kc, vc, causal, qseg=None, kseg=None):
        if use_flash:
            return _flash_block_stats(
                qc, kc, vc, causal, scale, flash_blk, interpret,
                qseg=qseg, kseg=kseg,
            )
        mask = tri if causal else None
        if qseg is not None:
            from chainermn_tpu.ops.flash_attention import segment_mask

            sm = segment_mask(qseg, kseg)[:, None]
            mask = sm if mask is None else (mask & sm)
        return _block_attn(qc, kc, vc, mask, scale)

    def zeros_stats():
        return (
            jnp.full((B, H, C), -jnp.inf, jnp.float32),
            jnp.zeros((B, H, C), jnp.float32),
            jnp.zeros((B, C, H, D), jnp.float32),
        )

    if segmented:
        seg = segment_ids.astype(jnp.int32)
        sega, segb = seg[:, :C], seg[:, C:]
    else:
        seg = sega = segb = None

    def segargs(qseg, kseg):
        return (qseg, kseg) if segmented else (None, None)

    # j = 0: own block — both diagonals triangular, late-attends-early full.
    sa = _online_merge(zeros_stats(), block_stats(
        qa, k[:, :C], v[:, :C], True, *segargs(sega, sega)
    ))
    sb = _online_merge(zeros_stats(), block_stats(
        qb, k[:, :C], v[:, :C], False, *segargs(segb, sega)
    ))
    sb = _online_merge(sb, block_stats(
        qb, k[:, C:], v[:, C:], True, *segargs(segb, segb)
    ))

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, j):
        # Segment ids ride the carry ONLY when segmented (a dead zeros
        # tensor would still be saved/rematerialized by jax.checkpoint).
        if segmented:
            k_blk, v_blk, seg_blk, sa, sb = carry
            seg_blk = lax.ppermute(seg_blk, axis_name, perm)
        else:
            k_blk, v_blk, sa, sb = carry
            seg_blk = None
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        # After j rotations the block originates at rank (my - j) mod n.
        early_live = my >= j           # src strictly behind: a·ka live
        # One conditional half-block: a·ka when early_live, else b·kb.
        q_in = jnp.where(early_live, qa, qb)
        k_in = jnp.where(early_live, k_blk[:, :C], k_blk[:, C:])
        v_in = jnp.where(early_live, v_blk[:, :C], v_blk[:, C:])
        if segmented:
            qseg_in = jnp.where(early_live, sega, segb)
            kseg_in = jnp.where(early_live, seg_blk[:, :C], seg_blk[:, C:])
            kseg_early = seg_blk[:, :C]
        else:
            qseg_in = kseg_in = kseg_early = None
        blk2 = block_stats(
            q_in, k_in, v_in, False, *segargs(qseg_in, kseg_in)
        )
        sa = _online_merge(sa, blk2, gate=early_live)
        sb = _online_merge(sb, blk2, gate=jnp.logical_not(early_live))
        # Late chunk b always attends the received early chunk ka.
        sb = _online_merge(sb, block_stats(
            qb, k_blk[:, :C], v_blk[:, :C], False,
            *segargs(segb, kseg_early)
        ))
        out = (k_blk, v_blk) + ((seg_blk,) if segmented else ()) + (sa, sb)
        return out, None

    carry0 = (k, v) + ((seg,) if segmented else ()) + (sa, sb)
    out_carry, _ = lax.scan(
        jax.checkpoint(body), carry0, jnp.arange(1, n)
    )
    sa, sb = out_carry[-2], out_carry[-1]

    def finish(stats):
        m, l, acc = stats
        denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return (acc / denom).astype(q.dtype)

    return jnp.concatenate([finish(sa), finish(sb)], axis=1)


def _local_seg_slice(segment_ids, axis_name, s_local, batch):
    """Slice row-uniform GLOBAL (S,) segment ids to this chip's local
    shard inside shard_map (ids bound at construction cannot know the
    shard; ``lax.axis_index`` can)."""
    if segment_ids.ndim != 1:
        raise ValueError(
            f"adapter segment_ids must be row-uniform GLOBAL (S,), got "
            f"shape {segment_ids.shape} — per-row (B, S) ids go to "
            "ring_attention/ulysses_attention directly (as LOCAL shards)"
        )
    n = axis_size_traced(axis_name)
    if segment_ids.shape[0] != s_local * n:
        # dynamic_slice CLAMPS out-of-range starts — wrong-length ids
        # would silently give every shard the same trailing window.
        raise ValueError(
            f"adapter segment_ids length {segment_ids.shape[0]} != global "
            f"sequence {s_local} * {n} shards = {s_local * n}"
        )
    my = lax.axis_index(axis_name)
    row = lax.dynamic_slice_in_dim(
        segment_ids.astype(jnp.int32), my * s_local, s_local
    )
    return jnp.broadcast_to(row[None], (batch, s_local))


def refuse_block_diffusion(block_diffusion, what: str) -> None:
    """A row of a table trained by block diffusion hands its block to
    the ``attention_fn``; the sequence-parallel adapters have no such
    mask (a shard's rows would be part clean, part noisy, and the blocks
    rotating past it would need the mask's two intervals): say so by
    name, do not attend causally instead."""
    if block_diffusion is not None:
        raise ValueError(
            f"{what} builds no block-diffusion mask (block_diffusion="
            f"{block_diffusion}): the [clean ; noisy] rows are not "
            f"sharded over a sequence axis; take make_flash_attention_fn")


def make_ring_attention_fn(axis_name: str, causal: bool = True,
                           segment_ids=None, window=None):
    """Adapter with the ``attention_fn(q, k, v, mask)`` signature the
    transformer layers accept (mask ignored: causality is positional).
    ``segment_ids``: optional row-uniform GLOBAL (S,) packed-sequence
    ids, sliced per shard at call time via the traced axis index.
    ``window``: as ``make_flash_attention_fn``'s — a row that has its own
    hands it over at the call."""
    from chainermn_tpu.ops.flash_attention import row_window

    own_window = window

    def fn(q, k, v, mask=None, window=None, block_diffusion=None):
        del mask
        refuse_block_diffusion(block_diffusion, "ring attention")
        qs = ks = None
        if segment_ids is not None:
            qs = _local_seg_slice(
                segment_ids, axis_name, q.shape[1], q.shape[0]
            )
            ks = qs
        return ring_attention(
            q, k, v, axis_name, causal=causal,
            q_segment_ids=qs, kv_segment_ids=ks,
            window=row_window(own_window, window),
        )

    return fn


def gather_sequence_kv(k, v, axis_name: str):
    """All-gather sequence-sharded K/V blocks into the full slice —
    the Ulysses-style building block the serving engine's
    sequence-parallel *prefill* step uses (docs/serving.md).

    ``k``/``v``: (B, S_local, Hk, D) — each shard holds consecutive
    tokens of one chunk slice.  Returns (B, S_local * n_shards, Hk, D)
    in ring order, i.e. the exact concatenation an unsharded chunk
    would have computed locally.

    Why a gather and not the ring above: the ring's online-softmax
    merges partial reductions in rotation order, so its accumulation
    order (and therefore its low-order float bits) depends on the shard
    count and total padded length.  The serving engine's contract is
    bit-exactness against the sequential oracle *and* content-addressed
    prefix pages that are byte-identical across bucket sizes — a plain
    concatenation preserves both (the downstream paged attention is
    unchanged), at the cost of materializing the slice's K/V per chip.
    Decode never calls this; it stays collective-free."""
    k = lax.all_gather(k, axis_name, axis=1, tiled=True)
    v = lax.all_gather(v, axis_name, axis=1, tiled=True)
    return k, v


def make_zigzag_ring_attention_fn(axis_name: str, segment_ids=None):
    """Adapter for :func:`zigzag_ring_attention` (always causal; inputs
    must be in zigzag shard layout, see :func:`zigzag_indices`).
    ``segment_ids``: optional row-uniform GLOBAL (S,) ids ALREADY in
    zigzag layout (apply the same permutation as the tokens)."""

    def fn(q, k, v, mask=None, window=None, block_diffusion=None):
        del mask
        refuse_block_diffusion(block_diffusion, "zigzag ring attention")
        if window is not None:
            raise ValueError(
                "zigzag ring attention builds no sliding window: a table "
                "with windowed rows takes make_ring_attention_fn or "
                "make_ulysses_attention_fn")
        seg = None
        if segment_ids is not None:
            seg = _local_seg_slice(
                segment_ids, axis_name, q.shape[1], q.shape[0]
            )
        return zigzag_ring_attention(q, k, v, axis_name, segment_ids=seg)

    return fn
