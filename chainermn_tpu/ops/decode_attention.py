"""Paged single-query decode attention — the serving data plane's hot op.

Training attention (``ops.flash_attention``) streams a *contiguous*
(B, S, H, D) K/V; serving cannot afford contiguity: sequences in a
continuously-batched decode step have wildly different lengths, grow one
token per iteration, and are admitted/evicted mid-flight.  The
PagedAttention answer (vLLM, arXiv:2309.06180) is to store K/V in
fixed-size *pages* indexed by a per-sequence block table, so memory is
allocated in O(block_size) quanta and the attention kernel follows the
table.

This module is the functional core shared by the serving engine and the
cached-KV model path (:mod:`chainermn_tpu.models.transformer`):

* :func:`paged_attention_decode` — one-query-per-sequence attention over
  paged K/V.  The reference-quality jnp lowering (gather pages → masked
  softmax) is the one lowering on every backend.
* :func:`write_prompt_pages` / :func:`write_token_pages` — the scatter
  writes that land prefill (whole prompt) and decode (one token per
  sequence) K/V into the pages.

Invalid-slot convention: block-table entries that do not name a real
page carry the value ``n_pages`` (one past the last page).  That is
out-of-bounds *high*, which JAX scatters **drop** and gathers **fill**
with zeros; negative sentinels would silently wrap (`a[-1]`) and corrupt
the last page.  Padding rows/positions therefore cost nothing and touch
nothing — no masks on the write side, one mask on the read side.

All reductions here are per-sequence: nothing crosses the batch
dimension and nothing is a collective, which is what keeps (a) batched
decode bit-identical to single-request decode and (b) the decode step
collective-free on the data plane (pinned by the serving lint fixture
and ``tests/golden/serving_decode_census.json``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.observability.spans import named_scope

def invalid_block(n_pages: int) -> int:
    """The sentinel block id for unallocated table slots: out-of-bounds
    HIGH (dropped by scatter, zero-filled by gather).  Never use -1 —
    negative indices wrap in JAX and would alias the last real page."""
    return int(n_pages)


def _positions_to_pages(block_tables, positions, page_size: int,
                        n_pages: int):
    """Map token positions to (page_id, slot) through the block table.

    ``block_tables``: (B, W) int32, invalid entries == ``n_pages``.
    ``positions``: (B, P) int32 token positions; positions that are
    negative or beyond the table's reach resolve to the invalid page.
    Returns ``(page_id, slot)``, both (B, P) int32.
    """
    W = block_tables.shape[1]
    valid = (positions >= 0) & (positions < W * page_size)
    safe = jnp.clip(positions, 0, W * page_size - 1)
    page = jnp.take_along_axis(block_tables, safe // page_size, axis=1)
    page = jnp.where(valid, page, invalid_block(n_pages))
    return page.astype(jnp.int32), (safe % page_size).astype(jnp.int32)


def write_prompt_pages(k_pages, new_k, block_tables, seq_lens):
    """Scatter a whole prompt's K (or V) into the pages.

    ``k_pages``: (N, page_size, Hkv, D); ``new_k``: (B, S, Hkv, D);
    ``seq_lens``: (B,) valid prompt lengths — positions ``t >= seq_lens[b]``
    (padding up to the bucket) are routed to the invalid page and dropped.
    Returns the updated pages.
    """
    N, page_size = k_pages.shape[0], k_pages.shape[1]
    B, S = new_k.shape[0], new_k.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    pos = jnp.where(pos < seq_lens[:, None], pos, -1)
    page, slot = _positions_to_pages(block_tables, pos, page_size, N)
    return k_pages.at[page, slot].set(
        new_k.astype(k_pages.dtype), mode="drop"
    )


def write_chunk_pages(k_pages, new_k, block_tables, start_lens):
    """Scatter a T-token chunk's K (or V) per sequence into the pages.

    ``new_k``: (B, T, Hkv, D) — token ``t`` of row ``b`` lands at position
    ``start_lens[b] + t``.  Rows with ``start_lens[b] < 0`` (padding slots
    in a chunk bucket) write nothing; positions beyond the table's reach
    route to the invalid page and are dropped, so a chunk may safely
    over-run a row's real suffix (speculative drafts, bucket padding) —
    every such slot is beyond the row's masked context and is rewritten
    by a later step before the mask can expose it.
    """
    N, page_size = k_pages.shape[0], k_pages.shape[1]
    B, T = new_k.shape[0], new_k.shape[1]
    pos = start_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    pos = jnp.where(start_lens[:, None] >= 0, pos, -1)
    page, slot = _positions_to_pages(block_tables, pos, page_size, N)
    return k_pages.at[page, slot].set(
        new_k.astype(k_pages.dtype), mode="drop"
    )


def write_token_pages(k_pages, new_k, block_tables, seq_lens):
    """Scatter one decode token's K (or V) per sequence into the pages.

    ``new_k``: (B, 1, Hkv, D) — the token at position ``seq_lens[b]``
    (the context length *before* this token).  Rows with
    ``seq_lens[b] < 0`` (padding slots in a decode bucket) write nothing.
    A T=1 chunk write is exactly this, so delegate — one lowering, one
    set of numerics.
    """
    return write_chunk_pages(k_pages, new_k, block_tables, seq_lens)


def paged_attention_decode(
    q,
    k_pages,
    v_pages,
    block_tables,
    seq_lens,
    *,
    k_scales=None,
    v_scales=None,
):
    """Single-query attention over paged K/V.

    ``q``: (B, 1, H, D) the decode step's query; ``k_pages``/``v_pages``:
    (N, page_size, Hkv, D) with Hkv dividing H (GQA/MQA); ``block_tables``:
    (B, W) int32 page ids (invalid == N); ``seq_lens``: (B,) the number of
    valid cache positions INCLUDING the just-written current token.

    Returns (B, 1, H, D) in ``q.dtype``.  The masked-softmax numerics
    mirror the dense training path in
    :class:`~chainermn_tpu.models.transformer.MultiHeadAttention`
    bit-for-bit at fp32: masked keys get ``finfo(float32).min`` logits
    (exactly-zero weights), softmax accumulates in fp32, and every
    reduction stays inside one sequence's row.

    ``k_scales``/``v_scales``: (N, page_size, Hkv) fp32 per-token-per-head
    scales for quantized (int8) pages — gathered through the same block
    table and multiplied back in after the gather (``kv_dtype`` in
    docs/serving.md).  ``None`` = pages are already in a compute dtype.
    """
    B, one, H, D = q.shape
    if one != 1:
        raise ValueError(
            f"paged_attention_decode consumes one query per sequence, got "
            f"a length-{one} chunk"
        )
    return paged_attention_chunk(
        q, k_pages, v_pages, block_tables, seq_lens - 1,
        k_scales=k_scales, v_scales=v_scales,
    )


@named_scope("paged-decode-attn")
def paged_attention_chunk(
    q,
    k_pages,
    v_pages,
    block_tables,
    start_lens,
    *,
    k_scales=None,
    v_scales=None,
):
    """Multi-query causal attention over paged K/V — the verify/suffix step.

    ``q``: (B, T, H, D) — T queries per sequence at consecutive positions
    ``start_lens[b] + t``; query ``t`` attends to cache positions
    ``< start_lens[b] + t + 1`` (its own freshly-written slot included),
    which is exactly the per-query causal bound a sequential decode would
    see.  Rows with ``start_lens[b] < 0`` are padding: everything is
    masked and the output row is garbage that callers never read.

    ``paged_attention_decode`` is the T=1 special case and delegates
    here, so single-token decode and multi-token verify share one
    lowering — bit-identical numerics at T=1 by construction.

    ``k_scales``/``v_scales``: (N, page_size, Hkv) fp32 scales when the
    pages are int8 (``kv_dtype``).  They ride the SAME gather (block
    table, fill value 0) so an invalid slot dequantizes to exactly the
    zeros the unquantized path gathers; the dequantized context is in
    ``q.dtype`` before any einsum, so everything downstream of the
    gather is byte-identical program structure to the full-precision
    path.

    Returns (B, T, H, D) in ``q.dtype``.
    """
    B, T, H, D = q.shape
    N, page_size, Hkv, _ = k_pages.shape
    if H % Hkv:
        raise ValueError(f"n_kv_heads ({Hkv}) must divide n_heads ({H})")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")

    ctx = block_tables.shape[1] * page_size

    def gather(pages, scales):
        g = jnp.take(pages, block_tables, axis=0, mode="fill", fill_value=0)
        g = g.reshape(B, ctx, Hkv, D)
        if scales is None:
            return g
        from chainermn_tpu.communicators.quant import dequantize_kv

        s = jnp.take(scales, block_tables, axis=0, mode="fill", fill_value=0)
        return dequantize_kv(g, s.reshape(B, ctx, Hkv), q.dtype)

    k = gather(k_pages, k_scales)
    v = gather(v_pages, v_scales)

    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    bounds = start_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None] + 1
    mask = (jnp.arange(ctx)[None, None] < bounds[:, :, None])[:, None]
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits.astype(jnp.float32)).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)
