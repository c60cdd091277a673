"""Ulysses-style sequence parallelism — all-to-all head↔sequence reshard.

Net-new capability (SURVEY §5.7).  The insight: attention is embarrassingly
parallel over *heads* but all-to-all over *sequence*, so when activations
arrive sequence-sharded, two ``lax.all_to_all``s re-shard to head-sharded
(full sequence per chip, H/n heads), run ordinary full attention locally,
and re-shard back.  The reference's differentiable ``alltoall`` function
(REF:chainermn/functions/collective_communication.py) is the primitive
this generalizes.

Compared with ring attention: one pair of all-to-alls instead of n
ppermute steps (lower latency on small worlds), but requires ``H % n == 0``
and holds the full sequence per chip during attention (memory ∝ S).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..communicators.mesh_utils import axis_size_traced


def ulysses_attention(
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
    """Sequence-parallel attention via head↔sequence all-to-all.

    q, k, v: (B, S_local, H, D) sequence-sharded inputs (inside
    ``shard_map`` over ``axis_name``); returns (B, S_local, H, D).
    Requires the head count H to be divisible by the axis size.
    ``q_segment_ids``/``kv_segment_ids``: optional (B, S_local) int32
    LOCAL shards of packed-sequence segment ids — all-gathered alongside
    the head reshard (attention here runs over the FULL sequence per
    chip) — or already-full (B, S_local * n) ids, used as-is (the
    adapter's closure-constant path, no collective).  Passed to the
    shared flash kernel's segment masks.

    ``window``: optional sliding-window size.  Unique among the SP
    layers, ulysses supports it EXACTLY: after the head all-to-all each
    chip holds the full sequence, so the kernel's global causal band
    applies unchanged (ring/zigzag would need cross-shard band
    bookkeeping and deliberately reject it).
    """
    n = axis_size_traced(axis_name)
    B, S_loc, H, D = q.shape
    Hk = k.shape[2]
    if H % n:
        raise ValueError(f"head count {H} not divisible by axis size {n}")
    if Hk != H and (H % Hk or Hk % n):
        # GQA: kv heads must divide the query heads AND the axis size —
        # the head all-to-all deals kv heads across chips too, after
        # which the shared flash kernel regroups (H/n)/(Hk/n) = G
        # query heads per kv head locally.
        raise ValueError(
            f"kv head count {Hk} must divide query heads {H} and be "
            f"divisible by axis size {n}"
        )
    if scale is None:
        scale = 1.0 / (D**0.5)
    if kv_segment_ids is not None and q_segment_ids is None:
        raise ValueError(
            "kv_segment_ids without q_segment_ids would be silently "
            "ignored; pass q_segment_ids (optionally alone — kv defaults "
            "to it)"
        )
    if kv_segment_ids is None:
        kv_segment_ids = q_segment_ids

    # (B, S_loc, H, D) → (B, S_full, H/n, D): split heads, concat sequence.
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)

    qs = ks = None
    if q_segment_ids is not None:
        def full_ids(ids):
            ids = ids.astype(jnp.int32)
            if ids.shape[1] == S_loc * n:
                return ids  # already full-sequence: no collective needed
            if ids.shape[1] != S_loc:
                raise ValueError(
                    f"segment ids sequence length {ids.shape[1]} is "
                    f"neither local ({S_loc}) nor full ({S_loc * n})"
                )
            return lax.all_gather(ids, axis_name, axis=1, tiled=True)

        qs = full_ids(q_segment_ids)
        ks = full_ids(kv_segment_ids)

    # Local compute on the full sequence / head shard: the hot attention op
    # shared with ops.flash_attention (Pallas kernel where shapes allow,
    # XLA fallback otherwise — one implementation of the math to maintain).
    from chainermn_tpu.ops.flash_attention import flash_attention

    out = flash_attention(
        qh, kh, vh, causal=causal, scale=scale,
        q_segment_ids=qs, kv_segment_ids=ks, window=window,
    )
    return to_seq(out.astype(q.dtype))


def make_ulysses_attention_fn(axis_name: str, causal: bool = True,
                              segment_ids=None, window=None):
    """Adapter matching the transformer layers' ``attention_fn`` slot.
    ``segment_ids``: optional row-uniform GLOBAL (S,) packed-sequence
    ids, sliced per shard at call time via the traced axis index.
    ``window``: as ``make_flash_attention_fn``'s — a row that has its own
    hands it over at the call."""
    from chainermn_tpu.ops.flash_attention import row_window

    from chainermn_tpu.parallel.ring_attention import refuse_block_diffusion

    own_window = window

    def fn(q, k, v, mask=None, window=None, block_diffusion=None):
        del mask
        refuse_block_diffusion(block_diffusion, "ulysses attention")
        qs = None
        if segment_ids is not None:
            if segment_ids.ndim != 1:
                raise ValueError(
                    "adapter segment_ids must be row-uniform GLOBAL (S,)"
                )
            # The closure already holds the FULL row: broadcast it
            # directly — attention runs over the full sequence here, so
            # no slice-then-all_gather round trip is needed.
            qs = jnp.broadcast_to(
                segment_ids.astype(jnp.int32)[None],
                (q.shape[0], segment_ids.shape[0]),
            )
        return ulysses_attention(
            q, k, v, axis_name, causal=causal, q_segment_ids=qs,
            window=row_window(own_window, window),
        )

    return fn
