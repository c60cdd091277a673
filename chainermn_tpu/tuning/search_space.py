"""Per-kernel search-space declarations.

Each kernel declares (a) its candidate configs, filtered to what can
actually compile — divisibility of the sequence/row count, sublane
alignment, and a VMEM budget per grid program — and (b) its cache-key
schema.  The kernel's own static default is ALWAYS a member of the
space, so the measured argmin can never be slower than shipping the
magic number (the tuner picks the default when nothing beats it).

VMEM model: the flash kernels' own footprint
(``ops.flash_attention.flash_vmem_bytes`` — streamed blocks twice,
scratch once, the fp32 ``(block_q, block_k)`` intermediates), the one the
static default is chosen by and the kernels' scoped-VMEM limit is set
from.  Candidates past the most a kernel may ask for
(``VMEM_LIMIT_MAX``) are pruned before compilation rather than left to
die as OOM (they are *also* skipped-on-error in the measure harness, for
the shapes the model misjudges).
"""

from __future__ import annotations

from typing import List, Optional

from chainermn_tpu.tuning.cache import bucket_pow2, make_key

#: candidate tile edges: every multiple-of-sublane power of two between
#: the smallest tile worth scheduling and a whole 2048 sequence.
BLOCK_CANDIDATES = (64, 128, 256, 512, 1024, 2048)

#: fused-CE row-chunk candidates; the static default 512 sits mid-range.
CE_CHUNK_CANDIDATES = (128, 256, 512, 1024, 2048, 4096)

#: cap on the transient (chunk, V) fp32 logit tile the CE scan holds.
CE_TILE_BYTES_MAX = 512 * 1024 * 1024


def _sublane(dtype) -> int:
    from chainermn_tpu.tuning.cache import dtype_name

    return 16 if dtype_name(dtype) == "bfloat16" else 8


def flash_search_space(
    Sq: int,
    Sk: int,
    D: int,
    dtype,
    which: str = "fwd",
    segmented: bool = False,
    vmem_budget: Optional[int] = None,
    window: Optional[int] = None,
) -> List[dict]:
    """Valid ``{"block_q", "block_k"}`` candidates for the flash kernels:
    blocks divide their sequence, meet the dtype's sublane alignment, and
    fit the VMEM a kernel may ask for.  The static default is inserted if
    the filters somehow excluded it (it compiles today, so it stays
    reachable)."""
    import numpy as np

    from chainermn_tpu.ops.flash_attention import (
        VMEM_LIMIT_MAX,
        flash_vmem_bytes,
    )

    if vmem_budget is None:
        vmem_budget = VMEM_LIMIT_MAX
    itemsize = np.dtype(dtype).itemsize
    sub = _sublane(dtype)
    out = []
    for bq in BLOCK_CANDIDATES:
        if bq > Sq or Sq % bq or bq % sub:
            continue
        for bk in BLOCK_CANDIDATES:
            if bk > Sk or Sk % bk or bk % sub:
                continue
            if flash_vmem_bytes(bq, bk, D, itemsize, which,
                                segmented) > vmem_budget:
                continue
            out.append({"block_q": bq, "block_k": bk})
    default = flash_default_config(Sq, Sk, D, dtype, which, segmented,
                                   window)
    if default not in out:
        out.append(default)
    return out


def flash_default_config(Sq: int, Sk: int, D: int, dtype,
                         which: str = "fwd", segmented: bool = False,
                         window: Optional[int] = None) -> dict:
    """The static default geometry (what a cache miss resolves to)."""
    from chainermn_tpu.ops.flash_attention import auto_block_size

    return {
        "block_q": auto_block_size(Sq, D, dtype, which, segmented, window),
        "block_k": auto_block_size(Sk, D, dtype, which, segmented, window),
    }


def flash_cache_key(kind: str, dev_kind: str, dtype, Sq: int, Sk: int,
                    D: int, causal: bool, window: Optional[int],
                    segmented: bool = False) -> str:
    """Cache key for the flash kernels.  ``kind``: ``fwd`` or ``bwd`` —
    forward and backward tile economics differ (the backward streams two
    extra operands and runs two kernels), so they tune independently.
    Sequence lengths are pow2-bucketed; head dim, causality, window width
    and segmenting are exact — each changes the kernel's inner loop."""
    if kind not in ("fwd", "bwd"):
        raise ValueError(f"kind must be 'fwd' or 'bwd', got {kind!r}")
    return make_key(
        f"flash_{kind}",
        dev_kind,
        dtype,
        (("q", bucket_pow2(Sq)), ("k", bucket_pow2(Sk)), ("d", D)),
        {
            "causal": bool(causal),
            "window": 0 if window is None else int(window),
            "seg": bool(segmented),
        },
    )


def ce_search_space(N: int, V: int, D: int, dtype=None) -> List[dict]:
    """Valid ``{"chunk"}`` candidates for the fused cross-entropy: chunk
    divides the row count (the scan needs equal tiles; ``_pick_chunk``
    would silently shrink a non-divisor, making it a duplicate config)
    and the transient ``(chunk, V)`` fp32 tile stays bounded.  The static
    default chunk is always included."""
    from chainermn_tpu.ops.fused_ce import DEFAULT_CHUNK, _pick_chunk

    out = []
    for c in CE_CHUNK_CANDIDATES:
        if c > N or N % c:
            continue
        if c * V * 4 > CE_TILE_BYTES_MAX:
            continue
        out.append({"chunk": c})
    default = {"chunk": _pick_chunk(N, DEFAULT_CHUNK)}
    if default not in out:
        out.append(default)
    return out


def ce_cache_key(dev_kind: str, dtype, N: int, V: int, D: int) -> str:
    """Cache key for the fused CE: token count pow2-bucketed (the scan
    length), vocab and model dim exact (they set the tile shape)."""
    return make_key(
        "fused_ce",
        dev_kind,
        dtype,
        (("n", bucket_pow2(N)), ("v", V), ("d", D)),
        {},
    )


#: candidate context-gather chunks (in PAGES) for paged decode attention:
#: how many block-table entries one gather materializes at a time.
DECODE_BLOCK_CTX_CANDIDATES = (4, 8, 16, 32, 64, 128)

#: cap on the transient gathered (batch, ctx, n_kv, D) K/V buffer a decode
#: step may materialize per gather chunk (both K and V, double-buffered).
DECODE_GATHER_BYTES_MAX = 64 * 1024 * 1024


def decode_search_space(
    n_pages: int, page_size: int, n_kv: int, D: int, dtype,
    batch: int = 8,
) -> List[dict]:
    """Valid ``{"block_ctx"}`` candidates for the paged decode-attention
    gather: chunks of at most the table width whose transient gathered
    K+V buffer stays bounded.  ``None`` → one-shot gather is the static
    default and always a member (spelled ``{"block_ctx": 0}``), so a
    tuned pick can never lose to it."""
    import numpy as np

    itemsize = np.dtype(dtype).itemsize
    out = [{"block_ctx": 0}]  # 0 = unchunked (the static default)
    for bc in DECODE_BLOCK_CTX_CANDIDATES:
        if bc >= n_pages:
            break
        per_chunk = 2 * 2 * batch * bc * page_size * n_kv * D * itemsize
        if per_chunk > DECODE_GATHER_BYTES_MAX:
            continue
        out.append({"block_ctx": bc})
    return out


def decode_cache_key(dev_kind: str, dtype, n_pages: int, page_size: int,
                     n_kv: int, D: int) -> str:
    """Cache key for the paged decode-attention gather chunk.  Page count
    is pow2-bucketed (it only scales the table width); page size, kv-head
    count and head dim are exact — they set the gathered tile shape.  The
    decode batch is NOT part of the key: the serving engine rebuckets the
    batch every iteration, and a per-batch key would fragment the cache
    across bucket churn for a knob whose optimum tracks the tile shape."""
    return make_key(
        "paged_decode",
        dev_kind,
        dtype,
        (("p", bucket_pow2(n_pages)), ("s", page_size), ("h", n_kv),
         ("d", D)),
        {},
    )


#: candidate gradient-allreduce bucket caps: the pow2 ladder around the
#: 4 MiB static default (chainermn_tpu.communicators.packing).
BUCKET_BYTES_CANDIDATES = tuple((1 << 20) * m for m in (1, 2, 4, 8, 16, 32))


def bucket_search_space(total_bytes: Optional[int] = None) -> List[dict]:
    """Candidate ``{"bucket_bytes"}`` configs for the fused gradient
    allreduce.  ``0`` (bucketing off — the legacy per-leaf/one-buffer
    lowering) is always a candidate: for small trees one unbucketed
    collective can win.  Caps beyond the first one covering the whole
    tree are pruned (they all produce the same one-bucket-per-dtype
    plan); the static default is always reachable."""
    from chainermn_tpu.communicators.packing import DEFAULT_BUCKET_BYTES

    out = [{"bucket_bytes": 0}]
    for b in BUCKET_BYTES_CANDIDATES:
        out.append({"bucket_bytes": b})
        if total_bytes is not None and b >= total_bytes:
            break
    default = {"bucket_bytes": DEFAULT_BUCKET_BYTES}
    if default not in out:
        out.append(default)
    return out


def bucket_cache_key(dev_kind: str, dtype, total_bytes: int,
                     n_leaves: int, communicator: str) -> str:
    """Cache key for the allreduce bucket cap: total gradient bytes and
    leaf count pow2-bucketed (the economics shift with both), dominant
    dtype and communicator name exact (each variant's collective pattern
    prices buckets differently)."""
    return make_key(
        "allreduce_bucket",
        dev_kind,
        dtype,
        (("b", bucket_pow2(total_bytes)), ("l", bucket_pow2(n_leaves))),
        {"comm": str(communicator)},
    )


#: candidate overlap-schedule stage widths (buckets emitted per stage):
#: 1 is maximal overlap (each bucket's allreduce-start issues the moment
#: its last grad leaf exists), wider stages amortize dispatch overhead
#: when buckets are small.
OVERLAP_GRANULARITY_CANDIDATES = (1, 2, 4)


def overlap_schedule_search_space(
        total_bytes: Optional[int] = None) -> List[dict]:
    """Candidate ``{"granularity", "bucket_bytes"}`` configs for the
    backward-overlapped allreduce schedule — the cross product of stage
    width and the (nonzero) bucket-cap ladder, since the two knobs trade
    against each other: smaller buckets expose more overlap points but
    need wider stages to keep per-collective dispatch cost amortized.
    The static default (granularity 1 × the 4 MiB default cap) is always
    first; ``bucket_bytes=0`` is excluded because the unbucketed path
    has no schedule to stage."""
    from chainermn_tpu.communicators.packing import DEFAULT_BUCKET_BYTES

    caps = [c["bucket_bytes"] for c in bucket_search_space(total_bytes)
            if c["bucket_bytes"] > 0]
    out = [{"granularity": 1, "bucket_bytes": DEFAULT_BUCKET_BYTES}]
    for g in OVERLAP_GRANULARITY_CANDIDATES:
        for b in caps:
            cfg = {"granularity": g, "bucket_bytes": b}
            if cfg not in out:
                out.append(cfg)
    return out


def overlap_cache_key(dev_kind: str, dtype, total_bytes: int,
                      n_leaves: int, communicator: str) -> str:
    """Cache key for the overlap schedule: same family signature as
    :func:`bucket_cache_key` (the schedule is a property of the same
    tree family) under a distinct kernel tag, so the two tuned answers
    coexist and ``bucket_bytes`` tuned alone stays valid."""
    return make_key(
        "overlap_schedule",
        dev_kind,
        dtype,
        (("b", bucket_pow2(total_bytes)), ("l", bucket_pow2(n_leaves))),
        {"comm": str(communicator)},
    )


def comm_dtype_search_space() -> List[dict]:
    """Candidate ``{"comm_dtype"}`` configs for the gradient wire dtype:
    ``"none"`` (full precision — the static default, pinned first so a
    tuned pick can never lose to it) plus every canonical narrow wire
    dtype.  Unlike the other spaces this one trades a little accuracy
    (bounded per dtype, see ``communicators.quant``) for wire bytes, so
    the tuner records the measured quantization error alongside the
    timing for the operator to veto."""
    from chainermn_tpu.communicators.quant import COMM_DTYPE_CHOICES

    return [{"comm_dtype": "none"}] + [
        {"comm_dtype": c} for c in COMM_DTYPE_CHOICES
    ]


def comm_dtype_cache_key(dev_kind: str, dtype, total_bytes: int,
                         n_leaves: int, communicator: str) -> str:
    """Cache key for the gradient wire dtype: same family signature as
    :func:`bucket_cache_key` (the trade-off is a property of the same
    tree family) under its own kernel tag."""
    return make_key(
        "comm_dtype",
        dev_kind,
        dtype,
        (("b", bucket_pow2(total_bytes)), ("l", bucket_pow2(n_leaves))),
        {"comm": str(communicator)},
    )


def kv_dtype_search_space() -> List[dict]:
    """Candidate ``{"kv_dtype"}`` configs for KV page storage: ``"none"``
    (model dtype — the static default) plus every canonical quantized
    page dtype."""
    from chainermn_tpu.communicators.quant import KV_DTYPE_CHOICES

    return [{"kv_dtype": "none"}] + [
        {"kv_dtype": c} for c in KV_DTYPE_CHOICES
    ]


def kv_dtype_cache_key(dev_kind: str, dtype, n_pages: int, page_size: int,
                       n_kv: int, d_head: int) -> str:
    """Cache key for the KV page dtype: same geometry signature as
    :func:`decode_cache_key` (the decision is a property of the same
    page shape) under its own kernel tag."""
    return make_key(
        "kv_dtype",
        dev_kind,
        dtype,
        (("p", bucket_pow2(n_pages)), ("s", page_size), ("h", n_kv),
         ("d", d_head)),
        {},
    )


def draft_search_space(n_layers: int) -> List[dict]:
    """Candidate ``{"draft", "draft_layers"}`` configs for the
    speculative draft source: ``"ngram"`` (model-free — the static
    default) plus the layer-truncated self-draft at a few depths.
    Deeper drafts accept longer but cost more per proposal, so the
    trade lands differently per model family — exactly what the
    measured argmin is for."""
    ks = sorted({max(1, int(n_layers) // 4), max(1, int(n_layers) // 2)})
    return [{"draft": "ngram", "draft_layers": 0}] + [
        {"draft": "model", "draft_layers": k} for k in ks
    ]


def draft_cache_key(dev_kind: str, dtype, vocab: int, d_model: int,
                    n_layers: int, max_len: int) -> str:
    """Cache key for the draft source: a property of the target model
    family (vocab/width/depth) and the serving context budget, under
    its own kernel tag."""
    return make_key(
        "draft",
        dev_kind,
        dtype,
        (("v", bucket_pow2(vocab)), ("d", bucket_pow2(d_model)),
         ("l", int(n_layers)), ("c", bucket_pow2(max_len))),
        {},
    )


def prefill_chunk_search_space(max_len: int,
                               block_size: int) -> List[dict]:
    """Candidate ``{"prefill_chunk"}`` token-slice sizes for chunked
    prefill: 0 (off — monolithic prefill, the static default) plus
    page-aligned slices strictly below the context budget.  Smaller
    slices bound decode p99 tighter but pay more scheduler iterations
    per prompt; the sweet spot is a property of the page geometry."""
    out = [{"prefill_chunk": 0}]
    for mult in (8, 16, 32, 64):
        c = int(block_size) * mult
        if 0 < c < int(max_len):
            out.append({"prefill_chunk": c})
    return out


def prefill_chunk_cache_key(dev_kind: str, max_len: int,
                            block_size: int) -> str:
    """Cache key for the prefill slice size: the page geometry and
    context budget alone (dtype-independent — the chunk program is the
    same jitted step either way)."""
    return make_key(
        "prefill_chunk",
        dev_kind,
        "none",
        (("c", bucket_pow2(max_len)), ("s", int(block_size))),
        {},
    )


def layout_search_space(mesh_axes, params=None, mesh=None) -> List[dict]:
    """Candidate ``{"plan"}`` configs for the parameter-layout search:
    every registry sharding plan whose axes the mesh has — and, when a
    parameter tree (and optionally the mesh, for divisibility) is given,
    that validates clean against it.  The ``dp`` plan (pure data
    parallelism, everything replicated — today's hand-picked layout) is
    pinned first as the static default, so a tuned layout can never
    lose to shipping no plan at all."""
    from chainermn_tpu.sharding import list_plans, validate

    axes = set(mesh_axes)
    out = [{"plan": "dp"}]
    for plan in list_plans():
        if plan.name == "dp" or not set(plan.axes) <= axes:
            continue
        if params is not None and not validate(plan, params, mesh).ok:
            continue
        out.append({"plan": plan.name})
    return out


def serve_group_search_space(n_heads: int, d_ff: int, d_model: int,
                             n_devices: int,
                             max_batch: int) -> List[dict]:
    """Candidate ``{"group_size", "pp_stages"}`` shard-group shapes for
    the serving cluster: how many tensor-parallel shards one replica
    spans (the registry ``tp`` plan over that many devices) crossed
    with how many pipeline microbatch stages the decode batch splits
    into.  ``{1, 1}`` (today's single-shard replica) is pinned first as
    the static default; group sizes must divide the model's heads, FFN
    and width AND fit the local device count, pipeline depths must
    leave each microbatch at least one row.  Bit-exactness makes every
    candidate produce identical streams — wall time per workload is the
    whole trade."""
    out = [{"group_size": 1, "pp_stages": 1}]
    groups = [1] + [
        k for k in (2, 4)
        if k <= int(n_devices)
        and n_heads % k == 0 and d_ff % k == 0 and d_model % k == 0
    ]
    stages = [1] + [s for s in (2, 4) if s <= int(max_batch)]
    for k in groups:
        for s in stages:
            cfg = {"group_size": k, "pp_stages": s}
            if cfg not in out:
                out.append(cfg)
    return out


def serve_group_cache_key(dev_kind: str, dtype, vocab: int, d_model: int,
                          n_layers: int, max_len: int, n_devices: int,
                          max_batch: int) -> str:
    """Cache key for the shard-group shape: model family (pow2-bucketed
    like the draft key), the serving context budget, and — unlike the
    single-engine tuners — the local device count and decode batch
    ceiling, since they bound the candidate set itself."""
    return make_key(
        "serve_group",
        dev_kind,
        dtype,
        (("v", bucket_pow2(vocab)), ("d", bucket_pow2(d_model)),
         ("l", int(n_layers)), ("c", bucket_pow2(max_len))),
        {"dev": str(int(n_devices)), "b": str(int(max_batch))},
    )


def layout_cache_key(dev_kind: str, dtype, n_params: int, n_leaves: int,
                     mesh_shape, model: str = "transformer_lm") -> str:
    """Cache key for the layout search: parameter count and leaf count
    pow2-bucketed (layout economics shift with model scale, not exact
    width), mesh shape and model family exact — the same plan table
    prices completely differently on a (8,) ring vs a (4, 2) torus, and
    across model families with different shardable structure."""
    return make_key(
        "layout",
        dev_kind,
        dtype,
        (("p", bucket_pow2(max(1, n_params))),
         ("l", bucket_pow2(max(1, n_leaves)))),
        {"mesh": "x".join(str(int(s)) for s in mesh_shape),
         "model": str(model)},
    )
