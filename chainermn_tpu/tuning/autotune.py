"""Tuners (measure → pick → persist) and the runtime lookups the ops
consult.

The tuners only ever run explicitly (CLI / ``bench.py --autotune``) —
never from inside an op.  The lookups are trace-time reads of the
persistent cache, validated against the *actual* call shape (pow2
bucketing means a 3072-long call can hit a 4096-bucket entry whose
blocks do not divide it — such an entry is ignored, not an error), and
return None whenever tuning is disabled, off-TPU, or on a miss; the ops
then use their static defaults.
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Tuple

import jax

from chainermn_tpu.tuning.cache import (
    TuneCache,
    autotune_enabled,
    device_kind,
    dtype_name,
    runtime_lookup_enabled,
    shared_cache,
)
from chainermn_tpu.tuning.measure import best_config, measure_candidates
from chainermn_tpu.tuning.search_space import (
    bucket_cache_key,
    bucket_search_space,
    ce_cache_key,
    ce_search_space,
    comm_dtype_cache_key,
    comm_dtype_search_space,
    decode_cache_key,
    decode_search_space,
    draft_cache_key,
    draft_search_space,
    flash_cache_key,
    flash_default_config,
    flash_search_space,
    kv_dtype_cache_key,
    kv_dtype_search_space,
    layout_cache_key,
    layout_search_space,
    overlap_cache_key,
    overlap_schedule_search_space,
    prefill_chunk_cache_key,
    prefill_chunk_search_space,
    serve_group_cache_key,
    serve_group_search_space,
)


def _blocks_valid(bq: int, bk: int, Sq: int, Sk: int, dtype) -> bool:
    """Mirror of ``flash_attention``'s compiled-path gate: blocks divide
    their sequences and meet the dtype's sublane alignment."""
    sub = 16 if dtype_name(dtype) == "bfloat16" else 8
    return (
        bq >= 1 and bk >= 1
        and Sq % bq == 0 and Sk % bk == 0
        and bq % sub == 0 and bk % sub == 0
    )


# --------------------------------------------------------------------------
# Runtime lookups — what flash_attention / fused_cross_entropy call when the
# caller does not pin a geometry.
# --------------------------------------------------------------------------


def lookup_flash_blocks(
    kind: str,
    *,
    Sq: int,
    Sk: int,
    D: int,
    dtype,
    causal: bool,
    window: Optional[int] = None,
    segmented: bool = False,
) -> Optional[Tuple[int, int]]:
    """Tuned ``(block_q, block_k)`` for the flash ``kind`` (``fwd`` /
    ``bwd``) or None (miss, invalid entry, or lookups disabled)."""
    if not runtime_lookup_enabled():
        return None
    try:
        key = flash_cache_key(
            kind, device_kind(), dtype, Sq, Sk, D, causal, window, segmented
        )
        entry = shared_cache().get(key)
        if not entry:
            return None
        bq, bk = int(entry["block_q"]), int(entry["block_k"])
    except Exception:
        return None
    if not _blocks_valid(bq, bk, Sq, Sk, dtype):
        return None
    return bq, bk


def lookup_ce_chunk(*, N: int, V: int, D: int, dtype) -> Optional[int]:
    """Tuned fused-CE row chunk or None (miss / disabled)."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(
            ce_cache_key(device_kind(), dtype, N, V, D)
        )
        if not entry:
            return None
        chunk = int(entry["chunk"])
    except Exception:
        return None
    return chunk if chunk >= 1 else None


def lookup_bucket_bytes(*, total_bytes: int, n_leaves: int, dtype,
                        communicator: str) -> Optional[int]:
    """Tuned gradient-allreduce bucket cap for one (tree size, leaf
    count, dominant dtype, communicator) family, or None (miss /
    disabled).  ``0`` is a valid tuned answer: the measured winner was
    the unbucketed path."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(bucket_cache_key(
            device_kind(), dtype, total_bytes, n_leaves, communicator
        ))
        if not entry:
            return None
        bb = int(entry["bucket_bytes"])
    except Exception:
        return None
    return bb if bb >= 0 else None


def lookup_overlap_schedule(*, total_bytes: int, n_leaves: int, dtype,
                            communicator: str) -> Optional[dict]:
    """Tuned overlap schedule (``{"granularity", "bucket_bytes"}``) for
    one (tree size, leaf count, dominant dtype, communicator) family, or
    None (miss / disabled).  Consulted by the communicators'
    ``resolve_overlap_granularity`` at trace time, after the ctor and
    ``CHAINERMN_TPU_OVERLAP_GRANULARITY`` env overrides."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(overlap_cache_key(
            device_kind(), dtype, total_bytes, n_leaves, communicator
        ))
        if not entry:
            return None
        g = int(entry["granularity"])
        bb = int(entry.get("bucket_bytes", -1))
    except Exception:
        return None
    if g < 1:
        return None
    return {"granularity": g, "bucket_bytes": bb if bb > 0 else None}


def lookup_decode_block_ctx(*, n_pages: int, page_size: int, n_kv: int,
                            d_head: int, dtype) -> Optional[int]:
    """Tuned context-gather chunk (in pages) for paged decode attention,
    or None (one-shot gather) on a miss / off-TPU / under pytest.  The
    inert-off-TPU guard doubles as the serving engine's determinism
    guard: CPU decode numerics never depend on the tune cache."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(decode_cache_key(
            device_kind(), dtype, n_pages, page_size, n_kv, d_head
        ))
        if not entry:
            return None
        bc = int(entry["block_ctx"])
    except Exception:
        return None
    return bc if bc >= 1 else None


def lookup_comm_dtype(*, total_bytes: int, n_leaves: int, dtype,
                      communicator: str) -> Optional[str]:
    """Tuned gradient wire dtype (canonical ``"int8"``/``"fp8"``) for
    one (tree size, leaf count, dominant dtype, communicator) family, or
    None (full precision) on a miss / off-TPU / under pytest.  Consulted
    by ``CommunicatorBase.resolve_comm_dtype`` after the ctor and
    ``CHAINERMN_TPU_COMM_DTYPE`` overrides — and like every lookup it is
    inert under pytest, so tier-1 gradients never quantize by surprise."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(comm_dtype_cache_key(
            device_kind(), dtype, total_bytes, n_leaves, communicator
        ))
        if not entry:
            return None
        from chainermn_tpu.communicators.quant import canonical_comm_dtype

        cd = canonical_comm_dtype(str(entry["comm_dtype"]))
    except Exception:
        return None
    return None if cd in (None, "none") else cd


def lookup_kv_dtype(*, n_pages: int, page_size: int, n_kv: int,
                    d_head: int, dtype) -> Optional[str]:
    """Tuned KV page storage dtype (canonical ``"int8"``) for one page
    geometry, or None (model dtype) on a miss / off-TPU / under pytest.
    Consulted by the serving engine's ``kv_dtype`` resolution after the
    config and ``CHAINERMN_TPU_KV_DTYPE`` overrides."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(kv_dtype_cache_key(
            device_kind(), dtype, n_pages, page_size, n_kv, d_head
        ))
        if not entry:
            return None
        from chainermn_tpu.communicators.quant import canonical_kv_dtype

        return canonical_kv_dtype(str(entry["kv_dtype"]))
    except Exception:
        return None


def lookup_draft(*, vocab: int, d_model: int, n_layers: int,
                 max_len: int, dtype) -> Optional[str]:
    """Tuned speculative draft source (``"ngram"``/``"model"``) for one
    target model family, or None (n-gram) on a miss / off-TPU / under
    pytest.  Consulted by the serving engine's ``draft`` resolution
    after the config and ``CHAINERMN_TPU_DRAFT`` overrides — inert
    under pytest like every lookup, so tier-1 never builds a draft
    model by surprise."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(draft_cache_key(
            device_kind(), dtype, vocab, d_model, n_layers, max_len
        ))
        if not entry:
            return None
        src = str(entry["draft"])
    except Exception:
        return None
    return src if src in ("ngram", "model") else None


def lookup_draft_layers(*, vocab: int, d_model: int, n_layers: int,
                        max_len: int, dtype) -> Optional[int]:
    """Companion to :func:`lookup_draft`: the tuned draft depth for the
    same key, or None (the engine's ``n_layers // 2`` default)."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(draft_cache_key(
            device_kind(), dtype, vocab, d_model, n_layers, max_len
        ))
        if not entry or entry.get("draft") != "model":
            return None
        k = int(entry["draft_layers"])
    except Exception:
        return None
    return k if k >= 1 else None


def lookup_prefill_chunk(*, max_len: int,
                         block_size: int) -> Optional[int]:
    """Tuned chunked-prefill slice size (tokens) for one page geometry,
    or None (0 — monolithic prefill) on a miss / off-TPU / under
    pytest.  Consulted by the serving engine's ``prefill_chunk``
    resolution after the config and ``CHAINERMN_TPU_PREFILL_CHUNK``
    overrides."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(prefill_chunk_cache_key(
            device_kind(), max_len, block_size
        ))
        if not entry:
            return None
        c = int(entry["prefill_chunk"])
    except Exception:
        return None
    return c if c > 0 else None


def lookup_layout(*, mesh, n_params: int, n_leaves: int, dtype,
                  model: str = "transformer_lm") -> Optional[str]:
    """Tuned registry-plan name for one (model family, scale, mesh
    shape) — or None (miss / disabled / the cached plan no longer fits
    the mesh).  Callers resolve the name via
    ``chainermn_tpu.sharding.get_plan``."""
    if not runtime_lookup_enabled():
        return None
    try:
        entry = shared_cache().get(layout_cache_key(
            device_kind(), dtype, n_params, n_leaves,
            tuple(mesh.devices.shape), model,
        ))
        if not entry:
            return None
        name = str(entry["plan"])
        from chainermn_tpu.sharding import get_plan

        plan = get_plan(name)
    except Exception:
        return None
    if not set(plan.axes) <= set(mesh.axis_names):
        return None
    return name


# --------------------------------------------------------------------------
# Tuners.
# --------------------------------------------------------------------------


def _require_tuning_allowed(what: str):
    if not autotune_enabled():
        raise RuntimeError(
            f"autotuning ({what}) is disabled in this context — under "
            "pytest the tuner is inert by design (tier-1 determinism "
            "guard), and CHAINERMN_TPU_AUTOTUNE=0 disables it explicitly"
        )


def _finish(key, results, default_cfg, cache, extra):
    """Pick the winner, fold in provenance, persist."""
    best = best_config(results)
    if best is None:
        return {"key": key, "chosen": None, "results": results,
                "error": "every candidate failed"}
    default_secs = next(
        (r["seconds"] for r in results if r["config"] == default_cfg),
        None,
    )
    entry = dict(best["config"])
    entry.update(
        seconds=best["seconds"],
        default_config=default_cfg,
        default_seconds=default_secs,
        speedup_vs_default=(
            round(default_secs / best["seconds"], 4)
            if default_secs else None
        ),
        candidates_timed=sum(1 for r in results if r["seconds"] is not None),
        candidates_skipped=sum(1 for r in results if r["seconds"] is None),
        device_kind=device_kind(),
        jax_version=jax.__version__,
        tuned_at=time.strftime("%Y-%m-%dT%H:%M:%S"),
        source="chainermn_tpu.tuning.autotune",
        **extra,
    )
    cache.put(key, entry)
    cache.save()
    return {"key": key, "chosen": dict(best["config"]),
            "seconds": best["seconds"], "default_seconds": default_secs,
            "speedup_vs_default": entry["speedup_vs_default"],
            "results": results, "cache_path": cache.path}


def tune_flash(
    *,
    Sq: int,
    Sk: int,
    D: int,
    dtype="bfloat16",
    causal: bool = True,
    window: Optional[int] = None,
    batch_heads: int = 8,
    cache: Optional[TuneCache] = None,
    n1: int = 3,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the flash attention forward AND backward block geometry for
    one shape family; returns ``{"fwd": record, "bwd": record}``.

    The backward sweep pins the forward blocks to the forward winner and
    varies only the backward geometry (``jax.grad`` re-runs the forward,
    so holding it constant isolates the backward's contribution to the
    argmin).  ``dry_run`` enumerates candidates without compiling or
    timing anything.
    """
    import numpy as np

    fwd_space = flash_search_space(Sq, Sk, D, dtype, which="fwd",
                                   window=window)
    bwd_space = flash_search_space(Sq, Sk, D, dtype, which="bwd",
                                   window=window)
    default_cfg = flash_default_config(Sq, Sk, D, dtype, "fwd",
                                       window=window)
    default_bwd = flash_default_config(Sq, Sk, D, dtype, "bwd",
                                       window=window)
    dev = device_kind()
    fwd_key = flash_cache_key("fwd", dev, dtype, Sq, Sk, D, causal, window)
    bwd_key = flash_cache_key("bwd", dev, dtype, Sq, Sk, D, causal, window)
    if dry_run:
        return {
            "kernel": "flash", "dry_run": True,
            "fwd": {"key": fwd_key, "candidates": fwd_space,
                    "default": default_cfg},
            "bwd": {"key": bwd_key, "candidates": bwd_space,
                    "default": default_bwd},
        }
    _require_tuning_allowed("flash attention")
    cache = cache or shared_cache()

    from chainermn_tpu.ops.flash_attention import _flash_bh, _flash_bh_fwd
    from chainermn_tpu.utils.profiling import sync

    scale = 1.0 / (D ** 0.5)
    rng = np.random.RandomState(0)
    q = jax.numpy.asarray(
        rng.randn(batch_heads, Sq, D), dtype_name(dtype)
    )
    k = jax.numpy.asarray(
        rng.randn(batch_heads, Sk, D), dtype_name(dtype)
    )
    v = jax.numpy.asarray(
        rng.randn(batch_heads, Sk, D), dtype_name(dtype)
    )

    out = {"kernel": "flash"}

    cached = cache.get(fwd_key) if not force else None
    if cached and _blocks_valid(
        int(cached.get("block_q", 0)), int(cached.get("block_k", 0)),
        Sq, Sk, dtype,
    ):
        out["fwd"] = {
            "key": fwd_key, "cached": True,
            "chosen": {"block_q": int(cached["block_q"]),
                       "block_k": int(cached["block_k"])},
        }
    else:
        if log:
            log(f"flash fwd {fwd_key}: {len(fwd_space)} candidates")

        def build_fwd(cfg):
            f = jax.jit(
                lambda q, k, v: _flash_bh_fwd(
                    q, k, v, scale=scale, causal=causal,
                    block_q=cfg["block_q"], block_k=cfg["block_k"],
                    interpret=False, window=window,
                )[0]
            )

            def run(n):
                t0 = time.perf_counter()
                for _ in range(n):
                    o = f(q, k, v)
                sync(o)
                return time.perf_counter() - t0

            return run

        results = measure_candidates(
            build_fwd, fwd_space, n1=n1, repeats=repeats, log=log
        )
        out["fwd"] = _finish(
            fwd_key, results, default_cfg, cache,
            {"kernel": "flash_fwd", "dtype": dtype_name(dtype),
             "Sq": Sq, "Sk": Sk, "D": D, "causal": causal,
             "window": window, "batch_heads": batch_heads},
        )

    fq = out["fwd"]["chosen"] or default_cfg
    cached = cache.get(bwd_key) if not force else None
    if cached and _blocks_valid(
        int(cached.get("block_q", 0)), int(cached.get("block_k", 0)),
        Sq, Sk, dtype,
    ):
        out["bwd"] = {
            "key": bwd_key, "cached": True,
            "chosen": {"block_q": int(cached["block_q"]),
                       "block_k": int(cached["block_k"])},
        }
        return out
    if log:
        log(f"flash bwd {bwd_key}: {len(bwd_space)} candidates")

    def build_bwd(cfg):
        def loss(q, k, v):
            return _flash_bh(
                q, k, v, scale, causal, fq["block_q"], fq["block_k"],
                False, window, cfg["block_q"], cfg["block_k"],
            ).sum()

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                dq, dk, dv = g(q, k, v)
            sync(dq)
            return time.perf_counter() - t0

        return run

    results = measure_candidates(
        build_bwd, bwd_space, n1=n1, repeats=repeats, log=log
    )
    out["bwd"] = _finish(
        bwd_key, results, default_bwd, cache,
        {"kernel": "flash_bwd", "dtype": dtype_name(dtype),
         "Sq": Sq, "Sk": Sk, "D": D, "causal": causal,
         "window": window, "batch_heads": batch_heads,
         "fwd_blocks": fq},
    )
    return out


def tune_fused_ce(
    *,
    N: int,
    V: int,
    D: int,
    dtype="bfloat16",
    cache: Optional[TuneCache] = None,
    n1: int = 3,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the fused cross-entropy row chunk for an ``(N, V, D)`` loss
    head; times the full fwd+bwd (``value_and_grad``), which is what the
    training step pays."""
    import numpy as np

    from chainermn_tpu.ops.fused_ce import DEFAULT_CHUNK, _pick_chunk

    space = ce_search_space(N, V, D, dtype)
    default_cfg = {"chunk": _pick_chunk(N, DEFAULT_CHUNK)}
    key = ce_cache_key(device_kind(), dtype, N, V, D)
    if dry_run:
        return {"kernel": "fused_ce", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("fused cross-entropy")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and int(cached.get("chunk", 0)) >= 1:
        return {"kernel": "fused_ce", "key": key, "cached": True,
                "chosen": {"chunk": int(cached["chunk"])}}

    from chainermn_tpu.ops.fused_ce import fused_cross_entropy
    from chainermn_tpu.utils.profiling import sync

    rng = np.random.RandomState(0)
    h = jax.numpy.asarray(rng.randn(N, D), dtype_name(dtype))
    emb = jax.numpy.asarray(rng.randn(V, D), dtype_name(dtype))
    labels = jax.numpy.asarray(
        rng.randint(0, V, size=(N,)), "int32"
    )
    if log:
        log(f"fused_ce {key}: {len(space)} candidates")

    def build(cfg):
        g = jax.jit(jax.value_and_grad(
            lambda h, emb: fused_cross_entropy(
                h, emb, labels, chunk=cfg["chunk"]
            ),
            argnums=(0, 1),
        ))

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                _loss, (dh, _demb) = g(h, emb)
            sync(dh)
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "fused_ce", "dtype": dtype_name(dtype),
         "N": N, "V": V, "D": D},
    )
    rec["kernel"] = "fused_ce"
    return rec


def tune_allreduce_bucket(
    *,
    communicator: str = "xla_ici",
    total_mb: float = 64.0,
    n_leaves: int = 64,
    dtype="float32",
    mesh=None,
    cache: Optional[TuneCache] = None,
    n1: int = 3,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the gradient-allreduce ``bucket_bytes`` for one tree family.

    Times ``eager_allreduce_grad`` over the shared synthetic mixed-shape
    tree (``packing.synthetic_grad_tree``) at each candidate cap —
    including 0, the unbucketed path — and persists the argmin under a
    key the communicators' trace-time ``resolve_bucket_bytes`` lookup
    reads back on TPU."""
    import numpy as np

    from chainermn_tpu.communicators.packing import (
        DEFAULT_BUCKET_BYTES,
        synthetic_grad_tree,
    )

    total_bytes = int(total_mb * 1024 * 1024)
    tree = synthetic_grad_tree(n_leaves, total_bytes, dtypes=(dtype,))
    total_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree)
    )
    space = bucket_search_space(total_bytes)
    default_cfg = {"bucket_bytes": DEFAULT_BUCKET_BYTES}
    key = bucket_cache_key(
        device_kind(), dtype, total_bytes, n_leaves, communicator
    )
    if dry_run:
        return {"kernel": "allreduce_bucket", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("allreduce bucketing")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and int(cached.get("bucket_bytes", -1)) >= 0:
        return {"kernel": "allreduce_bucket", "key": key, "cached": True,
                "chosen": {"bucket_bytes": int(cached["bucket_bytes"])}}

    from chainermn_tpu.communicators import create_communicator
    from chainermn_tpu.utils.profiling import sync

    n = None  # filled by the first build
    if log:
        log(f"allreduce_bucket {key}: {len(space)} candidates")

    def build(cfg):
        nonlocal n
        comm = create_communicator(
            communicator, mesh=mesh, bucket_bytes=cfg["bucket_bytes"]
        )
        n = comm.device_size
        stacked = jax.tree_util.tree_map(
            lambda l: jax.numpy.stack([jax.numpy.asarray(l)] * n), tree
        )

        def run(k):
            t0 = time.perf_counter()
            out = stacked
            for _ in range(k):
                out = comm.eager_allreduce_grad(out)
            sync(jax.tree_util.tree_leaves(out)[0])
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "allreduce_bucket", "dtype": dtype_name(dtype),
         "communicator": communicator, "total_bytes": total_bytes,
         "n_leaves": n_leaves, "device_size": n},
    )
    rec["kernel"] = "allreduce_bucket"
    return rec


def tune_overlap_schedule(
    *,
    communicator: str = "xla_ici",
    total_mb: float = 64.0,
    n_leaves: int = 64,
    dtype="float32",
    mesh=None,
    cache: Optional[TuneCache] = None,
    n1: int = 3,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the backward-overlap schedule (stage granularity ×
    ``bucket_bytes``) for one tree family.

    Times the overlapped ``eager_allreduce_grad`` at each candidate —
    the schedule's win is how well ``all-reduce-start`` pairs hide under
    the backward compute the latency-hiding scheduler interleaves, so
    this tuner is only meaningful on TPU (the shared
    ``_require_tuning_allowed`` gate already refuses under pytest).
    Persists the argmin under a key the communicators' trace-time
    ``resolve_overlap_granularity`` lookup reads back."""
    from chainermn_tpu.communicators.packing import synthetic_grad_tree

    total_bytes = int(total_mb * 1024 * 1024)
    tree = synthetic_grad_tree(n_leaves, total_bytes, dtypes=(dtype,))
    total_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree)
    )
    space = overlap_schedule_search_space(total_bytes)
    default_cfg = space[0]  # granularity 1 × the static default cap
    key = overlap_cache_key(
        device_kind(), dtype, total_bytes, n_leaves, communicator
    )
    if dry_run:
        return {"kernel": "overlap_schedule", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("overlap schedule")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and int(cached.get("granularity", 0)) >= 1:
        return {"kernel": "overlap_schedule", "key": key, "cached": True,
                "chosen": {
                    "granularity": int(cached["granularity"]),
                    "bucket_bytes": int(cached["bucket_bytes"]),
                }}

    from chainermn_tpu.communicators import create_communicator
    from chainermn_tpu.utils.profiling import sync

    n = None
    if log:
        log(f"overlap_schedule {key}: {len(space)} candidates")

    def build(cfg):
        nonlocal n
        comm = create_communicator(
            communicator, mesh=mesh,
            bucket_bytes=cfg["bucket_bytes"],
            overlap=True, overlap_granularity=cfg["granularity"],
        )
        n = comm.device_size
        stacked = jax.tree_util.tree_map(
            lambda l: jax.numpy.stack([jax.numpy.asarray(l)] * n), tree
        )

        def run(k):
            t0 = time.perf_counter()
            out = stacked
            for _ in range(k):
                out = comm.eager_allreduce_grad(out)
            sync(jax.tree_util.tree_leaves(out)[0])
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "overlap_schedule", "dtype": dtype_name(dtype),
         "communicator": communicator, "total_bytes": total_bytes,
         "n_leaves": n_leaves, "device_size": n},
    )
    rec["kernel"] = "overlap_schedule"
    return rec


def tune_decode_attention(
    *,
    n_pages: int,
    page_size: int,
    n_kv: int,
    d_head: int,
    n_heads: Optional[int] = None,
    batch: int = 8,
    dtype="bfloat16",
    cache: Optional[TuneCache] = None,
    n1: int = 3,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the paged decode-attention context-gather chunk for one page
    geometry.  Times :func:`~chainermn_tpu.ops.paged_attention_decode`
    over a full table (the worst-case context) at each candidate
    ``block_ctx`` — including 0, the one-shot gather — and persists the
    argmin under the key the serving engine's trace-time lookup
    (:func:`lookup_decode_block_ctx`) reads back on TPU.  Chunking is
    data movement only, so the tuned pick is bit-identical to the
    default; only the transient-buffer footprint and gather schedule
    move."""
    import numpy as np

    space = decode_search_space(n_pages, page_size, n_kv, d_head, dtype,
                                batch=batch)
    default_cfg = {"block_ctx": 0}
    key = decode_cache_key(
        device_kind(), dtype, n_pages, page_size, n_kv, d_head
    )
    if dry_run:
        return {"kernel": "paged_decode", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("paged decode attention")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and int(cached.get("block_ctx", -1)) >= 0:
        return {"kernel": "paged_decode", "key": key, "cached": True,
                "chosen": {"block_ctx": int(cached["block_ctx"])}}

    from chainermn_tpu.ops.decode_attention import paged_attention_decode
    from chainermn_tpu.utils.profiling import sync

    H = n_heads or n_kv
    W = n_pages // max(1, batch)  # pages per sequence, full occupancy
    rng = np.random.RandomState(0)
    dt = dtype_name(dtype)
    q = jax.numpy.asarray(rng.randn(batch, 1, H, d_head), dt)
    kp = jax.numpy.asarray(
        rng.randn(n_pages, page_size, n_kv, d_head), dt
    )
    vp = jax.numpy.asarray(
        rng.randn(n_pages, page_size, n_kv, d_head), dt
    )
    tables = jax.numpy.asarray(
        rng.permutation(n_pages)[: batch * W].reshape(batch, W), "int32"
    )
    lens = jax.numpy.full((batch,), W * page_size, "int32")
    if log:
        log(f"paged_decode {key}: {len(space)} candidates")

    def build(cfg):
        bc = cfg["block_ctx"] or None
        f = jax.jit(
            lambda q, kp, vp, t, sl: paged_attention_decode(
                q, kp, vp, t, sl, block_ctx=bc
            )
        )

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                o = f(q, kp, vp, tables, lens)
            sync(o)
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "paged_decode", "dtype": dt, "n_pages": n_pages,
         "page_size": page_size, "n_kv": n_kv, "d_head": d_head,
         "batch": batch},
    )
    rec["kernel"] = "paged_decode"
    return rec


def tune_comm_dtype(
    *,
    communicator: str = "xla_ici",
    total_mb: float = 64.0,
    n_leaves: int = 64,
    dtype="float32",
    mesh=None,
    cache: Optional[TuneCache] = None,
    n1: int = 3,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the gradient wire dtype (``comm_dtype``) for one tree family.

    Times ``eager_allreduce_grad`` over the shared synthetic tree at
    full precision and at each narrow wire dtype, persisting the argmin
    under the key ``resolve_comm_dtype`` reads back on TPU.  Every
    candidate's measured max-abs error vs the fp32 path is recorded in
    the result (and the winner's in the cache entry) so an operator can
    audit the accuracy cost of the picked wire — the per-dtype bounds in
    ``communicators.quant`` hold regardless of what is picked."""
    from chainermn_tpu.communicators.packing import synthetic_grad_tree
    from chainermn_tpu.communicators.quant import measure_comm_quant_error

    total_bytes = int(total_mb * 1024 * 1024)
    tree = synthetic_grad_tree(n_leaves, total_bytes, dtypes=(dtype,))
    total_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree)
    )
    space = comm_dtype_search_space()
    default_cfg = {"comm_dtype": "none"}
    key = comm_dtype_cache_key(
        device_kind(), dtype, total_bytes, n_leaves, communicator
    )
    if dry_run:
        return {"kernel": "comm_dtype", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("gradient wire dtype")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and cached.get("comm_dtype"):
        return {"kernel": "comm_dtype", "key": key, "cached": True,
                "chosen": {"comm_dtype": str(cached["comm_dtype"])}}

    from chainermn_tpu.communicators import create_communicator
    from chainermn_tpu.utils.profiling import sync

    n = None
    errs: dict = {}
    if log:
        log(f"comm_dtype {key}: {len(space)} candidates")

    def build(cfg):
        nonlocal n
        comm = create_communicator(
            communicator, mesh=mesh, comm_dtype=cfg["comm_dtype"]
        )
        n = comm.device_size
        if cfg["comm_dtype"] != "none":
            errs[cfg["comm_dtype"]] = measure_comm_quant_error(
                comm, tree, publish=False
            )
        stacked = jax.tree_util.tree_map(
            lambda l: jax.numpy.stack([jax.numpy.asarray(l)] * n), tree
        )

        def run(k):
            t0 = time.perf_counter()
            out = stacked
            for _ in range(k):
                out = comm.eager_allreduce_grad(out)
            sync(jax.tree_util.tree_leaves(out)[0])
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "comm_dtype", "dtype": dtype_name(dtype),
         "communicator": communicator, "total_bytes": total_bytes,
         "n_leaves": n_leaves, "device_size": n,
         "max_abs_err": errs},
    )
    rec["kernel"] = "comm_dtype"
    rec["max_abs_err"] = errs
    return rec


def tune_kv_dtype(
    *,
    n_pages: int,
    page_size: int,
    n_kv: int,
    d_head: int,
    n_heads: Optional[int] = None,
    batch: int = 8,
    dtype="bfloat16",
    cache: Optional[TuneCache] = None,
    n1: int = 3,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the KV page storage dtype for one page geometry.

    Times :func:`~chainermn_tpu.ops.paged_attention_decode` over a full
    table at the model dtype and at each quantized page dtype (int8
    pages + fp32 scale gather + in-kernel dequant), persisting the
    argmin under the key the serving engine's ``kv_dtype`` resolution
    reads back on TPU.  Note the timing captures the dequant overhead
    but not the capacity win — int8 pages halve pool bytes per token
    (docs/serving.md), which is why an operator may pin ``int8`` even
    when the step time ties."""
    import numpy as np

    space = kv_dtype_search_space()
    default_cfg = {"kv_dtype": "none"}
    key = kv_dtype_cache_key(
        device_kind(), dtype, n_pages, page_size, n_kv, d_head
    )
    if dry_run:
        return {"kernel": "kv_dtype", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("KV page dtype")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and cached.get("kv_dtype"):
        return {"kernel": "kv_dtype", "key": key, "cached": True,
                "chosen": {"kv_dtype": str(cached["kv_dtype"])}}

    from chainermn_tpu.communicators.quant import quantize_kv
    from chainermn_tpu.ops.decode_attention import paged_attention_decode
    from chainermn_tpu.utils.profiling import sync

    H = n_heads or n_kv
    W = n_pages // max(1, batch)
    rng = np.random.RandomState(0)
    dt = dtype_name(dtype)
    q = jax.numpy.asarray(rng.randn(batch, 1, H, d_head), dt)
    kv_f = jax.numpy.asarray(rng.randn(n_pages, page_size, n_kv, d_head), dt)
    vv_f = jax.numpy.asarray(rng.randn(n_pages, page_size, n_kv, d_head), dt)
    kv_q, kv_s = quantize_kv(kv_f)
    vv_q, vv_s = quantize_kv(vv_f)
    tables = jax.numpy.asarray(
        rng.permutation(n_pages)[: batch * W].reshape(batch, W), "int32"
    )
    lens = jax.numpy.full((batch,), W * page_size, "int32")
    if log:
        log(f"kv_dtype {key}: {len(space)} candidates")

    def build(cfg):
        quantized = cfg["kv_dtype"] != "none"
        kp, vp = (kv_q, vv_q) if quantized else (kv_f, vv_f)
        ks, vs = (kv_s, vv_s) if quantized else (None, None)
        f = jax.jit(
            lambda q, kp, vp, t, sl: paged_attention_decode(
                q, kp, vp, t, sl, k_scales=ks, v_scales=vs
            )
        )

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                o = f(q, kp, vp, tables, lens)
            sync(o)
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "kv_dtype", "dtype": dt, "n_pages": n_pages,
         "page_size": page_size, "n_kv": n_kv, "d_head": d_head,
         "batch": batch},
    )
    rec["kernel"] = "kv_dtype"
    return rec


def _serve_model_and_engine_factory(vocab, d_model, n_heads, d_ff,
                                    n_layers, max_len, dtype,
                                    block_size, n_blocks, max_batch):
    """One target LM + init params, and a factory building a fresh
    serving engine over them per candidate config — shared scaffolding
    for the serving-loop tuners."""
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving.engine import EngineConfig, InferenceEngine

    dt = getattr(jnp, dtype_name(dtype))
    lm = TransformerLM(vocab=vocab, d_model=d_model, n_heads=n_heads,
                       d_ff=d_ff, n_layers=n_layers, max_len=max_len,
                       dtype=dt)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 8), jnp.int32))

    def make_engine(**cfg_overrides):
        cfg = EngineConfig(block_size=block_size, n_blocks=n_blocks,
                           max_len=max_len, max_batch=max_batch,
                           **cfg_overrides)
        return InferenceEngine(lm, params, cfg)

    return lm, np.random.RandomState(0), make_engine


def tune_draft(
    *,
    vocab: int = 8192,
    d_model: int = 1024,
    n_heads: int = 8,
    d_ff: int = 4096,
    n_layers: int = 8,
    max_len: int = 512,
    block_size: int = 16,
    n_blocks: int = 256,
    batch: int = 4,
    prompt_len: int = 64,
    max_new: int = 32,
    spec_tokens: int = 4,
    dtype="bfloat16",
    cache: Optional[TuneCache] = None,
    n1: int = 1,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the speculative draft source for one target model family.

    Times a fixed continuous-batching workload (``batch`` requests,
    ``spec_tokens``-deep speculation) to completion under each draft
    config — n-gram prompt lookup versus the layer-truncated self-draft
    at each candidate depth — and persists the fastest.  Stream content
    is identical across candidates by the exact-match acceptance
    invariant, so wall time per workload is the whole story: the draft
    choice trades proposal cost against accepted tokens per verify."""
    from chainermn_tpu.serving.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )

    space = draft_search_space(n_layers)
    default_cfg = dict(space[0])
    key = draft_cache_key(
        device_kind(), dtype, vocab, d_model, n_layers, max_len
    )
    if dry_run:
        return {"kernel": "draft", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("speculative draft source")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and cached.get("draft"):
        return {"kernel": "draft", "key": key, "cached": True,
                "chosen": {"draft": str(cached["draft"]),
                           "draft_layers": int(cached.get(
                               "draft_layers", 0))}}

    lm, rng, make_engine = _serve_model_and_engine_factory(
        vocab, d_model, n_heads, d_ff, n_layers, max_len, dtype,
        block_size, n_blocks, batch,
    )
    prompts = [
        list(rng.randint(1, vocab, size=prompt_len).astype(int))
        for _ in range(batch)
    ]
    if log:
        log(f"draft {key}: {len(space)} candidates")

    def build(cfg):
        engine = make_engine(
            draft=cfg["draft"],
            draft_layers=(cfg["draft_layers"]
                          if cfg["draft"] == "model" else None),
        )

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                sched = ContinuousBatchingScheduler(
                    engine, spec_tokens=spec_tokens)
                for i, p in enumerate(prompts):
                    sched.add_request(Request(
                        request_id=i, prompt=list(p),
                        max_new_tokens=max_new))
                sched.run_to_completion()
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "draft", "dtype": dtype_name(dtype), "vocab": vocab,
         "d_model": d_model, "n_layers": n_layers, "max_len": max_len,
         "batch": batch, "prompt_len": prompt_len, "max_new": max_new,
         "spec_tokens": spec_tokens},
    )
    rec["kernel"] = "draft"
    return rec


def tune_prefill_chunk(
    *,
    max_len: int = 512,
    block_size: int = 16,
    vocab: int = 8192,
    d_model: int = 1024,
    n_heads: int = 8,
    d_ff: int = 4096,
    n_layers: int = 8,
    n_blocks: int = 256,
    decode_batch: int = 3,
    max_new: int = 24,
    long_context: bool = False,
    dtype="bfloat16",
    cache: Optional[TuneCache] = None,
    n1: int = 1,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the chunked-prefill slice size for one page geometry.

    Unlike the throughput tuners, the metric here is the workload's
    *worst decode stall*: ``decode_batch`` short requests stream while
    one near-budget prompt arrives mid-flight, and ``run(n)`` returns
    the summed maximum scheduler-step wall time across ``n`` workload
    repetitions.  Monolithic prefill (0) charges the whole long prompt
    to one step — the decode p99 spike chunked prefill exists to bound
    — so the argmin lands on the slice size whose per-step cost hides
    best behind the decode cadence.  Throughput is deliberately NOT the
    objective: chunking always costs a little of it.

    With ``long_context`` the same objective reruns at the long-context
    bucket: the context budget doubles, the engine's seed ladder stops
    at the BASE budget, and the long arrival crosses it via lazy bucket
    growth — so the argmin reflects per-step cost at the GROWN bucket,
    where a slice that hid fine at the base budget can stall decode
    (attention over the longer context makes every slice step dearer).
    The growth recompiles themselves are one-time and warmed out by the
    measurement harness; the leg has its own cache key, so base and
    long-context slice sizes tune independently."""
    from chainermn_tpu.serving.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )

    ctx = int(max_len) * 2 if long_context else int(max_len)
    space = prefill_chunk_search_space(max_len, block_size)
    default_cfg = dict(space[0])
    key = prefill_chunk_cache_key(device_kind(), ctx, block_size)
    if dry_run:
        return {"kernel": "prefill_chunk", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("chunked-prefill slice size")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and cached.get("prefill_chunk") is not None:
        return {"kernel": "prefill_chunk", "key": key, "cached": True,
                "chosen": {"prefill_chunk": int(
                    cached["prefill_chunk"])}}

    lm, rng, make_engine = _serve_model_and_engine_factory(
        vocab, d_model, n_heads, d_ff, n_layers, ctx, dtype,
        block_size, n_blocks, decode_batch + 1,
    )
    short_len = max(block_size, max_len // 16)
    long_len = ctx - max_new - 1
    shorts = [
        list(rng.randint(1, vocab, size=short_len).astype(int))
        for _ in range(decode_batch)
    ]
    long_prompt = list(rng.randint(1, vocab, size=long_len).astype(int))
    if log:
        log(f"prefill_chunk {key}: {len(space)} candidates "
            f"(long prompt {long_len} tok"
            + (", crosses the seed ladder" if long_context else "")
            + ")")

    def build(cfg):
        over = {"prefill_chunk": int(cfg["prefill_chunk"])}
        if long_context:
            # Seed ladder stops at the BASE budget; the long arrival
            # must grow past it, so measured stalls are at the grown
            # bucket (run(1) warms the growth compiles away).
            over["prefill_buckets"] = (int(max_len),)
        engine = make_engine(**over)

        def run(n):
            total = 0.0
            for _ in range(n):
                sched = ContinuousBatchingScheduler(engine)
                for i, p in enumerate(shorts):
                    sched.add_request(Request(
                        request_id=i, prompt=list(p),
                        max_new_tokens=max_new))
                # warm the decode cadence before the long arrival
                for _ in range(2):
                    sched.step()
                sched.add_request(Request(
                    request_id=len(shorts), prompt=list(long_prompt),
                    max_new_tokens=4))
                worst = 0.0
                while sched.has_work:
                    t0 = time.perf_counter()
                    sched.step()
                    worst = max(worst, time.perf_counter() - t0)
                total += worst
            return total

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "prefill_chunk", "dtype": dtype_name(dtype),
         "max_len": ctx, "block_size": block_size,
         "decode_batch": decode_batch, "long_len": long_len,
         "long_context": bool(long_context),
         "metric": "sum of worst per-step wall time per workload"},
    )
    rec["kernel"] = "prefill_chunk"
    return rec


def tune_serve_group(
    *,
    vocab: int = 8192,
    d_model: int = 1024,
    n_heads: int = 8,
    d_ff: int = 4096,
    n_layers: int = 8,
    max_len: int = 512,
    block_size: int = 16,
    n_blocks: int = 256,
    batch: int = 4,
    prompt_len: int = 64,
    max_new: int = 24,
    dtype="bfloat16",
    cache: Optional[TuneCache] = None,
    n1: int = 1,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the serving shard-group SHAPE for one target model family:
    tensor-parallel group size (registry ``tp`` plan over that many
    local devices) crossed with pipeline microbatch depth for the
    decode step.  A fixed continuous-batching workload runs to
    completion under each candidate; streams are bit-identical across
    the whole space (per-sequence attention + counter-based sampling +
    contiguous microbatch splits), so wall time per workload is the
    entire objective — group shape is a pure throughput decision, like
    the draft source.  The persisted argmin is what ``tools.serve`` and
    the router would spend a whole shard group of processes on, priced
    here on one process's local devices before committing the fleet."""
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving.engine import EngineConfig, InferenceEngine
    from chainermn_tpu.serving.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )

    n_devices = len(jax.devices())
    space = serve_group_search_space(n_heads, d_ff, d_model,
                                     n_devices, batch)
    default_cfg = dict(space[0])
    key = serve_group_cache_key(
        device_kind(), dtype, vocab, d_model, n_layers, max_len,
        n_devices, batch,
    )
    if dry_run:
        return {"kernel": "serve_group", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("serving shard-group shape")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and cached.get("group_size"):
        return {"kernel": "serve_group", "key": key, "cached": True,
                "chosen": {"group_size": int(cached["group_size"]),
                           "pp_stages": int(cached.get(
                               "pp_stages", 1))}}

    dt = getattr(jnp, dtype_name(dtype))
    lm = TransformerLM(vocab=vocab, d_model=d_model, n_heads=n_heads,
                       d_ff=d_ff, n_layers=n_layers, max_len=max_len,
                       dtype=dt)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 8), jnp.int32))
    rng = np.random.RandomState(0)
    prompts = [
        list(rng.randint(1, vocab, size=prompt_len).astype(int))
        for _ in range(batch)
    ]
    if log:
        log(f"serve_group {key}: {len(space)} candidates "
            f"({n_devices} local devices)")

    def build(cfg):
        plan = mesh = None
        if cfg["group_size"] > 1:
            from jax.sharding import Mesh

            plan = "tp"
            mesh = Mesh(
                np.asarray(jax.devices()[: cfg["group_size"]]),
                ("model",),
            )
        ecfg = EngineConfig(block_size=block_size, n_blocks=n_blocks,
                            max_len=max_len, max_batch=batch)
        engine = InferenceEngine(lm, params, ecfg, plan=plan, mesh=mesh)
        engine.pp_stages = int(cfg["pp_stages"])

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                sched = ContinuousBatchingScheduler(engine)
                for i, p in enumerate(prompts):
                    sched.add_request(Request(
                        request_id=i, prompt=list(p),
                        max_new_tokens=max_new))
                while sched.has_work:
                    sched.step()
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "serve_group", "dtype": dtype_name(dtype),
         "vocab": vocab, "d_model": d_model, "n_layers": n_layers,
         "max_len": max_len, "batch": batch,
         "n_devices": n_devices},
    )
    rec["kernel"] = "serve_group"
    return rec


def tune_layout(
    *,
    mesh,
    batch: int = 8,
    seq: int = 64,
    vocab: int = 256,
    d_model: int = 64,
    n_heads: int = 4,
    d_ff: int = 256,
    n_layers: int = 2,
    dtype="bfloat16",
    data_axis: str = "data",
    model: str = "transformer_lm",
    cache: Optional[TuneCache] = None,
    n1: int = 2,
    repeats: int = 3,
    force: bool = False,
    dry_run: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune the parameter LAYOUT itself: time one gspmd train step per
    registry sharding plan valid for ``mesh`` (dp replicate vs tp vs
    fsdp vs zero vs dp_tp — whatever validates against the model) and
    persist the argmin plan name.  The search space is the plan
    registry, so a plan added by user code is automatically a candidate
    the next tuning run; ``dp`` (today's hand-picked layout) is the
    default the winner must beat.  ``mesh`` must carry ``data_axis``
    (the batch always shards over it)."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.parallel.sharding import make_gspmd_train_step
    from chainermn_tpu.sharding import get_plan

    if data_axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no {data_axis!r} axis (axes: "
            f"{tuple(mesh.axis_names)}) — the layout tuner's batch "
            "always shards over the data axis"
        )
    dt = jnp.bfloat16 if dtype_name(dtype) == "bfloat16" else jnp.float32
    lm = TransformerLM(
        vocab=vocab, d_model=d_model, n_heads=n_heads, d_ff=d_ff,
        n_layers=n_layers, max_len=seq, dtype=dt,
    )
    tokens = jax.numpy.asarray(
        np.random.RandomState(0).randint(0, vocab, (batch, seq)), "int32"
    )
    params = lm.init(jax.random.PRNGKey(0), tokens)["params"]
    # Host copies: the plan-driven step donates its param/moment buffers,
    # and device_put may alias an on-device input's buffer into the
    # placed tree — numpy leaves guarantee every candidate starts from
    # fresh device arrays no earlier candidate could have donated away.
    params = jax.tree.map(np.asarray, params)
    leaves = jax.tree_util.tree_leaves(params)
    n_params = int(sum(leaf.size for leaf in leaves))

    space = layout_search_space(mesh.axis_names, params, mesh)
    default_cfg = {"plan": "dp"}
    key = layout_cache_key(
        device_kind(), dtype, n_params, len(leaves),
        tuple(mesh.devices.shape), model,
    )
    if dry_run:
        return {"kernel": "layout", "dry_run": True, "key": key,
                "candidates": space, "default": default_cfg}
    _require_tuning_allowed("sharding-plan layout")
    cache = cache or shared_cache()
    cached = cache.get(key) if not force else None
    if cached and cached.get("plan"):
        return {"kernel": "layout", "key": key, "cached": True,
                "chosen": {"plan": str(cached["plan"])}}

    from chainermn_tpu.utils.profiling import sync

    opt = optax.adam(1e-3)

    def loss_fn(p, batch_tokens):
        logits = lm.apply({"params": p}, batch_tokens)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        tgt = jnp.roll(batch_tokens, -1, axis=1)
        return -jnp.mean(
            jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        )

    if log:
        log(f"layout {key}: {len(space)} candidate plan(s): "
            f"{[c['plan'] for c in space]}")

    def build(cfg):
        plan = get_plan(cfg["plan"])
        step, shard_fn = make_gspmd_train_step(
            loss_fn, opt, mesh, plan, data_axis=data_axis
        )
        p, s = shard_fn(params, opt.init(params))
        holder = {"p": p, "s": s}

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                holder["p"], holder["s"], loss = step(
                    holder["p"], holder["s"], tokens
                )
            sync(loss)
            return time.perf_counter() - t0

        return run

    results = measure_candidates(build, space, n1=n1, repeats=repeats,
                                 log=log)
    rec = _finish(
        key, results, default_cfg, cache,
        {"kernel": "layout", "dtype": dtype_name(dtype), "model": model,
         "mesh_shape": list(int(s) for s in mesh.devices.shape),
         "mesh_axes": list(mesh.axis_names), "n_params": n_params,
         "n_leaves": len(leaves), "batch": batch, "seq": seq},
    )
    rec["kernel"] = "layout"
    return rec


def tune_lm_shapes(
    *,
    batch: int,
    seq: int,
    n_heads: int,
    d_model: int,
    vocab: int,
    window: Optional[int] = None,
    dtype="bfloat16",
    cache: Optional[TuneCache] = None,
    force: bool = False,
    dry_run: bool = False,
    n1: int = 3,
    repeats: int = 3,
    log: Optional[Callable[[str], None]] = None,
) -> dict:
    """Tune every searched kernel the LM bench step hits — the flash
    fwd/bwd geometry at the step's (batch*heads, S, head_dim) and the CE
    chunk at its (batch*S, vocab, d_model).  This is what
    ``bench.py --autotune`` and the CLI's default mode call."""
    if d_model % n_heads:
        raise ValueError(f"d_model {d_model} not divisible by heads {n_heads}")
    flash = tune_flash(
        Sq=seq, Sk=seq, D=d_model // n_heads, dtype=dtype, causal=True,
        window=window, batch_heads=batch * n_heads, cache=cache,
        force=force, dry_run=dry_run, n1=n1, repeats=repeats, log=log,
    )
    ce = tune_fused_ce(
        N=batch * seq, V=vocab, D=d_model, dtype=dtype, cache=cache,
        force=force, dry_run=dry_run, n1=n1, repeats=repeats, log=log,
    )
    return {"flash": flash, "fused_ce": ce}
