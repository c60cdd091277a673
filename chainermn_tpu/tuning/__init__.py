"""Kernel autotuning — searched block configs for the Pallas hot paths.

The hot kernels (``ops.flash_attention``, ``ops.fused_ce``) ship with
one static geometry each: ``block_q``/``block_k`` the largest tile that
fits Mosaic's default scoped VMEM (``ops.flash_attention
.auto_block_size``; PERF.md §6, PR 25) and ``chunk = 512``.  Blockwise
TPU kernels are highly sensitive to tile shape, and the best choice
shifts with sequence length, head dim, dtype and the causal/window band
— so, following the reference framework's own design principle (expose
the knob, but pick a fast default FOR the user:
``allreduce_grad_dtype``, ``double_buffering``), this package measures
the best config per shape
once and remembers it:

* :mod:`~chainermn_tpu.tuning.search_space` — per-kernel candidate
  declarations (flash fwd/bwd ``block_q``×``block_k`` within VMEM
  limits, fused-CE ``chunk``), each with the static default included so
  a tuned pick can never lose to it;
* :mod:`~chainermn_tpu.tuning.measure` — compile-and-time harness
  (median-of-k slope timing via ``utils.profiling``; candidates that
  fail to compile or OOM are skipped, not fatal);
* :mod:`~chainermn_tpu.tuning.cache` — persistent JSON cache keyed by
  ``(kernel, device_kind, dtype, shape bucket, causal/window flags)``,
  path overridable via ``CHAINERMN_TPU_TUNE_CACHE`` (default under
  ``/tmp``, never inside the repo);
* :mod:`~chainermn_tpu.tuning.autotune` — the tuners and the runtime
  lookups the ops consult when the caller does not pin blocks.

Determinism guard: lookups and tuning are inert under pytest and on
non-TPU backends — there the ops use their static defaults, bit-identical
to the pre-tuning behavior.  Tuning itself only ever runs explicitly:
``python -m chainermn_tpu.tools.autotune`` or ``bench.py --autotune``.
"""

from chainermn_tpu.tuning.cache import (  # noqa: F401
    DEFAULT_CACHE_PATH,
    ENV_AUTOTUNE,
    ENV_CACHE_PATH,
    TuneCache,
    autotune_enabled,
    bucket_pow2,
    cache_path,
    device_kind,
    runtime_lookup_enabled,
    shared_cache,
)
from chainermn_tpu.tuning.search_space import (  # noqa: F401
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
from chainermn_tpu.tuning.autotune import (  # noqa: F401
    lookup_bucket_bytes,
    lookup_ce_chunk,
    lookup_comm_dtype,
    lookup_decode_block_ctx,
    lookup_draft,
    lookup_draft_layers,
    lookup_flash_blocks,
    lookup_kv_dtype,
    lookup_layout,
    lookup_overlap_schedule,
    lookup_prefill_chunk,
    tune_allreduce_bucket,
    tune_comm_dtype,
    tune_decode_attention,
    tune_draft,
    tune_flash,
    tune_fused_ce,
    tune_kv_dtype,
    tune_layout,
    tune_lm_shapes,
    tune_overlap_schedule,
    tune_prefill_chunk,
    tune_serve_group,
)
