"""Serving subsystem: paged KV cache, continuous batching, jitted decode.

Training repos usually bolt inference on as an afterthought; this
package is the deliberate version — the smallest serving stack that
exercises the repo's own model (:class:`~chainermn_tpu.models.transformer
.TransformerLM`) with production-shaped mechanics:

* :mod:`~chainermn_tpu.serving.kv_cache` — paged KV accounting:
  fixed-size pages, per-sequence block tables, alloc/free/defragment,
  conservation invariants, occupancy stats (vLLM's PagedAttention
  memory model, host side), plus copy-on-write prefix sharing: a
  token-run-keyed prefix index, per-page refcounts, and an LRU cached
  pool that lets prompt pages outlive their sequences;
* :mod:`~chainermn_tpu.serving.engine` — the execution engine: jitted
  prefill, single-token decode, and multi-token chunk steps with static
  padding buckets (bounded recompiles), the paged-attention data plane
  from :mod:`~chainermn_tpu.ops.decode_attention`, host-side
  deterministic sampling;
* :mod:`~chainermn_tpu.serving.spec` — draft proposal sources for
  speculative decoding: n-gram prompt lookup (model-free) and the
  layer-truncated self-draft model (both deterministic per request);
* :mod:`~chainermn_tpu.serving.scheduler` — Orca-style iteration-level
  continuous batching: FCFS admission with a free-page watermark
  (prefix hits discounted), one batched decode/verify per step,
  preemption by eviction with recompute;
* :mod:`~chainermn_tpu.serving.frontend` — bounded-queue submission
  with backpressure, per-request deadlines, streaming token callbacks;
* :mod:`~chainermn_tpu.serving.cluster` — the multi-replica tier:
  load-aware routing, prefill/decode disaggregation, KV-page migration
  over the host plane, heartbeat failover (see ``docs/serving.md``,
  "Multi-replica tier").

The load-bearing property, pinned by ``tests/test_serving.py``: a token
stream is bit-identical whether a request runs alone through
:meth:`engine.InferenceEngine.generate` or shares continuous-batched
iterations — including across preemption, prefix-cache hits, and
speculative accept/reject — batching, sharing, and speculation are pure
throughput decisions, never quality ones.
"""

from chainermn_tpu.serving.engine import (  # noqa: F401
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from chainermn_tpu.serving.frontend import (  # noqa: F401
    QueueFull,
    RequestHandle,
    ServeFrontend,
)
from chainermn_tpu.serving.kv_cache import (  # noqa: F401
    CacheStats,
    OutOfBlocks,
    PagedKVCache,
    prefix_digest,
    prompt_digests,
)
from chainermn_tpu.serving.scheduler import (  # noqa: F401
    ContinuousBatchingScheduler,
    Request,
    RequestState,
)
from chainermn_tpu.serving.spec import (  # noqa: F401
    DraftModel,
)
from chainermn_tpu.serving.workload import (  # noqa: F401
    Arrival,
    TrafficSpec,
)
