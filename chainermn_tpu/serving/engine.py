"""Jitted inference engine: bucketed prefill + single-token paged decode.

The engine is the *execution* half of serving (the scheduler is the
*policy* half): it owns the device-side KV pages, the two jitted step
programs, and sampling.  Design constraints, in order:

1. **Bit-stable batching.**  A token stream must not depend on which
   other requests happened to share its decode batch — that is what lets
   the scheduler batch aggressively while `tests/test_serving.py` pins
   batched == sequential.  Everything per-sequence: the paged attention
   reduces only within one sequence's gathered context, padding rows
   write to the dropped invalid page, and sampling is host-side per
   request (greedy argmax on fp32 logits; temperature/top-k from a
   per-request counter-based RNG independent of batch composition).
2. **Bounded recompiles.**  jit re-traces per shape, so every host-side
   shape is padded to a static bucket: prompt length (pow2 ladder),
   decode batch (pow2 up to ``max_batch``), and block-table width (pow2
   pages).  The compile count is the number of *buckets touched*, not
   the number of requests — pinned by the recompile-count test.
3. **CPU-safe.**  The data plane is pure jnp (gather/scatter + einsum
   softmax, :mod:`chainermn_tpu.ops.decode_attention`), so the tier-1
   suite runs the whole engine under ``JAX_PLATFORMS=cpu``.

The decode data plane is collective-free by construction — no psum ever
belongs in a per-sequence cache read — and stays that way via the
``serving_decode`` lint fixture and the
``tests/golden/serving_decode_census.json`` golden.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.communicators import quant
from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.observability.spans import annotate
from chainermn_tpu.serving.kv_cache import PagedKVCache
from chainermn_tpu.serving.spec import DraftModel, propose_draft as _ngram_draft

#: draft proposal sources the engine can dispatch to.
DRAFT_SOURCES = ("ngram", "model")
ENV_DRAFT = "CHAINERMN_TPU_DRAFT"
ENV_PREFILL_CHUNK = "CHAINERMN_TPU_PREFILL_CHUNK"
#: largest default chunk bucket (tokens) — the T ladder for slices and
#: verify windows is capped here and grows lazily beyond (see
#: ``EngineConfig.max_len_growth``), so a 128k ``max_len`` does not
#: pre-declare a 128k-token chunk program.
DEFAULT_CHUNK_CAP = 4096


def _resolve_draft(cfg: "EngineConfig") -> str:
    """``draft`` source resolution, same order as ``kv_dtype``: explicit
    config -> ``CHAINERMN_TPU_DRAFT`` env -> ``"ngram"``."""
    if cfg.draft is not None:
        if cfg.draft not in DRAFT_SOURCES:
            raise ValueError(
                f"draft must be one of {DRAFT_SOURCES}, got {cfg.draft!r}")
        return cfg.draft
    env = os.environ.get(ENV_DRAFT)
    return env if env in DRAFT_SOURCES else "ngram"


def _resolve_prefill_chunk(cfg: "EngineConfig") -> int:
    """``prefill_chunk`` resolution (0 = off): explicit config ->
    ``CHAINERMN_TPU_PREFILL_CHUNK`` env -> off."""
    if cfg.prefill_chunk is not None:
        return max(0, int(cfg.prefill_chunk))
    try:
        return max(0, int(os.environ.get(ENV_PREFILL_CHUNK, 0)))
    except ValueError:
        return 0


def _resolve_kv_dtype(cfg: "EngineConfig"):
    """``kv_dtype`` resolution, mirroring the comm side's ctor -> env ->
    off order: an explicit config value (any spelling, including
    ``"none"``) wins outright; an unset one consults the
    ``CHAINERMN_TPU_KV_DTYPE`` env."""
    if cfg.kv_dtype is not None:
        return quant.canonical_kv_dtype(cfg.kv_dtype)
    try:
        return quant.canonical_kv_dtype(os.environ.get(quant.ENV_KV_DTYPE))
    except ValueError:
        return None


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling policy.  ``temperature == 0`` is greedy
    (argmax, RNG never consulted); otherwise softmax sampling at the
    given temperature, optionally truncated to the ``top_k`` most likely
    tokens.  ``seed`` plus the token position form a counter-based RNG,
    so a request's stream is reproducible and independent of batching."""

    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static geometry of the serving engine.

    ``n_blocks * block_size`` is the total KV pool in tokens;
    ``max_len`` bounds any single sequence (prompt + generated);
    ``max_batch`` is the widest decode iteration.  Buckets are pow2
    ladders derived from these unless given explicitly."""

    block_size: int = 16
    n_blocks: int = 256
    max_len: int = 2048
    max_batch: int = 8
    #: enable the prefix index / CoW sharing in the page accounting.
    prefix_cache: bool = True
    #: KV page storage dtype: ``"int8"`` stores pages quantized with
    #: per-token-per-head scales (docs/serving.md — ~half the pool bytes
    #: per token, bounded decode error); ``None`` resolves
    #: ``CHAINERMN_TPU_KV_DTYPE`` -> model dtype;
    #: ``"none"`` pins full precision.
    kv_dtype: Optional[str] = None
    prefill_buckets: Optional[Tuple[int, ...]] = None
    batch_buckets: Optional[Tuple[int, ...]] = None
    table_width_buckets: Optional[Tuple[int, ...]] = None
    #: T ladder for the multi-token chunk step (speculative verify and
    #: prefix-hit suffix prefill share one jitted program).
    chunk_buckets: Optional[Tuple[int, ...]] = None
    #: speculative draft source: ``"ngram"`` (prompt lookup, free) or
    #: ``"model"`` (layer-truncated self-draft under its own jit);
    #: ``None`` resolves ``CHAINERMN_TPU_DRAFT`` -> ``"ngram"``.  Either
    #: source is verified by the same chunk step, so streams stay
    #: bit-exact regardless.
    draft: Optional[str] = None
    #: layers in the truncated draft (``draft="model"`` only); ``None``
    #: = ``max(1, n_layers // 2)``.  ``n_layers`` gives an exact (but
    #: pointless in production) draft — useful for acceptance tests.
    draft_layers: Optional[int] = None
    #: chunked prefill: prompts whose un-cached suffix exceeds this many
    #: tokens prefill in slices of this size, interleaved with decode
    #: iterations (bounds decode p99 under long-prompt arrival).
    #: ``None`` resolves ``CHAINERMN_TPU_PREFILL_CHUNK`` -> 0 (off);
    #: 0 pins off.
    prefill_chunk: Optional[int] = None
    #: sequence-parallel prefill: shard the chunk program's token axis
    #: over this many devices (pow2; the ``sp`` registry plan supplies
    #: the replicated placement), so one slice's activations and K/V
    #: transients split across chips.  Decode is untouched — it stays
    #: single-program and collective-free.  0/1 = off.
    sp: int = 0
    #: lazily extend the prompt/chunk/table-width bucket ladders (next
    #: pow2, capped at ``max_len`` worth of tokens/pages) instead of
    #: raising when a value overflows the ladder — each extension costs
    #: exactly one traced recompile on THIS replica only (the fleet
    #: routes long prompts to replicas whose ladders are already warm
    #: via the gossiped ``max_bucket``).  False pins the pre-growth
    #: hard-error behavior.
    max_len_growth: bool = True

    def resolved(self) -> "EngineConfig":
        def pow2_ladder(lo, hi):
            out, v = [], lo
            while v < hi:
                out.append(v)
                v *= 2
            out.append(hi)
            return tuple(sorted(set(out)))

        max_pages = -(-self.max_len // self.block_size)
        return dataclasses.replace(
            self,
            prefill_buckets=self.prefill_buckets
            or pow2_ladder(min(16, self.max_len), self.max_len),
            batch_buckets=self.batch_buckets
            or pow2_ladder(1, self.max_batch),
            table_width_buckets=self.table_width_buckets
            or pow2_ladder(1, max_pages),
            # The default chunk ladder stops at DEFAULT_CHUNK_CAP:
            # chunk rows are prefill slices and verify windows, both
            # small by design, so max_len=131072 must not imply 17
            # compiled chunk programs.  Longer rows (a prefix-cached
            # suffix without chunked prefill) grow the ladder lazily.
            chunk_buckets=self.chunk_buckets
            or pow2_ladder(1, min(self.max_len, DEFAULT_CHUNK_CAP)),
        )


def _bucket(value: int, buckets: Tuple[int, ...], what: str) -> int:
    for b in buckets:
        if value <= b:
            return b
    raise ValueError(f"{what} {value} exceeds the largest bucket "
                     f"{buckets[-1]}")


class InferenceEngine:
    """Cached-KV inference over a trained :class:`TransformerLM`.

    ``lm`` is the model the ``params`` were trained with (any ``decode``
    / ``paged`` setting — prefill and decode twins are constructed here,
    sharing the trained parameter structure).  The engine owns:

    * ``kv`` — the :class:`PagedKVCache` page accounting;
    * the device pages (flax ``cache`` collection of both twins);
    * the two jitted steps and their bucket bookkeeping.
    """

    def __init__(self, lm: TransformerLM, params,
                 config: Optional[EngineConfig] = None, *,
                 plan=None, mesh=None):
        if lm.table is not None:
            kinds = sorted({row.mixer for row in lm.table.layers})
            raise ValueError(
                f"InferenceEngine does not serve a model built from a "
                f"block table (mixers: {kinds}): the paged cache and the "
                f"scheduler keep keys and values only, no recurrent "
                f"state (a mamba2 mixer's vector state, a gdn mixer's "
                f"matrix state a head and its convolution's window), no "
                f"token before the first (a cca mixer's convolutions and "
                f"value shift), no positions for an attention row's rotary "
                f"embedding and no router state handed from layer to "
                f"layer, and the page geometry "
                f"is read from n_heads / n_layers (ROADMAP.md R3)"
            )
        cfg = (config or EngineConfig(max_len=lm.max_len)).resolved()
        if cfg.max_len > lm.max_len:
            raise ValueError(
                f"config.max_len {cfg.max_len} exceeds the model's "
                f"max_len {lm.max_len}"
            )
        self.config = cfg
        self.params = params["params"] if "params" in params else params
        self.lm = lm
        self.kv = PagedKVCache(cfg.n_blocks, cfg.block_size,
                               prefix_cache=cfg.prefix_cache)

        self.kv_dtype = _resolve_kv_dtype(cfg)
        twin = dict(
            vocab=lm.vocab, d_model=lm.d_model, n_heads=lm.n_heads,
            d_ff=lm.d_ff, n_layers=lm.n_layers, max_len=lm.max_len,
            dtype=lm.dtype, n_kv_heads=lm.n_kv_heads,
            page_count=cfg.n_blocks, page_size=cfg.block_size,
            kv_dtype=self.kv_dtype,
        )
        self._prefill_model = TransformerLM(**twin, paged="prefill")
        self._decode_model = TransformerLM(**twin, paged="decode")
        self._chunk_model = TransformerLM(**twin, paged="chunk")

        # Mutable bucket ladders: start from the resolved config and
        # extend lazily (next pow2, capped) when max_len_growth is on —
        # a long prompt costs one extra trace on this replica instead
        # of a hard error, and the growth count is pinned in stats().
        self._prefill_buckets = list(cfg.prefill_buckets)
        self._table_buckets = list(cfg.table_width_buckets)
        self._chunk_buckets = list(cfg.chunk_buckets)
        self._table_cap = max(1, -(-cfg.max_len // cfg.block_size))
        self._bucket_growths = 0
        self._max_prefilled = 0

        # Sequence-parallel prefill (docs/serving.md): a fourth jitted
        # program — the chunk step under shard_map over the 'sp' mesh
        # axis — used for single-row slices whose T bucket the axis
        # divides.  Placement (params/cache replicated) comes from the
        # 'sp' registry plan.
        self.sp = int(cfg.sp) if cfg.sp and int(cfg.sp) > 1 else 0
        self._sp_mesh = None
        self._sp_chunk_model = None
        if self.sp:
            if self.sp & (self.sp - 1):
                raise ValueError(
                    f"sp must be a power of two (it has to divide the "
                    f"pow2 chunk buckets), got {self.sp}"
                )
            if plan is not None:
                raise ValueError(
                    "sp prefill and an explicit tensor-parallel plan "
                    "are mutually exclusive: sp brings its own mesh "
                    "and the 'sp' registry plan"
                )
            devs = jax.devices() if mesh is None else list(
                np.asarray(mesh.devices).reshape(-1)
            )
            if len(devs) < self.sp:
                raise ValueError(
                    f"sp={self.sp} needs {self.sp} devices, have "
                    f"{len(devs)}"
                )
            from jax.sharding import Mesh

            self._sp_mesh = Mesh(np.asarray(devs[: self.sp]), ("sp",))
            self._sp_chunk_model = TransformerLM(
                **twin, paged="chunk", sp_axis="sp"
            )
            plan, mesh = "sp", self._sp_mesh

        # Cache geometry without allocating a throwaway param set; zeros
        # ARE the empty pages (every table slot starts invalid, so stale
        # page contents are unreachable anyway).
        W0 = cfg.table_width_buckets[0]
        cache_shapes = jax.eval_shape(
            lambda: self._prefill_model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                block_tables=jnp.zeros((1, W0), jnp.int32),
                seq_lens=jnp.zeros((1,), jnp.int32),
            )["cache"]
        )
        self._cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes
        )

        # Quantized engines also pull the "intermediates" collection (the
        # per-layer kv round-trip errors sown by MultiHeadAttention) and
        # return their max, the serve/kv_quant_err gauge's source.  The
        # default path keeps the exact two-output signature it always had.
        kv_q = self.kv_dtype is not None
        muts = ["cache", "intermediates"] if kv_q else ["cache"]

        def _kv_err(upd):
            leaves = jax.tree.leaves(upd.get("intermediates", {}))
            if not leaves:
                return jnp.zeros((), jnp.float32)
            return jnp.max(jnp.stack([l.astype(jnp.float32) for l in leaves]))

        def prefill_step(params, cache, tokens, block_tables, seq_lens):
            logits, upd = self._prefill_model.apply(
                {"params": params, "cache": cache}, tokens,
                block_tables=block_tables, seq_lens=seq_lens,
                mutable=muts,
            )
            # Logits of the LAST PROMPT TOKEN per row — what samples the
            # first generated token.  (Padding rows index position 0 of
            # garbage; callers never read them.)
            idx = jnp.maximum(seq_lens - 1, 0)[:, None, None]
            last = jnp.take_along_axis(
                logits, jnp.broadcast_to(
                    idx, (logits.shape[0], 1, logits.shape[2])
                ), axis=1,
            )[:, 0]
            if kv_q:
                return last.astype(jnp.float32), upd["cache"], _kv_err(upd)
            return last.astype(jnp.float32), upd["cache"]

        def decode_step(params, cache, tokens, block_tables, seq_lens):
            logits, upd = self._decode_model.apply(
                {"params": params, "cache": cache}, tokens[:, None],
                position_offset=jnp.maximum(seq_lens, 0)[:, None],
                block_tables=block_tables, seq_lens=seq_lens,
                mutable=muts,
            )
            if kv_q:
                return (logits[:, 0].astype(jnp.float32), upd["cache"],
                        _kv_err(upd))
            return logits[:, 0].astype(jnp.float32), upd["cache"]

        def chunk_step(params, cache, tokens, block_tables, start_lens):
            # T tokens per row starting at context position start_lens[b]
            # (< 0 = padding row, writes drop, mask hides everything).
            T = tokens.shape[1]
            offs = (jnp.maximum(start_lens, 0)[:, None]
                    + jnp.arange(T, dtype=jnp.int32)[None])
            logits, upd = self._chunk_model.apply(
                {"params": params, "cache": cache}, tokens,
                position_offset=offs,
                block_tables=block_tables, seq_lens=start_lens,
                mutable=muts,
            )
            if kv_q:
                return logits.astype(jnp.float32), upd["cache"], _kv_err(upd)
            return logits.astype(jnp.float32), upd["cache"]

        def cow_step(cache, old, new):
            # Device half of a copy-on-write split: duplicate page `old`
            # into the freshly-allocated page `new` on every cache leaf.
            # old/new are traced scalars, so every split shares ONE
            # compiled program.
            return jax.tree.map(lambda l: l.at[new].set(l[old]), cache)

        # donate the pages: each step consumes the previous step's cache,
        # so the (large) page buffers update in place where the backend
        # supports aliasing.
        self._prefill_jit = jax.jit(prefill_step, donate_argnums=(1,))
        self._decode_jit = jax.jit(decode_step, donate_argnums=(1,))
        self._chunk_jit = jax.jit(chunk_step, donate_argnums=(1,))
        self._cow_jit = jax.jit(cow_step, donate_argnums=(0,))

        self._sp_chunk_jit = None
        if self.sp:
            from jax.sharding import PartitionSpec as P

            from chainermn_tpu.communicators.base import shard_map_compat

            def sp_chunk_step(params, cache, tokens, block_tables,
                              start_lens):
                # Shard body: tokens is this shard's C = T/sp
                # consecutive slice tokens; start_lens carries the
                # GLOBAL slice start (replicated).  The model gathers
                # the full slice's K/V, writes it whole (identical on
                # every shard, so the cache output is validly declared
                # replicated), and attends the local queries — the
                # per-shard attention start offset (r*C) is added
                # inside the layer; positions here are global.
                import jax.lax as _lax

                C = tokens.shape[1]
                r = _lax.axis_index("sp")
                offs = (jnp.maximum(start_lens, 0)[:, None] + r * C
                        + jnp.arange(C, dtype=jnp.int32)[None])
                logits, upd = self._sp_chunk_model.apply(
                    {"params": params, "cache": cache}, tokens,
                    position_offset=offs,
                    block_tables=block_tables, seq_lens=start_lens,
                    mutable=muts,
                )
                if kv_q:
                    return (logits.astype(jnp.float32), upd["cache"],
                            _kv_err(upd))
                return logits.astype(jnp.float32), upd["cache"]

            out_specs = (P(None, "sp"), P()) + ((P(),) if kv_q else ())
            self._sp_chunk_jit = jax.jit(
                shard_map_compat(
                    sp_chunk_step, self._sp_mesh,
                    in_specs=(P(), P(), P(None, "sp"), P(), P()),
                    out_specs=out_specs,
                ),
                donate_argnums=(1,),
            )

        #: shard-group mirror hook: when set (the leader of a TP shard
        #: group), every device-mutating step — prefill/decode/chunk/
        #: CoW/defrag — first emits ``(op, host payload)`` here, and a
        #: follower replays it with :meth:`apply_step`.  The payload is
        #: exactly the host-side arrays the jit call consumes, so the
        #: replayed program is the SAME compiled program: on CPU the
        #: mirrored caches stay bit-identical, on a real TP mesh each
        #: process runs its shard of the one GSPMD program in lockstep.
        self.mirror_sink = None
        #: decode microbatching for the tp×pp serving mode: when > 1,
        #: each decode iteration splits its rows into this many
        #: contiguous microbatches (``parallel/pipeline.py`` supplies
        #: the fill order) and runs one step per microbatch — on a
        #: shard group the stage subgroups overlap those steps.
        #: Bit-exact by construction: attention is per-sequence and
        #: sampling counter-based, so no stream's tokens depend on
        #: batch composition.
        self.pp_stages = 1
        self._prefill_shapes: set = set()
        self._decode_shapes: set = set()
        self._chunk_shapes: set = set()
        self._sp_shapes: set = set()
        self._tokens_decoded = 0
        self._tokens_prefilled = 0
        self._tokens_chunked = 0
        self._tokens_prefix_cached = 0
        self._cow_splits = 0
        self._kv_quant_err = 0.0

        self.plan = None
        self.mesh = None
        if plan is not None:
            self._apply_plan(plan, mesh)

        # Draft source + chunked prefill (resolution: config -> env ->
        # default, like kv_dtype above).  The draft model is
        # built AFTER plan placement so its param subset references the
        # placed arrays, not stale host copies.
        self.draft_source = _resolve_draft(cfg)
        self.prefill_chunk = _resolve_prefill_chunk(cfg)
        self.draft_model: Optional[DraftModel] = None
        if self.draft_source == "model":
            k = cfg.draft_layers or max(1, lm.n_layers // 2)
            self.draft_model = DraftModel(
                lm, self.params, k, cfg.prefill_buckets
            )

    def _apply_plan(self, plan, mesh) -> None:
        """Tensor-parallel placement from a sharding plan: device_put
        the params and the KV pages with the plan's resolved
        NamedShardings (the ``tp`` table shards attention heads / FFN
        hidden on the params and the KV-head axis of ``k_pages`` /
        ``v_pages``).  The jitted step programs are untouched — GSPMD
        propagates the input shardings through the same prefill /
        decode / chunk programs, so the single-device path stays
        byte-identical and the TP token stream is pinned bit-exact
        against it by ``tests/test_shardplan.py``."""
        from chainermn_tpu.sharding import ShardingPlan, get_plan

        if isinstance(plan, str):
            plan = get_plan(plan)
        if not isinstance(plan, ShardingPlan):
            raise TypeError(
                f"plan must be a ShardingPlan or registry name, got "
                f"{type(plan).__name__}"
            )
        if mesh is None:
            raise ValueError(
                f"plan {plan.name!r} needs mesh=: the plan only names "
                "axes; the mesh supplies the devices behind them"
            )
        missing = set(plan.axes) - set(mesh.axis_names)
        if missing:
            raise ValueError(
                f"plan {plan.name!r} shards over axes {sorted(missing)} "
                f"the mesh lacks (mesh axes: {tuple(mesh.axis_names)})"
            )
        self.plan = plan
        self.mesh = mesh
        self.params = jax.device_put(
            self.params, plan.shardings(mesh, self.params)
        )
        # Placement, not a replayed step: followers run _apply_plan
        # themselves at attach (the plan is part of engine construction,
        # not the mirrored op stream), so no mirror emit here.
        self._cache = jax.device_put(  # hostlint: disable=H003
            self._cache, plan.shardings(mesh, self._cache)
        )
        if getattr(self, "draft_model", None) is not None:
            self.draft_model.rebind(self.params)

    # -- shard-group mirroring -----------------------------------------
    def _mirror(self, op: str, *payload) -> None:
        if self.mirror_sink is not None:
            self.mirror_sink(op, payload)

    def apply_step(self, op: str, payload) -> None:
        """Replay one mirrored device step — the follower half of a TP
        shard group.  ``(op, payload)`` is what the leader's
        ``mirror_sink`` emitted; the follower drives the same jitted
        program over its own params/cache (same seed-derived values,
        same plan placement) and keeps only the cache update — logits
        are discarded, sampling and all host accounting are
        leader-only."""
        if op == "prefill":
            padded, table, lens = payload
            out = self._prefill_jit(
                self.params, self._cache, jnp.asarray(padded),
                jnp.asarray(table), jnp.asarray(lens),
            )
            self._cache = out[1]
        elif op == "decode":
            tok, tables, lens = payload
            out = self._decode_jit(
                self.params, self._cache, jnp.asarray(tok),
                jnp.asarray(tables), jnp.asarray(lens),
            )
            self._cache = out[1]
        elif op == "chunk":
            tok, tables, start, use_sp = payload
            step = self._sp_chunk_jit if use_sp else self._chunk_jit
            out = step(
                self.params, self._cache, jnp.asarray(tok),
                jnp.asarray(tables), jnp.asarray(start),
            )
            self._cache = out[1]
        elif op == "cow":
            old, new = payload
            self._cache = self._cow_jit(
                self._cache, jnp.asarray(old, jnp.int32),
                jnp.asarray(new, jnp.int32),
            )
        elif op == "defrag":
            (perm,) = payload
            iperm = jnp.asarray(perm)
            self._cache = jax.tree.map(
                lambda leaf: jnp.take(leaf, iperm, axis=0), self._cache
            )
        else:
            raise ValueError(f"unknown mirrored op {op!r}")

    # -- geometry ------------------------------------------------------
    @property
    def max_batch(self) -> int:
        return self.config.max_batch

    @property
    def max_bucket(self) -> int:
        """Longest context (tokens) this replica has actually run a
        prefill or chunk program over — "my ladders, jit caches and
        pages are warm up to here".  Gossiped in ``ReplicaLoad`` so the
        router can steer a long prompt to a replica that will serve it
        without a cold trace (and, mid-prefill, to the replica already
        streaming that document's pages)."""
        return self._max_prefilled

    def _bucket_grow(self, value: int, ladder: List[int], cap: int,
                     what: str) -> int:
        """Bucket ``value`` on a mutable ladder, extending it (next
        pow2, capped at ``cap``) instead of raising when
        ``max_len_growth`` is on.  Every appended bucket is about to be
        traced by the caller, so the growth count IS the extra-compile
        count — pinned via ``stats()['bucket_growths']``."""
        for b in ladder:
            if value <= b:
                return b
        if not self.config.max_len_growth or value > cap:
            raise ValueError(f"{what} {value} exceeds the largest bucket "
                             f"{ladder[-1]}")
        while ladder[-1] < value:
            ladder.append(min(ladder[-1] * 2, cap))
            self._bucket_growths += 1
        return ladder[-1]

    def table_width(self, n_tokens: int) -> int:
        """Bucketed block-table width for a context of ``n_tokens``."""
        return self._bucket_grow(
            max(1, self.kv.blocks_for(n_tokens)),
            self._table_buckets, self._table_cap, "table width",
        )

    # -- steps ---------------------------------------------------------
    def prefill(self, token_ids, seq_id) -> np.ndarray:
        """Run one prompt (host int sequence) through the prefill step,
        writing its K/V into the pages of the already-allocated
        ``seq_id``.  Returns the fp32 (vocab,) logits of the last prompt
        token.  One sequence per call: per-request prefill keeps the
        compiled shapes to one ladder and the token stream independent
        of co-arrivals."""
        toks = np.asarray(token_ids, np.int32).reshape(-1)
        L = len(toks)
        if L == 0:
            raise ValueError("empty prompt")
        if L >= self.config.max_len:
            raise ValueError(
                f"prompt of {L} tokens leaves no room to generate within "
                f"max_len {self.config.max_len}"
            )
        with annotate("table-build"):
            S = self._bucket_grow(L, self._prefill_buckets,
                                  self.config.max_len, "prompt length")
            W = self.table_width(L)
            padded = np.zeros((1, S), np.int32)
            padded[0, :L] = toks
            table = self.kv.padded_table(seq_id, W)[None]
            self._prefill_shapes.add((S, W))
        self._mirror("prefill", padded, table, np.asarray([L], np.int32))
        with annotate("dispatch"):
            out = self._prefill_jit(
                self.params, self._cache, jnp.asarray(padded),
                jnp.asarray(table), jnp.asarray([L], np.int32),
            )
        last, self._cache = out[0], out[1]
        if self.kv_dtype is not None:
            self._note_kv_err(out[2])
        self._tokens_prefilled += L
        self._max_prefilled = max(self._max_prefilled, L)
        with annotate("readback"):
            return np.asarray(last[0])

    def decode(self, tokens, seq_ids, seq_lens) -> np.ndarray:
        """One decode iteration: for each running sequence, write the
        given (just-sampled) token at position ``seq_lens[i]`` and
        return the fp32 (B, vocab) logits predicting the next one.

        ``tokens``/``seq_ids``/``seq_lens`` are parallel host lists; the
        batch is padded to its pow2 bucket with inert rows (invalid
        tables, ``seq_len = -1`` → the page write drops, the gather
        masks to nothing).

        With ``pp_stages > 1`` the iteration splits into per-stage
        microbatches dispatched as separate steps (same per-row
        results — batch composition never changes a stream).
        """
        B = len(tokens)
        if self.pp_stages > 1 and B > 1:
            from chainermn_tpu.parallel.pipeline import (
                decode_microbatches,
            )

            return np.concatenate([
                self._decode_step(tokens[a:b], seq_ids[a:b],
                                  seq_lens[a:b])
                for a, b in decode_microbatches(B, self.pp_stages)
            ], axis=0)
        return self._decode_step(tokens, seq_ids, seq_lens)

    def _decode_step(self, tokens, seq_ids, seq_lens) -> np.ndarray:
        B = len(tokens)
        if B == 0:
            raise ValueError("empty decode batch")
        if B > self.config.max_batch:
            raise ValueError(
                f"decode batch {B} exceeds max_batch "
                f"{self.config.max_batch}"
            )
        with annotate("table-build"):
            Bp = _bucket(B, self.config.batch_buckets, "decode batch")
            W = max(
                self.table_width(int(l) + 1) for l in seq_lens
            )
            tok = np.zeros((Bp,), np.int32)
            tok[:B] = np.asarray(tokens, np.int32)
            lens = np.full((Bp,), -1, np.int32)
            lens[:B] = np.asarray(seq_lens, np.int32)
            tables = np.full((Bp, W), self.kv.invalid, np.int32)
            for i, sid in enumerate(seq_ids):
                tables[i] = self.kv.padded_table(sid, W)
            self._decode_shapes.add((Bp, W))
        self._mirror("decode", tok, tables, lens)
        with annotate("dispatch"):
            out = self._decode_jit(
                self.params, self._cache, jnp.asarray(tok),
                jnp.asarray(tables), jnp.asarray(lens),
            )
        logits, self._cache = out[0], out[1]
        if self.kv_dtype is not None:
            self._note_kv_err(out[2])
        self._tokens_decoded += B
        with annotate("readback"):
            return np.asarray(logits[:B])

    def chunk(self, token_rows, seq_ids, start_lens) -> np.ndarray:
        """One multi-token step: for each row, write ``len(token_rows[i])``
        consecutive tokens starting at context position ``start_lens[i]``
        and return fp32 (B, T, vocab) logits — ``logits[i, t]`` predicts
        position ``start_lens[i] + t + 1``, exactly what ``len(row)``
        sequential :meth:`decode` calls would have produced (bit-exact:
        the T=1 lowering is shared, and each query carries its own
        causal bound).

        This one program serves both speculative *verify* (row =
        pending token + draft) and prefix-cache *suffix prefill* (row =
        the un-shared prompt tail).  Rows may over-run a sequence's real
        suffix (draft tokens, T-bucket padding): those writes land
        beyond the masked context and are rewritten by a later step
        before any mask exposes them.
        """
        B = len(token_rows)
        if B == 0:
            raise ValueError("empty chunk batch")
        if B > self.config.max_batch:
            raise ValueError(
                f"chunk batch {B} exceeds max_batch {self.config.max_batch}"
            )
        Tmax = max(len(r) for r in token_rows)
        if Tmax == 0:
            raise ValueError("empty chunk row")
        T = self._bucket_grow(Tmax, self._chunk_buckets,
                              self.config.max_len, "chunk length")
        Bp = _bucket(B, self.config.batch_buckets, "decode batch")
        W = max(self.table_width(self.kv.seq_len(sid)) for sid in seq_ids)
        # Sequence-parallel routing: single-row slices whose T bucket
        # the sp axis divides run under the shard_map program (bit-
        # identical — the gather is pure concatenation); everything
        # else (multi-row verify batches, tiny buckets) stays on the
        # single-device chunk program.
        use_sp = bool(self.sp and B == 1 and T % self.sp == 0)
        with annotate("table-build"):
            tok = np.zeros((Bp, T), np.int32)
            start = np.full((Bp,), -1, np.int32)
            tables = np.full((Bp, W), self.kv.invalid, np.int32)
            for i, (row, sid, s) in enumerate(
                zip(token_rows, seq_ids, start_lens)
            ):
                tok[i, : len(row)] = np.asarray(row, np.int32)
                start[i] = int(s)
                tables[i] = self.kv.padded_table(sid, W)
        if use_sp:
            self._sp_shapes.add((Bp, T, W))
            step = self._sp_chunk_jit
        else:
            self._chunk_shapes.add((Bp, T, W))
            step = self._chunk_jit
        self._mirror("chunk", tok, tables, start, use_sp)
        with annotate("dispatch"):
            out = step(
                self.params, self._cache, jnp.asarray(tok),
                jnp.asarray(tables), jnp.asarray(start),
            )
        logits, self._cache = out[0], out[1]
        if self.kv_dtype is not None:
            self._note_kv_err(out[2])
        self._tokens_chunked += sum(len(r) for r in token_rows)
        covered = max(
            (int(s) + len(r)
             for r, s in zip(token_rows, start_lens) if int(s) >= 0),
            default=0,
        )
        self._max_prefilled = max(self._max_prefilled, covered)
        with annotate("readback"):
            return np.asarray(logits[:B])

    def prefill_cached(self, token_ids, seq_id, n_cached: int) -> np.ndarray:
        """Prefill a prompt whose first ``n_cached`` tokens are already
        covered by shared prefix pages: only the suffix runs through the
        chunk step (attending over the cached pages).  Returns the fp32
        (vocab,) logits of the last prompt token — bit-identical to what
        a full :meth:`prefill` would have produced.  ``n_cached`` must
        leave at least one suffix token (the fully-cached case needs the
        rewind path: CoW the last page, re-decode the final token)."""
        toks = np.asarray(token_ids, np.int32).reshape(-1)
        L = len(toks)
        if n_cached <= 0:
            return self.prefill(toks, seq_id)
        if n_cached >= L:
            raise ValueError(
                f"n_cached {n_cached} leaves no suffix for a prompt of "
                f"{L} tokens (use the CoW rewind path)"
            )
        if L >= self.config.max_len:
            raise ValueError(
                f"prompt of {L} tokens leaves no room to generate within "
                f"max_len {self.config.max_len}"
            )
        suffix = [int(t) for t in toks[n_cached:]]
        logits = self.chunk([suffix], [seq_id], [n_cached])
        self._tokens_prefilled += len(suffix)
        self._tokens_prefix_cached += n_cached
        return logits[0, len(suffix) - 1]

    def make_writable(self, seq_id, position: int) -> bool:
        """Copy-on-write guard before a K/V write at ``position``:
        delegates the accounting to :meth:`PagedKVCache.make_writable`
        and, when a split happened, copies the device page so the
        writer's fresh page starts as an exact replica.  Returns whether
        a split happened.  May raise
        :class:`~chainermn_tpu.serving.kv_cache.OutOfBlocks`."""
        split = self.kv.make_writable(seq_id, position)
        if split is None:
            return False
        old, new = split
        self._mirror("cow", int(old), int(new))
        self._cache = self._cow_jit(
            self._cache, jnp.asarray(old, jnp.int32),
            jnp.asarray(new, jnp.int32),
        )
        self._cow_splits += 1
        return True

    def _note_kv_err(self, err) -> None:
        """Fold one step's KV round-trip quantization error into the
        running max and publish the ``serve/kv_quant_err`` gauge when
        telemetry is active (host-plane: gauges cannot be set in-jit)."""
        self._kv_quant_err = max(self._kv_quant_err, float(err))
        from chainermn_tpu.observability import reporter as _reporter
        from chainermn_tpu.observability import spans as _spans

        if _spans.telemetry_active():
            rep = _reporter.get_reporter()
            if rep is not None:
                rep.gauge("serve/kv_quant_err", self._kv_quant_err)

    # -- speculative drafts --------------------------------------------
    def propose_draft(self, context, n_draft: int) -> List[int]:
        """Up to ``n_draft`` draft tokens continuing ``context`` from the
        resolved draft source — n-gram prompt lookup or the truncated
        draft model.  Either way a pure deterministic function of the
        context alone, so the exact-match acceptance downstream keeps
        streams bit-exact regardless of which source proposed."""
        if n_draft <= 0:
            return []
        if self.draft_model is not None:
            return self.draft_model.propose(context, n_draft)
        return _ngram_draft(context, n_draft)

    # -- sampling ------------------------------------------------------
    @staticmethod
    def sample(logits: np.ndarray, params: SamplingParams,
               position: int) -> int:
        """Sample one token from fp32 (vocab,) logits.  Greedy at
        ``temperature == 0`` (np.argmax — deterministic, first-max on
        ties).  Otherwise counter-based: the RNG is seeded from
        ``(seed, position)`` alone, so the draw does not depend on batch
        composition, scheduling order, or preemption history."""
        with annotate("sample"):
            if params.temperature == 0.0:
                return int(np.argmax(logits))
            z = logits.astype(np.float64) / params.temperature
            if params.top_k:
                k = min(params.top_k, z.shape[-1])
                cutoff = np.partition(z, -k)[-k]
                z = np.where(z >= cutoff, z, -np.inf)
            z = z - z.max()
            p = np.exp(z)
            p /= p.sum()
            rng = np.random.default_rng((int(params.seed), int(position)))
            return int(rng.choice(p.shape[-1], p=p))

    # -- maintenance ---------------------------------------------------
    def defragment(self) -> int:
        """Compact the page pool (see :meth:`PagedKVCache.defragment`)
        and permute the device pages to match.  Returns the number of
        pages moved (0 = already compact, no device copy)."""
        perm = self.kv.defragment()
        if perm is None:
            return 0
        self._mirror("defrag", np.asarray(perm))
        iperm = jnp.asarray(perm)

        def permute(leaf):
            # every cache leaf is a page array: (n_blocks, bs, n_kv, d)
            return jnp.take(leaf, iperm, axis=0)

        self._cache = jax.tree.map(permute, self._cache)
        return int(self.kv._last_defrag_moves)

    def reset(self) -> None:
        """Drop every sequence and the prefix index (device pages are
        left as-is — unreachable without a table entry)."""
        for sid in self.kv.seq_ids():
            self.kv.free(sid)
        self.kv.drop_prefix_cache()

    # -- introspection -------------------------------------------------
    def stats(self) -> dict:
        """Occupancy + compile bookkeeping (the recompile-count test's
        surface, and the scheduler's gauge source)."""
        out = {
            "cache": self.kv.stats().as_dict(),
            "prefill_compiles": len(self._prefill_shapes),
            "decode_compiles": len(self._decode_shapes),
            "chunk_compiles": len(self._chunk_shapes),
            "prefill_shapes": sorted(self._prefill_shapes),
            "decode_shapes": sorted(self._decode_shapes),
            "chunk_shapes": sorted(self._chunk_shapes),
            "tokens_prefilled": self._tokens_prefilled,
            "tokens_decoded": self._tokens_decoded,
            "tokens_chunked": self._tokens_chunked,
            "tokens_prefix_cached": self._tokens_prefix_cached,
            "cow_splits": self._cow_splits,
        }
        # Quantized-KV keys only when the feature is on, so the default
        # stats shape (and everything golden-pinned to it) is unchanged.
        if self.kv_dtype is not None:
            out["kv_dtype"] = self.kv_dtype
            out["kv_quant_err"] = self._kv_quant_err
        # Same shape-stability rule for the new levers: keys appear only
        # when the feature is on.
        if self.draft_model is not None:
            out["draft_source"] = self.draft_source
            out["draft_layers"] = self.draft_model.n_layers
            out["draft_compiles"] = self.draft_model.compiles
        if self.prefill_chunk:
            out["prefill_chunk"] = self.prefill_chunk
        if self.sp:
            out["sp"] = self.sp
            out["sp_chunk_compiles"] = len(self._sp_shapes)
            out["sp_chunk_shapes"] = sorted(self._sp_shapes)
        if self._bucket_growths:
            # Lazily-grown ladder entries (== extra traces accepted on
            # this replica); absent until a growth actually happens so
            # the default stats shape is unchanged.
            out["bucket_growths"] = self._bucket_growths
        out["max_bucket"] = self._max_prefilled
        # Cross-check against jit's own cache where the API exists.
        for name, fn in (("prefill", self._prefill_jit),
                         ("decode", self._decode_jit),
                         ("chunk", self._chunk_jit)):
            try:
                out[f"{name}_jit_cache_size"] = fn._cache_size()
            except Exception:
                pass
        return out

    # -- convenience ---------------------------------------------------
    def generate(self, prompt, max_new_tokens: int,
                 sampling: Optional[SamplingParams] = None,
                 stop_token: Optional[int] = None) -> List[int]:
        """Single-request generation through the SAME prefill/decode
        machinery the scheduler drives — the sequential oracle the
        continuous-batching parity test compares against, and the
        simplest way to smoke-test an engine."""
        sp = sampling or SamplingParams()
        toks = list(np.asarray(prompt, np.int32).reshape(-1))
        L = len(toks)
        total = min(L + max_new_tokens, self.config.max_len)
        sid = object()
        self.kv.allocate(sid, L)
        try:
            logits = self.prefill(toks, sid)
            out: List[int] = []
            cur = L
            while cur < total:
                nxt = self.sample(logits, sp, cur)
                out.append(nxt)
                if stop_token is not None and nxt == stop_token:
                    break
                if cur + 1 >= total:
                    break
                self.kv.extend(sid, cur + 1)
                logits = self.decode([nxt], [sid], [cur])[0]
                cur += 1
            return out
        finally:
            self.kv.free(sid)
