"""Transformer encoder-decoder and LM.

Reference anchor: BASELINE config #4 ("Transformer enc-dec WMT,
hierarchical 2D allreduce on multi-host v4 pod") — the reference repo
itself had no transformer (it predates them); this is the net-new model
family the baseline configs demand, built TPU-first: bf16 activations,
einsum attention that XLA tiles onto the MXU, static shapes, and
``lax.scan``-free dense blocks (depth unrolled at trace time).

Tensor-parallel note: head and MLP-hidden dimensions are the natural
``model``-axis shardings; ``chainermn_tpu.parallel.sharding`` carries the
PartitionSpec rules, and the attention layer can run sequence-parallel via
``chainermn_tpu.parallel.ring_attention`` / ``ulysses``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.models.block_table import (
    BlockTable,
    CCASpec,
    ExpertsSpec,
    GDNSpec,
    KDASpec,
    LayerSpec,
    MLASpec,
    SSMSpec,
    YarnSpec,
    gpt2_table,
    rotary_frequencies,
)
from chainermn_tpu.observability.spans import named_scope, telemetry_active


def sinusoidal_positions(max_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(max_len)[:, None]
    div = np.exp(np.arange(0, d_model, 2) * (-np.log(10000.0) / d_model))
    pe = np.zeros((max_len, d_model), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


class ZeroCentredRMSNorm(nn.Module):
    """``x rsqrt(mean x^2 + eps) (1 + w)``: the learned ``w`` starts at 0;
    statistics in float32."""

    epsilon: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (x32 * (1.0 + w)).astype(self.dtype)


#: The block table's ``NORMS`` as modules.
NORM_CLASSES = {"layernorm": nn.LayerNorm, "rmsnorm": nn.RMSNorm,
                "rmsnorm_zc": ZeroCentredRMSNorm}


class MultiHeadAttention(nn.Module):
    d_model: int
    n_heads: int
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None  # pluggable (ring/ulysses SP)
    decode: bool = False        # incremental decoding with a KV cache
    cache_len: int = 0          # cache capacity (max sequence length)
    n_kv_heads: Optional[int] = None  # GQA/MQA: fewer K/V heads (divides
                                      # n_heads; None = MHA)
    paged: Optional[str] = None  # paged KV cache (serving): None |
                                 # "prefill" (write whole prompt, dense
                                 # causal attention) | "decode" (write one
                                 # token, paged single-query attention)
    page_count: int = 0          # number of cache pages (paged modes)
    page_size: int = 0           # tokens per page (paged modes)
    kv_dtype: Optional[str] = None  # quantized pages: "int8" stores K/V
                                    # pages as int8 with per-token-per-head
                                    # fp32 scales ("k_scales"/"v_scales"
                                    # cache leaves); None = pages in the
                                    # compute dtype
    sp_axis: Optional[str] = None   # sequence-parallel chunk prefill: the
                                    # token axis is sharded over this mesh
                                    # axis (shard_map); K/V all-gather to
                                    # the full slice before the page write
                                    # (paged="chunk" only)
    scale: Optional[float] = None   # softmax scale; None = 1/sqrt(d_head).
                                    # An ``attention_fn`` must have been
                                    # built with the same one
    d_head: Optional[int] = None    # a head's width; None = d_model /
                                    # n_heads
    rotary_dim: int = 0             # rotary positions 0..S-1 on the first
                                    # so many dimensions of a query and key
                                    # head (0: none), at ``rope_theta``
    rope_theta: float = 10000.0
    yarn: Optional[YarnSpec] = None  # the rotary positions YaRN-scaled
    window: Optional[int] = None    # a query sees its ``window`` most
                                    # recent positions, itself among them
                                    # (None: every earlier one).  The row's:
                                    # handed to the ``attention_fn`` as
                                    # ``window=``, a band on the dense
                                    # path's mask
    block_diffusion: Optional[int] = None  # the row runs under the
                                    # block-diffusion mask with this block
                                    # (its table's): the rows are a
                                    # document's clean copy then its noised
                                    # one (``flash_attention.
                                    # blockdiff_mask``), handed to the
                                    # ``attention_fn`` as
                                    # ``block_diffusion=``, the dense
                                    # path's whole mask
    qk_norm: Optional[str] = None   # a norm of ``NORM_CLASSES`` over each
                                    # query and key head, before the
                                    # rotation (``q_norm``, ``k_norm``)
    norm_eps: float = 1e-6          # its epsilon
    out_gate: bool = False          # ``query`` projects [q | gate] a head
                                    # and ``out`` takes attn * sigmoid(gate)
    head_gate: bool = False         # ``out`` takes attn * sigmoid(gate(h)),
                                    # ``gate`` one number a head (d_model x
                                    # n_heads), as an mla row's

    @nn.compact
    def __call__(self, q_in, kv_in, mask=None, *, block_tables=None,
                 seq_lens=None, positions=None):
        """``positions``: the (S,) token positions the row's rotary
        positions turn by; None is ``0 .. S-1``."""
        d_head = self.d_head or self.d_model // self.n_heads
        n_kv = self.n_kv_heads or self.n_heads
        if (self.rotary_dim or self.qk_norm or self.out_gate
                or self.head_gate or self.window is not None
                or self.block_diffusion is not None) and (
                self.decode or self.paged is not None):
            raise ValueError(
                "an attention row with rotary positions, QK-norm, an "
                "output gate, a window or the block-diffusion mask is "
                "built for training and whole-sequence evaluation: the "
                "KV caches take no positions, keep no gate, free no page "
                "behind a window and yield a token a step, not a block")
        if self.n_heads % n_kv:
            raise ValueError(
                f"n_kv_heads ({n_kv}) must divide n_heads ({self.n_heads})"
            )
        if self.scale is not None:
            if self.paged is not None:
                raise ValueError(
                    "a softmax scale other than 1/sqrt(d_head) is not "
                    "built for the paged KV cache (ops/decode_attention)"
                )
            if self.attention_fn is not None and getattr(
                    self.attention_fn, "scale", None) != self.scale:
                raise ValueError(
                    f"this layer's softmax scale is {self.scale}, the "
                    f"attention_fn was built with "
                    f"{getattr(self.attention_fn, 'scale', None)}: pass "
                    f"the same scale to make_flash_attention_fn"
                )
        dense = lambda name, h, width=d_head: nn.DenseGeneral(  # noqa: E731
            (h, width), dtype=self.dtype, name=name, use_bias=False
        )
        gate = None
        with named_scope("mixer-proj"):
            if self.out_gate:
                q, gate = jnp.split(
                    dense("query", self.n_heads, 2 * d_head)(q_in), 2,
                    axis=-1)
            else:
                q = dense("query", self.n_heads)(q_in)
            k = dense("key", n_kv)(kv_in)
            v = dense("value", n_kv)(kv_in)
        if self.head_gate:
            with named_scope("mixer-gate"):
                gate = nn.Dense(self.n_heads, dtype=self.dtype,
                                use_bias=False, name="gate")(q_in)[..., None]
        if self.qk_norm or self.rotary_dim:
            with named_scope("attn-rope"):
                if self.qk_norm:
                    norm = lambda name: NORM_CLASSES[self.qk_norm](  # noqa: E731
                        epsilon=self.norm_eps, dtype=self.dtype, name=name)
                    # (flax's own module scope would read as the layers'
                    # pre-norm, as around Mamba2Mixer's gated norm)
                    with nn.override_named_call(False):
                        q, k = norm("q_norm")(q), norm("k_norm")(k)
                if self.rotary_dim:
                    pos = (jnp.arange(q.shape[1]) if positions is None
                           else positions)
                    q, k = (rotate_partial(
                        x, pos, self.rotary_dim, self.rope_theta,
                        self.yarn).astype(self.dtype) for x in (q, k))

        def project_out(out):
            if gate is not None:
                with named_scope("mixer-gate"):
                    out = out * nn.sigmoid(gate.astype(jnp.float32)).astype(
                        out.dtype)
            with named_scope("mixer-proj"):
                return nn.DenseGeneral(
                    self.d_model, axis=(-2, -1), dtype=self.dtype,
                    name="out", use_bias=False)(out)

        if self.paged is not None:
            # Paged KV cache (serving, docs/serving.md): K/V live in
            # fixed-size pages indexed by a per-sequence block table, so
            # sequences of different lengths share one physical cache and
            # grow in O(page_size) quanta.  Same "cache" collection idiom
            # (and the same param structure) as the dense decode path
            # below, so trained params drop in unchanged.
            from chainermn_tpu.ops.decode_attention import (
                paged_attention_chunk,
                paged_attention_decode,
                write_chunk_pages,
                write_prompt_pages,
                write_token_pages,
            )

            if self.decode:
                raise ValueError(
                    "paged and decode are mutually exclusive KV cache "
                    "modes: the dense cache keeps one scalar index for "
                    "the whole batch, pages keep per-sequence lengths"
                )
            if self.attention_fn is not None:
                raise ValueError(
                    "paged modes are incompatible with attention_fn: the "
                    "pluggable adapters ignore the cache mask and would "
                    "attend to the wrong page slots"
                )
            if self.paged not in ("prefill", "decode", "chunk"):
                raise ValueError(
                    f"paged must be 'prefill', 'decode' or 'chunk', got "
                    f"{self.paged!r}"
                )
            if self.sp_axis is not None and self.paged != "chunk":
                raise ValueError(
                    "sp_axis shards the multi-token chunk step only; "
                    "decode is per-token (nothing to shard) and whole-"
                    "prompt prefill should use the chunk path when "
                    "sequence-sharded"
                )
            if self.page_count <= 0 or self.page_size <= 0:
                raise ValueError("paged modes require page_count > 0 and "
                                 "page_size > 0")
            if block_tables is None or seq_lens is None:
                raise ValueError(
                    "paged modes require block_tables and seq_lens"
                )
            if self.kv_dtype not in (None, "int8"):
                raise ValueError(
                    f"kv_dtype must be None or 'int8', got "
                    f"{self.kv_dtype!r}"
                )
            from chainermn_tpu.communicators.quant import (
                dequantize_kv,
                quantize_kv,
            )

            # Quantized pages (kv_dtype="int8", docs/serving.md): K/V
            # pages store int8 payloads with a per-token-per-head fp32
            # scale leaf alongside — the scale pages share the page
            # geometry's leading (page, slot) axes, so the SAME scatter
            # writes and the same block-table gather route them.
            page_dt = jnp.int8 if self.kv_dtype else k.dtype
            pages = (self.page_count, self.page_size, n_kv, d_head)
            pk = self.variable(
                "cache", "k_pages", lambda: jnp.zeros(pages, page_dt)
            )
            pv = self.variable(
                "cache", "v_pages", lambda: jnp.zeros(pages, page_dt)
            )
            sk = sv = None
            if self.kv_dtype:
                sshape = (self.page_count, self.page_size, n_kv)
                sk = self.variable(
                    "cache", "k_scales",
                    lambda: jnp.zeros(sshape, jnp.float32),
                )
                sv = self.variable(
                    "cache", "v_scales",
                    lambda: jnp.zeros(sshape, jnp.float32),
                )

            def write_kv(writer, lens):
                # One write path for all three paged modes: quantize the
                # fresh K/V (when kv_dtype is on) and scatter payloads
                # and scales through the same (page, slot) routing.
                if not self.kv_dtype:
                    pk.value = writer(pk.value, k, block_tables, lens)
                    pv.value = writer(pv.value, v, block_tables, lens)
                    return
                qk, k_sc = quantize_kv(k)
                qv, v_sc = quantize_kv(v)
                pk.value = writer(pk.value, qk, block_tables, lens)
                pv.value = writer(pv.value, qv, block_tables, lens)
                sk.value = writer(sk.value, k_sc, block_tables, lens)
                sv.value = writer(sv.value, v_sc, block_tables, lens)
                # Round-trip quantization error of this write — the
                # ``serve/kv_quant_err`` gauge's source (engine pulls the
                # "intermediates" collection when kv_dtype is on).
                err = jnp.maximum(
                    jnp.max(jnp.abs(dequantize_kv(qk, k_sc, jnp.float32)
                                    - k.astype(jnp.float32))),
                    jnp.max(jnp.abs(dequantize_kv(qv, v_sc, jnp.float32)
                                    - v.astype(jnp.float32))),
                )
                self.sow("intermediates", "kv_quant_err", err)

            def scales():
                # Read AFTER write_kv, so the freshly-written slots carry
                # this step's scales, not the pre-write zeros.
                return dict(
                    k_scales=sk.value if self.kv_dtype else None,
                    v_scales=sv.value if self.kv_dtype else None,
                )

            if self.paged == "prefill":
                # Write the whole prompt's K/V (padding positions beyond
                # seq_lens route to the invalid page and are dropped);
                # the attention itself is the ordinary dense causal path
                # over the local K/V — the prompt IS the whole context,
                # and it is still local in full precision (quantization
                # error enters only when pages are READ back: decode,
                # chunk, and prefix-cached suffix prefill).
                write_kv(write_prompt_pages, seq_lens)
            elif self.paged == "chunk":
                # Verify/suffix-prefill mode: T consecutive tokens per
                # sequence starting at position ``seq_lens[b]`` (here the
                # context length BEFORE the chunk).  All T tokens' K/V are
                # written first, then each query attends with its own
                # causal bound — exactly what T sequential decode steps
                # would have seen, in one lowering.
                attn_start = seq_lens
                if self.sp_axis is not None:
                    # Sequence-sharded slice (Ulysses-style): this shard
                    # holds C consecutive tokens starting at global
                    # position seq_lens + r*C.  Gather the FULL slice's
                    # K/V (pure concatenation — no cross-shard
                    # reduction, so pages are byte-identical to the
                    # unsharded chunk's), write it whole on every shard
                    # (identical values -> the cache stays replicated),
                    # and attend only the local queries at their global
                    # causal bounds.  Quantization (kv_dtype) runs
                    # after the gather, on the full slice, inside
                    # write_kv.
                    from jax import lax as _splax

                    from chainermn_tpu.parallel.ring_attention import (
                        gather_sequence_kv,
                    )

                    C = q.shape[1]
                    k, v = gather_sequence_kv(k, v, self.sp_axis)
                    r = _splax.axis_index(self.sp_axis)
                    # Padding rows (seq_lens < 0) must stay fully
                    # masked on every shard, not just rank 0.
                    attn_start = jnp.where(
                        seq_lens >= 0, seq_lens + r * C, seq_lens
                    )
                write_kv(write_chunk_pages, seq_lens)
                out = paged_attention_chunk(
                    q, pk.value, pv.value, block_tables, attn_start,
                    **scales(),
                )
                return project_out(out)
            else:
                if q.shape[1] != 1:
                    raise ValueError(
                        f"paged decode consumes exactly one token per "
                        f"call, got a length-{q.shape[1]} chunk"
                    )
                write_kv(write_token_pages, seq_lens)
                out = paged_attention_decode(
                    q, pk.value, pv.value, block_tables, seq_lens + 1,
                    **scales(),
                )
                return project_out(out)

        if self.decode:
            # KV cache (flax "cache" collection): one new token per call is
            # written at the running index; attention runs over the whole
            # cache with positions beyond the index masked.  Same param
            # structure as the training path, so trained params drop in.
            if self.attention_fn is not None:
                raise ValueError(
                    "decode=True is incompatible with attention_fn: the "
                    "pluggable adapters (flash/ring/ulysses) impose their "
                    "own causality with the query at local position 0 and "
                    "ignore the cache mask, so they would silently attend "
                    "to the wrong cache slots; build the decode twin "
                    "without attention_fn (generate() does this)"
                )
            if self.cache_len <= 0:
                raise ValueError("decode=True requires cache_len > 0")
            if q.shape[1] != 1:
                raise ValueError(
                    f"decode mode consumes exactly one token per call, got "
                    f"a length-{q.shape[1]} chunk (the single-position "
                    f"cache mask would silently hide the chunk's own "
                    f"tokens); feed tokens one at a time, as generate() does"
                )
            B = q.shape[0]
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros((B, self.cache_len, n_kv, d_head),
                                  k.dtype),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros((B, self.cache_len, n_kv, d_head),
                                  v.dtype),
            )
            cidx = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            i = cidx.value
            import jax.lax as _lax

            ck.value = _lax.dynamic_update_slice(ck.value, k, (0, i, 0, 0))
            cv.value = _lax.dynamic_update_slice(cv.value, v, (0, i, 0, 0))
            cidx.value = i + q.shape[1]
            k, v = ck.value, cv.value
            mask = (jnp.arange(self.cache_len) <= i)[None, None, None, :]

        if self.attention_fn is not None:
            # GQA-aware adapters (flash and its SP compositions) consume
            # the reduced kv head count directly, and take the row's
            # window where it has one, its block where its table trains
            # by block diffusion.
            from chainermn_tpu.ops.flash_attention import row_mask

            out = self.attention_fn(q, k, v, mask, **row_mask({
                "window": self.window,
                "block_diffusion": self.block_diffusion}))
        else:
            if self.block_diffusion is not None:
                from chainermn_tpu.ops.flash_attention import blockdiff_mask

                mask = blockdiff_mask(
                    q.shape[1] // 2, self.block_diffusion)[None, None]
            if self.window is not None:
                # (a window is causal, as the kernels have it)
                behind = jnp.arange(q.shape[1])[:, None] - jnp.arange(
                    k.shape[1])[None, :]
                band = (behind >= 0) & (behind < self.window)
                mask = band if mask is None else mask & band
            if n_kv != self.n_heads:
                # Dense-softmax path: broadcast kv heads (the grads sum
                # back over the group through repeat's transpose).
                k = jnp.repeat(k, self.n_heads // n_kv, axis=2)
                v = jnp.repeat(v, self.n_heads // n_kv, axis=2)
            scale = self.scale or 1.0 / np.sqrt(d_head)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            if mask is not None:
                logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
            weights = nn.softmax(logits.astype(jnp.float32)).astype(self.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
        return project_out(out)


class FeedForward(nn.Module):
    d_model: int
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.d_ff, dtype=self.dtype, use_bias=False, name="wi")(x)
        h = nn.gelu(h)
        return nn.Dense(self.d_model, dtype=self.dtype, use_bias=False, name="wo")(h)


class Relu2FeedForward(nn.Module):
    """``wo(relu(wi x)^2)``: two matrices, no gate."""

    d_model: int
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.d_ff, dtype=self.dtype, use_bias=False, name="wi")(x)
        return nn.Dense(self.d_model, dtype=self.dtype, use_bias=False,
                        name="wo")(jnp.square(nn.relu(h)))


class GatedFeedForward(nn.Module):
    """SwiGLU: ``wo(silu(a) * b)`` with ``[a | b] = wi x``, one input
    matrix of width ``2 d_ff``."""

    d_model: int
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(2 * self.d_ff, dtype=self.dtype, use_bias=False,
                     name="wi")(x)
        a, b = jnp.split(h, 2, axis=-1)
        return nn.Dense(self.d_model, dtype=self.dtype, use_bias=False,
                        name="wo")(nn.silu(a) * b)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, np.log(1e-3), np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GroupedRMSNorm(nn.Module):
    """RMSNorm over each of ``groups`` equal parts of the last axis, one
    learned scale a channel; statistics in float32."""

    groups: int
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        parts = x.astype(jnp.float32).reshape(
            x.shape[:-1] + (self.groups, d // self.groups))
        parts = parts * jax.lax.rsqrt(
            jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
            + self.epsilon)
        return (parts.reshape(x.shape) * scale).astype(self.dtype)


class ExpertLayer(nn.Module):
    """A sparse-expert FFN for one expert-parallel rank (an
    :class:`ExpertsSpec` row): the router over all the published experts
    in float32, the (token, choice) pairs of the experts held here sorted
    by expert and put through the held experts as grouped matmuls (every
    stack (count, d_expert, d_model): ``experts_up`` and ``experts_gate``
    hold their matrices output-major), each result added back times its
    router weight, plus the shared expert (of the experts' own form: a
    :class:`Relu2FeedForward` or a :class:`GatedFeedForward`) over every
    token where the spec has one, times ``sigmoid(h w_s)`` where the spec
    gates it.  By the spec's kinds: the router ``sigmoid`` (top-k,
    :func:`moe_dropless.route`, group-limited where the spec has groups), ``softmax`` (top-k, no bias,
    :func:`moe_dropless.route_softmax`) or ``mlp_softmax`` (top-1,
    :func:`moe_dropless.route_mlp_softmax`, which takes the router state
    of the layer before and hands its own on: then the layer is called
    with ``state`` and returns ``(out, state)``); the experts ``relu2``
    (two matrices) or ``swiglu`` (three).  No token is dropped
    (:mod:`chainermn_tpu.parallel.moe_dropless`); what the absent experts
    would add is left out.  ``sow``s the chosen experts as
    ``intermediates/chosen`` for whoever asks for that collection."""

    d_model: int
    spec: ExpertsSpec
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-5      # the mlp_softmax router's own RMSNorm

    @nn.compact
    def __call__(self, h, state=None):
        from chainermn_tpu.ops.grouped_matmul import (
            TILE_ROWS,
            grouped_relu2_mlp,
            grouped_swiglu_mlp,
            weight_blocks,
        )
        from chainermn_tpu.ops.ssd import publish_geometry
        from chainermn_tpu.parallel import moe_dropless as moe

        z, d = self.spec, self.d_model
        first, count = z.experts_held
        f32 = jnp.float32
        lecun = nn.initializers.lecun_normal()
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        stacked_out_major = nn.initializers.lecun_normal(
            in_axis=-1, out_axis=-2, batch_axis=(0,))
        lead = h.shape[:-1]
        tokens = int(np.prod(lead))
        n_rows = moe.rows_bound(tokens * z.top_k, count, z.n_experts)
        if (z.router == "mlp_softmax") != (state is not None):
            raise ValueError("an mlp_softmax router, and only it, takes "
                             "the router state of the layer before")
        if telemetry_active():
            n_tiles = moe.buffer_tiles(n_rows, count)
            publish_geometry("moe_geometry", "moe", {
                "experts": z.n_experts, "experts_held": count,
                "top_k": z.top_k, "tokens": tokens,
                "pair_rows": tokens * z.top_k,
                "buffer_rows": TILE_ROWS * n_tiles, "tile_rows": TILE_ROWS,
                "dispatch_steps": n_tiles,
                **dict(zip(("d_block", "expert_block"), weight_blocks(
                    d, z.d_expert, jnp.dtype(self.dtype).itemsize)))},
                form="pallas_tile_aligned", router=z.router,
                expert=z.expert, dispatch_form=moe.DISPATCH_FORM)
        with named_scope("moe-layer"):
            x = h.reshape(tokens, d)
            with named_scope("moe-route"):
                router = lambda: self.param(  # noqa: E731
                    "router", lecun, (d, z.n_experts), f32)
                # (the softmax router has no bias on the choice)
                bias = None if z.router == "softmax" else self.param(
                    "router_bias", nn.initializers.zeros, (z.n_experts,),
                    f32)
                if z.router == "softmax":
                    chosen, weight = moe.route_softmax(
                        x, router(), top_k=z.top_k, scaling=z.scaling)
                elif z.router == "sigmoid":
                    chosen, weight = moe.route(
                        x, router(), bias, top_k=z.top_k, scaling=z.scaling,
                        n_group=z.n_group, topk_group=z.topk_group)
                else:
                    r = z.d_router
                    chosen, weight, state = moe.route_mlp_softmax(
                        x, state.reshape(tokens, r), {
                            "down": self.param("router_down", lecun,
                                               (d, r), f32),
                            "gamma": self.param(
                                "router_gamma", nn.initializers.ones, (r,),
                                f32),
                            "norm": self.param(
                                "router_norm", nn.initializers.ones, (r,),
                                f32),
                            "w1": self.param("router_w1", lecun, (r, r),
                                             f32),
                            "w2": self.param("router_w2", lecun, (r, r),
                                             f32),
                            "w3": self.param("router_w3", lecun,
                                             (r, z.n_experts), f32)},
                        bias, eps=self.norm_eps)
                    state = state.reshape(lead + (r,))
                self.sow("intermediates", "chosen", chosen)
                plan = moe.dispatch(chosen, (first, count), n_rows)
            with named_scope("moe-dispatch"):
                rows = moe.gather_rows(x, plan)
            shape = (count, z.d_expert, d)
            if z.expert == "relu2":
                routed = grouped_relu2_mlp(
                    rows,
                    self.param("experts_up", stacked_out_major, shape, f32),
                    self.param("experts_down", stacked, shape, f32),
                    plan.tile_group, plan.n_live)
            else:
                routed = grouped_swiglu_mlp(
                    rows,
                    self.param("experts_gate", stacked_out_major, shape,
                               f32),
                    self.param("experts_up", stacked_out_major, shape, f32),
                    self.param("experts_down", stacked, shape, f32),
                    plan.tile_group, plan.n_live)
            with named_scope("moe-dispatch"):
                out = moe.combine(routed, weight, plan, tokens)
            if z.d_shared:
                with named_scope("moe-shared"):
                    ffn = (Relu2FeedForward if z.expert == "relu2"
                           else GatedFeedForward)
                    shared = ffn(d, z.d_shared, self.dtype, name="shared")(x)
                    if z.shared_gate:
                        shared = shared * nn.sigmoid(nn.Dense(
                            1, dtype=self.dtype, use_bias=False,
                            name="shared_gate")(x).astype(f32)).astype(
                                shared.dtype)
                    out = out + shared
            out = out.astype(self.dtype).reshape(lead + (d,))
            return out if state is None else (out, state)


def rebalance_routers(params, chosen, rate: float):
    """``params`` (a :class:`TransformerLM`'s) with every expert layer's
    ``router_bias`` moved one step by the balancing controller
    (:func:`chainermn_tpu.parallel.moe_dropless.rebalance`), each by the
    experts its own router chose this step.  ``chosen``: ``{layer name:
    (tokens, top_k) int}``, as the layers sow them
    (``intermediates/<layer>/ExpertLayer_0/chosen``) and a loss function
    hands them back as its ``aux``.  A training loop calls it after the
    optimizer's step; nothing else of ``params`` is touched."""
    from chainermn_tpu.parallel.moe_dropless import rebalance

    out = dict(params)
    for name, took in chosen.items():
        experts = dict(params[name]["ExpertLayer_0"])
        experts["router_bias"] = rebalance(experts["router_bias"], took,
                                           rate)
        out[name] = dict(params[name], ExpertLayer_0=experts)
    return out


def rotary_partner(rotary_dim: int, d_head: int,
                   lanes: Tuple[int, bool] = (0, False)) -> np.ndarray:
    """The (d_head, d_head) signed permutation ``P`` that fetches every
    lane's partner whole: ``(x @ P)[i] = -x[i + half]`` and ``(x @
    P)[i + half] = x[i]`` for ``i < half = rotary_dim / 2``, 0 past
    ``rotary_dim``.  ``lanes`` = ``(start, interleave)``: the turned
    lanes begin at ``start``, and where ``interleave`` the pairs are
    neighbours, ``(2i, 2i + 1)``.  A 0 / 1 / -1 matrix: a bfloat16
    operand's product with it, summed in float32, is that operand's own
    values."""
    start, interleave = lanes
    half = rotary_dim // 2
    i = np.arange(half)
    a, b = (start + 2 * i, start + 2 * i + 1) if interleave else (
        start + i, start + i + half)
    partner = np.zeros((d_head, d_head), np.float32)
    partner[b, a] = -1.0
    partner[a, b] = 1.0
    return partner


def _rotary_tables(positions, d_head: int, rotary_dim: int, theta: float,
                   yarn: Optional[YarnSpec],
                   lanes: Tuple[int, bool] = (0, False)):
    """``cos`` and ``sin`` of a row's angles as (S, 1, d_head) float32,
    the ``rotary_dim / 2`` frequencies laid twice side by side (each
    twice in a row where the pairs interleave) from the turned lanes'
    start and a frequency of 0 elsewhere (``cos`` 1, ``sin`` 0: those
    lanes pass through); under ``yarn`` the turned lanes times its
    scale."""
    freq, scale = rotary_frequencies(rotary_dim, theta, yarn)
    start, interleave = lanes
    rest = d_head - start - rotary_dim
    turned = np.repeat(freq, 2) if interleave else np.concatenate(
        [freq, freq])
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        np.concatenate([np.zeros(start), turned, np.zeros(rest)]),
        jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if yarn is not None:
        cos = cos * jnp.asarray(np.concatenate(
            [np.ones(start), np.full(rotary_dim, scale), np.ones(rest)]),
            jnp.float32)
        sin = sin * scale
    return cos, sin


def fetch_partner(x, rotary_dim: int, lanes: Tuple[int, bool] = (0, False)):
    """``x @ P`` in float32: every lane's partner, to the bit.  The
    product's precision follows from the operand's dtype and nothing
    else: one bfloat16 pass summed in float32 is exact for a bfloat16
    ``x``, any other takes ``Precision.HIGHEST``."""
    one_pass = x.dtype == jnp.bfloat16
    return jnp.einsum(
        "...d,de->...e", x,
        jnp.asarray(rotary_partner(rotary_dim, x.shape[-1], lanes), x.dtype),
        precision=None if one_pass else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _turn(x, cos, sin, rotary_dim: int, lanes=(0, False)):
    """``x cos + (x P) sin`` in float32, one pass over whole heads."""
    return x * cos + fetch_partner(x, rotary_dim, lanes) * sin


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def rotate_partial(x, positions, rotary_dim: int, theta: float,
                   yarn: Optional[YarnSpec] = None,
                   lanes: Tuple[int, bool] = (0, False)):
    """Rotary positions on the first ``rotary_dim`` of the last axis of
    ``x`` (b, S, heads, d_head), the rest passed through, in float32:
    dimension ``i`` of the first half of the rotated part pairs with ``i
    + rotary_dim / 2``, at the angle ``positions x theta^(-2 i /
    rotary_dim)`` — or, under ``yarn``, at the row's blended frequencies
    with ``cos`` and ``sin`` times its scale
    (``block_table.rotary_frequencies``).  ``lanes`` = ``(start,
    interleave)``, a latent-attention row's: the ``rotary_dim`` turned
    dimensions begin at lane ``start`` (a head ``[nope | rope]``), and
    where ``interleave`` pair ``i`` is lanes ``(2i, 2i + 1)`` of them.

    ``x`` comes in the type the caller holds it.  A head is turned whole,
    the lanes never cut into halves: ``x cos + (x P) sin`` against
    full-width tables, the partner lanes fetched by one product with the
    signed permutation ``P`` (:func:`rotary_partner`).  ``positions`` are
    token indices (integers: they take no gradient); the cotangent of
    ``x`` is the cotangent turned back, rounded to ``x``'s type once."""
    return _rotate_fwd(x, positions, rotary_dim, theta, yarn, lanes)[0]


def _rotate_fwd(x, positions, rotary_dim, theta, yarn, lanes=(0, False)):
    if telemetry_active():
        from chainermn_tpu.ops.ssd import publish_geometry

        publish_geometry(
            "rope_geometry", "rope", {
                "seq": x.shape[1], "heads": x.shape[2],
                "d_head": x.shape[-1], "rotary_dim": rotary_dim},
            form="lane_dense_product", operand=jnp.dtype(x.dtype).name,
            precision=("one_bf16_pass" if x.dtype == jnp.bfloat16
                       else "highest"))
    cos, sin = _rotary_tables(positions, x.shape[-1], rotary_dim, theta,
                              yarn, lanes)
    # (an empty array carries the operand's type to the backward rule)
    return _turn(x, cos, sin, rotary_dim, lanes), (
        cos, sin, jnp.zeros((0,), x.dtype))


def _rotate_bwd(rotary_dim, theta, yarn, lanes, residuals, g):
    cos, sin, like = residuals
    return _turn(g, cos, -sin, rotary_dim, lanes).astype(like.dtype), None


rotate_partial.defvjp(_rotate_fwd, _rotate_bwd)


def shift_tokens(x, by: int):
    """``x`` (b, S, ...) moved ``by`` tokens later along the sequence,
    zeros before the first: what a causal tap reads."""
    if by == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (by, 0)
    return jnp.pad(x, pad)[:, :x.shape[1]]


class CCAMixer(nn.Module):
    """Compressed convolutional attention (arXiv:2510.04476) as the
    ``zaya`` family lays it out (a :class:`CCASpec` row): queries and keys
    are projected DOWN into a latent of ``n_heads`` and ``n_kv_heads``
    heads, ``q~ = h W_q``, ``k~ = h W_k``; the values are two halves,
    ``[h W_v1 ; h_(t-1) W_v2]`` (with two KV heads: head 0 reads this
    token, head 1 the one before); over the query and key channels a
    causal depthwise convolution of ``time0`` taps and then one of
    ``time1`` taps grouped by head, both with a bias; ``q = conv[:q] +
    (q~ + repeat(k~)) / 2``, ``k = conv[q:] + (mean_group(q~) + k~) / 2``;
    per head ``q <- sqrt(D) q / |q|``, ``k <- temp_head sqrt(D) k / |k|``
    (``temp``: one learned float32 a KV head), rotary positions on the
    first ``rotary_dim`` dimensions; causal GQA softmax attention at
    ``1/sqrt(D)`` IN THE LATENT; ``W_o`` back up to the model.  Every
    sequence starts at position 0 with nothing before it: training and
    whole-sequence evaluation (no cache, no sequence sharding)."""

    d_model: int
    cca: CCASpec
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, h, mask=None):
        from chainermn_tpu.ops.ssd import publish_geometry

        z = self.cca
        f32 = jnp.float32
        Hq, Hkv, D = z.n_heads, z.n_kv_heads, z.d_head
        G, group, C = Hq + Hkv, Hq // Hkv, z.conv_dim
        B, S = h.shape[:2]
        if telemetry_active():
            publish_geometry("cca_geometry", "cca", {
                "seq": S, "d_model": self.d_model, "q_heads": Hq,
                "kv_heads": Hkv, "d_head": D, "latent_q": Hq * D,
                "latent_kv": Hkv * D, "taps0": z.time0, "taps1": z.time1,
                "rotary_dim": z.rotary_dim}, form="xla_shifts")
        scale = 1.0 / np.sqrt(D)
        if self.attention_fn is not None and getattr(
                self.attention_fn, "scale", None) not in (None, scale):
            raise ValueError(
                f"the cca mixer attends at 1/sqrt(d_head), the "
                f"attention_fn was built with scale "
                f"{self.attention_fn.scale}")
        def dense(n, name):
            layer = nn.Dense(n, dtype=self.dtype, use_bias=False, name=name)

            def project(x):
                with named_scope("mixer-proj"):
                    return layer(x)

            return project

        normal = nn.initializers.lecun_normal()
        with named_scope("cca-mixer"):
            q0 = dense(Hq * D, "query")(h)
            k0 = dense(Hkv * D, "key")(h)
            with named_scope("cca-conv"):
                v = jnp.concatenate([
                    dense(Hkv * D // 2, "value_now")(h),
                    dense(Hkv * D // 2, "value_before")(shift_tokens(h, 1)),
                ], axis=-1).reshape(B, S, Hkv, D)
                w0 = self.param("conv0_kernel", normal, (z.time0, C), f32)
                w1 = self.param(
                    "conv1_kernel", nn.initializers.lecun_normal(
                        in_axis=(0, 2), out_axis=3, batch_axis=(1,)),
                    (z.time1, G, D, D), f32)
                u = jnp.concatenate([q0, k0], axis=-1).astype(f32)
                c = self.param("conv0_bias", nn.initializers.zeros, (C,),
                               f32) + sum(   # the last tap: this token
                    shift_tokens(u, z.time0 - 1 - j) * w0[j]
                    for j in range(z.time0))
                c = c.astype(self.dtype).reshape(B, S, G, D)
                c = self.param("conv1_bias", nn.initializers.zeros, (C,),
                               f32).reshape(G, D) + sum(
                    jnp.einsum("bsgd,gde->bsge",
                               shift_tokens(c, z.time1 - 1 - j),
                               w1[j].astype(self.dtype)).astype(f32)
                    for j in range(z.time1))
                qh = q0.astype(f32).reshape(B, S, Hkv, group, D)
                kh = k0.astype(f32).reshape(B, S, Hkv, 1, D)
                q = c[:, :, :Hq] + (0.5 * (qh + kh)).reshape(B, S, Hq, D)
                k = c[:, :, Hq:] + 0.5 * (
                    jnp.mean(qh, axis=3) + kh[:, :, :, 0])
            with named_scope("cca-rope"):
                temp = self.param("temp", nn.initializers.ones, (Hkv,), f32)

                def unit(x):
                    return x * (np.sqrt(D) * jax.lax.rsqrt(jnp.sum(
                        jnp.square(x), axis=-1, keepdims=True) + 1e-12))

                q, k = unit(q), unit(k) * temp[:, None]
                if z.rotary_dim:
                    pos = jnp.arange(S)
                    q = rotate_partial(q, pos, z.rotary_dim, z.rope_theta)
                    k = rotate_partial(k, pos, z.rotary_dim, z.rope_theta)
                q, k = q.astype(self.dtype), k.astype(self.dtype)
            if self.attention_fn is not None:
                out = self.attention_fn(q, k, v, mask)
            else:
                kk = jnp.repeat(k, group, axis=2)
                vv = jnp.repeat(v, group, axis=2)
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
                if mask is not None:
                    logits = jnp.where(mask, logits,
                                       jnp.finfo(jnp.float32).min)
                weights = nn.softmax(logits.astype(f32)).astype(self.dtype)
                out = jnp.einsum("bhqk,bkhd->bqhd", weights, vv)
            return dense(self.d_model, "out")(out.reshape(B, S, Hq * D))


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer (arXiv:2405.21060), as the ``granitemoehybrid``
    family lays it out: ``[z | xBC | dt] = in_proj(h)``; a causal depthwise
    convolution and SiLU over ``xBC = [x | B | C]``; ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; the state-space scan
    (:func:`chainermn_tpu.ops.ssd.ssd_scan`); the gated norm
    ``RMSNorm(y * silu(z))`` over all channels, or over each of
    ``ssm.norm_groups`` equal parts of them; ``out_proj``.  Every
    sequence starts from a zero state (no document boundaries inside a
    row, no recurrent cache: training and whole-sequence evaluation)."""

    d_model: int
    ssm: SSMSpec
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        from chainermn_tpu.ops.ssd import causal_conv_silu, ssd_scan

        z = self.ssm
        f32 = jnp.float32
        with named_scope("mamba-mixer"):
            with named_scope("mixer-proj"):
                proj = nn.Dense(z.d_inner + z.conv_dim + z.n_heads,
                                dtype=self.dtype, use_bias=False,
                                name="in_proj")(h)
            gate, xbc, dt = jnp.split(
                proj, [z.d_inner, z.d_inner + z.conv_dim], axis=-1)
            xbc = causal_conv_silu(
                xbc,
                self.param("conv_kernel", nn.initializers.lecun_normal(),
                           (z.d_conv, z.conv_dim), f32),
                self.param("conv_bias", nn.initializers.zeros,
                           (z.conv_dim,), f32))
            x, B, C = jnp.split(
                xbc, [z.d_inner, z.d_inner + z.n_groups * z.d_state],
                axis=-1)
            heads = (z.n_heads,)
            with named_scope("mixer-gate"):
                dt = jax.nn.softplus(
                    dt.astype(f32)
                    + self.param("dt_bias", _dt_bias_init, heads))
            lead = x.shape[:2]
            x = x.reshape(lead + (z.n_heads, z.d_head))
            with named_scope("mixer-gate"):
                decay = -jnp.exp(self.param("A_log", _a_log_init, heads))
            y = ssd_scan(
                x, dt, decay,
                B.reshape(lead + (z.n_groups, z.d_state)),
                C.reshape(lead + (z.n_groups, z.d_state)),
                self.param("D", nn.initializers.ones, heads, f32),
                chunk=z.chunk)
            y = y.reshape(lead + (z.d_inner,))
            norm = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              name="norm") if z.norm_groups == 1 else (
                GroupedRMSNorm(z.norm_groups, self.norm_eps, self.dtype,
                               name="norm"))
            # The gated norm is a module called ``norm``: its own name on
            # the path would read as the layers' pre-norm (``MODEL_PARTS``),
            # so flax's per-module scope is off around this one call.
            with named_scope("mixer-gate"), nn.override_named_call(False):
                y = norm(y.astype(f32) * nn.silu(gate.astype(f32)))
            with named_scope("mixer-proj"):
                return nn.Dense(self.d_model, dtype=self.dtype,
                                use_bias=False, name="out_proj")(y)


class GatedDeltaNetMixer(nn.Module):
    """The Gated DeltaNet mixer (arXiv:2412.06464) as the ``qwen3_next``
    family lays it out (a :class:`GDNSpec` row): ``[q | k | v | z] =
    in_proj_qkvz(h)``, ``[b | a] = in_proj_ba(h)``; a causal depthwise
    convolution and SiLU over ``[q | k | v]``, no bias
    (:func:`chainermn_tpu.ops.ssd.causal_conv_silu`, the Mamba-2 mixers'
    kernels); per head ``q <- q / |q| / sqrt(d_k)``, ``k <- k / |k|``
    with ``|x| = sqrt(sum x^2 + 1e-6)``; ``beta = sigmoid(b)``, ``g =
    -exp(A_log) softplus(a + dt_bias)``, float32, one number a value head
    a token; the chunked gated delta rule
    (:func:`chainermn_tpu.ops.gated_delta.gated_delta_rule`); per head
    ``RMSNorm(o) * silu(z)`` with one plain scale a channel of a head;
    ``out_proj``.  Every sequence starts from a zero state (no document
    boundaries inside a row, no recurrent cache: training and
    whole-sequence evaluation)."""

    d_model: int
    gdn: GDNSpec
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        from chainermn_tpu.ops.gated_delta import gated_delta_rule
        from chainermn_tpu.ops.ssd import causal_conv_silu

        z = self.gdn
        f32 = jnp.float32
        lead = h.shape[:2]
        with named_scope("gdn-mixer"):
            with named_scope("mixer-proj"):
                proj = nn.Dense(z.conv_dim + z.value_dim, dtype=self.dtype,
                                use_bias=False, name="in_proj_qkvz")(h)
                ba = nn.Dense(2 * z.n_v_heads, dtype=self.dtype,
                              use_bias=False, name="in_proj_ba")(h)
            qkv, gate = jnp.split(proj, [z.conv_dim], axis=-1)
            qkv = causal_conv_silu(
                qkv, self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (z.d_conv, z.conv_dim), f32))
            q, k, v = jnp.split(qkv, [z.key_dim, 2 * z.key_dim], axis=-1)
            heads = (z.n_v_heads,)
            with named_scope("mixer-gate"):
                def unit(x):
                    x = x.astype(f32).reshape(lead + (z.n_k_heads, z.d_k))
                    return x * jax.lax.rsqrt(jnp.sum(
                        jnp.square(x), axis=-1, keepdims=True) + 1e-6)

                q = (unit(q) * (1.0 / np.sqrt(z.d_k))).astype(self.dtype)
                k = unit(k).astype(self.dtype)
                b, a = jnp.split(ba.astype(f32), 2, axis=-1)
                beta = jax.nn.sigmoid(b)
                g = -jnp.exp(self.param("A_log", _a_log_init, heads)) * (
                    jax.nn.softplus(
                        a + self.param("dt_bias", _dt_bias_init, heads)))
            o = gated_delta_rule(
                q, k, v.reshape(lead + (z.n_v_heads, z.d_v)), g, beta,
                chunk=z.chunk)
            with named_scope("mixer-gate"):
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                    + self.norm_eps)
                scale = self.param("norm_scale", nn.initializers.ones,
                                   (z.d_v,), f32)
                y = o * scale * nn.silu(gate.astype(f32).reshape(o.shape))
                y = y.astype(self.dtype).reshape(lead + (z.value_dim,))
            with named_scope("mixer-proj"):
                return nn.Dense(self.d_model, dtype=self.dtype,
                                use_bias=False, name="out_proj")(y)


class KDAMixer(nn.Module):
    """The Kimi-Delta-Attention mixer (Kimi Linear, arXiv:2510.26692) as
    the ``bailing_hybrid`` family lays it out (a :class:`KDASpec` row):
    ``[q | k | v | f] = in_proj_qkvf(h)``, ``[b | a] = in_proj_bg(h)``; a
    causal depthwise convolution and SiLU over ``[q | k | v]``, no bias
    (:func:`chainermn_tpu.ops.ssd.causal_conv_silu`, the Mamba-2 mixers'
    kernels); ``beta = sigmoid(b)`` a head; the chunked rule
    (:func:`chainermn_tpu.ops.kda.kda_rule`: two Mosaic kernels whose
    grid walks the heads), whose kernels make the heads' float32 side
    themselves, in VMEM, from the convolution's ``q``, ``k`` and the
    projection's ``f`` — per head ``q <- q / |q| / sqrt(d_k)``, ``k <- k
    / |k|`` with ``|x| = sqrt(sum x^2 + 1e-6)``, and the log-decay a head
    and KEY CHANNEL ``g = lower_bound * sigmoid(exp(A_log) * (f +
    dt_bias))`` in ``(lower_bound, 0)`` with its running sums — and take
    the cotangents back through it; per head ``RMSNorm(o) * sigmoid(a)``
    with one plain scale a channel of a head and ONE gate a head;
    ``out_proj``.  Every sequence starts from a zero state.  The output
    side is float32 arithmetic whose result is ``y`` in the activations'
    type: of every head's float32 numbers none stands as an array."""

    d_model: int
    kda: KDASpec
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        from chainermn_tpu.ops.kda import kda_rule
        from chainermn_tpu.ops.ssd import causal_conv_silu

        z = self.kda
        f32 = jnp.float32
        lead, H = h.shape[:2], z.n_heads
        with named_scope("kda-mixer"):
            with named_scope("mixer-proj"):
                proj = nn.Dense(z.conv_dim + z.key_dim, dtype=self.dtype,
                                use_bias=False, name="in_proj_qkvf")(h)
                bg = nn.Dense(2 * H, dtype=self.dtype,
                              use_bias=False, name="in_proj_bg")(h)
            qkv, f = jnp.split(proj, [z.conv_dim], axis=-1)
            qkv = causal_conv_silu(
                qkv, self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (z.d_conv, z.conv_dim), f32))
            q, k, v = jnp.split(qkv, [z.key_dim, 2 * z.key_dim], axis=-1)
            b, gate = jnp.split(bg, 2, axis=-1)
            scale = self.param("norm_scale", nn.initializers.ones,
                               (z.d_v,), f32)
            a_log = self.param("A_log", _a_log_init, (H,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (z.key_dim,))
            with named_scope("mixer-gate"):
                beta = jax.nn.sigmoid(b.astype(f32))
                rate = jnp.exp(a_log)
            by_head = lead + (H, z.d_k)
            o = kda_rule(
                q.reshape(by_head), k.reshape(by_head),
                v.reshape(lead + (H, z.d_v)), f.reshape(by_head), beta,
                rate, dt_bias.reshape(H, z.d_k),
                lower_bound=z.lower_bound, chunk=z.chunk)
            with named_scope("mixer-gate"):
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                    + self.norm_eps)
                y = o * scale * jax.nn.sigmoid(gate.astype(f32))[..., None]
                y = y.astype(self.dtype).reshape(lead + (z.value_dim,))
            with named_scope("mixer-proj"):
                return nn.Dense(self.d_model, dtype=self.dtype,
                                use_bias=False, name="out_proj")(y)


def norm_leading(x, scale, eps: float, width: int):
    """RMSNorm over the first ``width`` lanes of the last axis of ``x``
    times ``scale`` (width,), the other lanes passed through, in float32
    and without cutting the head: the statistics are a masked mean."""
    x32 = x.astype(jnp.float32)
    lead = jnp.arange(x.shape[-1]) < width
    mean = jnp.sum(jnp.where(lead, jnp.square(x32), 0.0), axis=-1,
                   keepdims=True) / width
    gain = jnp.concatenate([scale.astype(jnp.float32), jnp.ones(
        (x.shape[-1] - width,), jnp.float32)])
    return jnp.where(lead, x32 * jax.lax.rsqrt(mean + eps) * gain, x32)


class MLAMixer(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) for
    training, as the ``bailing_hybrid`` family lays it out (an attention
    row with an :class:`MLASpec`): per head ``[q_nope | q_rope] =
    query(h)``; ``[c | k_rope] = kv_a(h)``, ``c <- kv_norm(c)``
    (RMSNorm over the latent), per head ``[k_nope | v] = kv_b(c)``; with
    ``qk_norm`` an RMSNorm over the nope part of every query and key head
    (``q_norm``, ``k_norm``: one scale a channel, shared by the heads);
    rotary positions 0..S-1 on ``q_rope`` and on ``k_rope``, ONE key
    vector a token that every head shares; ``k = [k_nope | k_rope]``;
    causal softmax of ``q k^T / sqrt(d_nope + d_rope)`` over values of
    ``d_v`` (the ``attention_fn``, whose kernels take a value width of
    their own, else the dense path); with ``head_gate`` times
    ``sigmoid(gate(h))``, one number a head; ``out``.  No cache and no
    absorbed form: training and whole-sequence evaluation."""

    d_model: int
    n_heads: int
    mla: MLASpec
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    qk_norm: bool = False
    head_gate: bool = False
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, h, mask=None):
        z, H = self.mla, self.n_heads
        f32 = jnp.float32
        ones = nn.initializers.ones
        with named_scope("mla-mixer"):
            with named_scope("mixer-proj"):
                q = nn.DenseGeneral((H, z.d_qk), dtype=self.dtype,
                                    use_bias=False, name="query")(h)
                kva = nn.Dense(z.kv_rank + z.d_rope, dtype=self.dtype,
                               use_bias=False, name="kv_a")(h)
                gate = None if not self.head_gate else nn.Dense(
                    H, dtype=self.dtype, use_bias=False, name="gate")(h)
            with named_scope("attn-rope"):
                latent = norm_leading(
                    kva, self.param("kv_norm", ones, (z.kv_rank,), f32),
                    self.norm_eps, z.kv_rank)
                c = latent[..., :z.kv_rank].astype(self.dtype)
                k_rope = latent[..., None, z.kv_rank:].astype(self.dtype)
            with named_scope("mixer-proj"):
                kv = nn.DenseGeneral((H, z.d_nope + z.d_v), dtype=self.dtype,
                                     use_bias=False, name="kv_b")(c)
            k_nope, v = jnp.split(kv, [z.d_nope], axis=-1)
            with named_scope("attn-rope"):
                if self.qk_norm:
                    q = norm_leading(
                        q, self.param("q_norm", ones, (z.d_nope,), f32),
                        self.norm_eps, z.d_nope).astype(self.dtype)
                    k_nope = norm_leading(
                        k_nope, self.param("k_norm", ones, (z.d_nope,), f32),
                        self.norm_eps, z.d_nope).astype(self.dtype)
                pos = jnp.arange(h.shape[1])
                q = rotate_partial(
                    q, pos, z.d_rope, z.rope_theta, None,
                    (z.d_nope, z.interleave)).astype(self.dtype)
                k_rope = rotate_partial(
                    k_rope, pos, z.d_rope, z.rope_theta, None,
                    (0, z.interleave)).astype(self.dtype)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_rope, k_nope.shape[:3] + (z.d_rope,))], axis=-1)
            if self.attention_fn is not None:
                if getattr(self.attention_fn, "scale", None) is not None:
                    raise ValueError(
                        "an mla row scales its scores by 1/sqrt(d_nope + "
                        "d_rope): build the attention_fn without a scale")
                out = self.attention_fn(q, k, v, mask)
            else:
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (
                    1.0 / np.sqrt(z.d_qk))
                if mask is not None:
                    logits = jnp.where(mask, logits,
                                       jnp.finfo(jnp.float32).min)
                weights = nn.softmax(logits.astype(f32)).astype(self.dtype)
                out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
            if gate is not None:
                with named_scope("mixer-gate"):
                    out = out * jax.nn.sigmoid(
                        gate.astype(f32))[..., None].astype(out.dtype)
            with named_scope("mixer-proj"):
                return nn.DenseGeneral(
                    self.d_model, axis=(-2, -1), dtype=self.dtype,
                    name="out", use_bias=False)(out)


class Block(nn.Module):
    """One layer, built from its row of the block table: ``x + rm *
    mixer(norm(x))`` where the row has a mixer, then ``x + rm *
    ffn(norm(x))`` where it has an FFN.  A row whose experts' router
    keeps a state (``ExpertsSpec.d_router``) is called with the state the
    layer before handed on, ``(x, mask, router_state)``, and returns
    ``(x, router_state)``: a second value from layer to layer beside the
    residual stream, an argument and a result, never hidden state (any
    row called with a state returns the pair, its own rows' or not)."""

    d_model: int
    row: LayerSpec
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    decode: bool = False
    cache_len: int = 0
    paged: Optional[str] = None
    page_count: int = 0
    page_size: int = 0
    kv_dtype: Optional[str] = None
    sp_axis: Optional[str] = None
    block_diffusion: Optional[int] = None   # the table's

    @nn.compact
    def __call__(self, x, mask=None, router_state=None, *,
                 block_tables=None, seq_lens=None, positions=None):
        row = self.row

        def normed(x):
            with named_scope("norm"):
                return NORM_CLASSES[row.norm](
                    epsilon=row.norm_eps, dtype=self.dtype)(x)

        def residual(x, branch):
            with named_scope("residual"):
                if row.residual_multiplier != 1.0:
                    branch = branch * jnp.asarray(
                        row.residual_multiplier, branch.dtype)
                return x + branch

        def no_cache(what):
            if self.decode or self.paged is not None:
                raise ValueError(
                    f"{what}: incremental decoding and the paged KV cache "
                    f"are built for plain attention layers only")

        if row.mixer == "attention" and row.mla is not None:
            no_cache("an mla row keeps no latent cache and has no absorbed "
                     "decode")
            h = normed(x)
            with named_scope("attn-mixer"):
                branch = MLAMixer(
                    self.d_model, row.n_heads, row.mla, self.dtype,
                    self.attention_fn, qk_norm=row.qk_norm,
                    head_gate=row.head_gate, norm_eps=row.norm_eps)(h, mask)
            x = residual(x, branch)
        elif row.mixer == "kda":
            no_cache("a kda layer keeps no recurrent state between calls "
                     "(its matrix state a head and its convolution's "
                     "window)")
            x = residual(x, KDAMixer(
                self.d_model, row.kda, row.norm_eps, self.dtype)(normed(x)))
        elif row.mixer == "attention":
            h = normed(x)
            with named_scope(
                    "attn-blockdiff" if self.block_diffusion is not None
                    else "attn-mixer" if row.window is None
                    else "attn-window"):
                branch = MultiHeadAttention(
                    self.d_model, row.n_heads, self.dtype, self.attention_fn,
                    decode=self.decode, cache_len=self.cache_len,
                    n_kv_heads=row.n_kv_heads, paged=self.paged,
                    page_count=self.page_count, page_size=self.page_size,
                    kv_dtype=self.kv_dtype, sp_axis=self.sp_axis,
                    scale=row.attn_scale, d_head=row.d_head,
                    rotary_dim=row.rotary_dim, rope_theta=row.rope_theta,
                    yarn=row.yarn, window=row.window,
                    qk_norm=row.norm if row.qk_norm else None,
                    norm_eps=row.norm_eps, out_gate=row.out_gate,
                    head_gate=row.head_gate,
                    block_diffusion=self.block_diffusion,
                )(h, h, mask, block_tables=block_tables, seq_lens=seq_lens,
                  positions=positions)
            x = residual(x, branch)
        elif row.mixer == "mamba2":
            if self.decode or self.paged is not None:
                raise ValueError(
                    "a mamba2 layer keeps no recurrent state between "
                    "calls: incremental decoding and the paged KV cache "
                    "are built for attention layers only"
                )
            x = residual(x, Mamba2Mixer(self.d_model, row.ssm, row.norm_eps,
                                        self.dtype)(normed(x)))
        elif row.mixer == "cca":
            if self.decode or self.paged is not None:
                raise ValueError(
                    "a cca layer keeps no cache between calls (its "
                    "convolutions and its value read the token before): "
                    "incremental decoding and the paged KV cache are "
                    "built for attention layers only"
                )
            x = residual(x, CCAMixer(self.d_model, row.cca, self.dtype,
                                     self.attention_fn)(normed(x), mask))
        elif row.mixer == "gdn":
            if self.decode or self.paged is not None:
                raise ValueError(
                    "a gdn layer keeps no recurrent state between calls "
                    "(its matrix state a head and its convolution's "
                    "window): incremental decoding and the paged KV cache "
                    "are built for attention layers only"
                )
            x = residual(x, GatedDeltaNetMixer(
                self.d_model, row.gdn, row.norm_eps, self.dtype)(normed(x)))

        def handed_on(x):
            return x if router_state is None else (x, router_state)

        if row.ffn == "experts":
            experts = ExpertLayer(self.d_model, row.experts, self.dtype,
                                  row.norm_eps)
            if not row.experts.d_router:
                return handed_on(residual(x, experts(normed(x))))
            out, router_state = experts(normed(x), router_state)
            return residual(x, out), router_state
        if row.ffn == "none":
            return handed_on(x)
        ffn = {"gelu": FeedForward, "swiglu": GatedFeedForward,
               "relu2": Relu2FeedForward}[row.ffn]
        h = normed(x)
        with named_scope("ffn"):
            branch = ffn(self.d_model, row.d_ff, self.dtype)(h)
        return handed_on(residual(x, branch))


def EncoderLayer(d_model: int, n_heads: int, d_ff: int,
                 dtype: Any = jnp.bfloat16,
                 attention_fn: Optional[Callable] = None,
                 n_kv_heads: Optional[int] = None, **kwargs) -> Block:
    """The GPT-2-style row of the table as a layer (pre-LayerNorm
    attention + two-matrix GELU FFN): what ViT and the encoder-decoder
    build their stacks from."""
    return Block(d_model, LayerSpec(n_heads=n_heads, n_kv_heads=n_kv_heads,
                                    d_ff=d_ff), dtype, attention_fn, **kwargs)


class DecoderLayer(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, y, enc, self_mask=None, cross_mask=None):
        h = nn.LayerNorm(dtype=self.dtype)(y)
        y = y + MultiHeadAttention(self.d_model, self.n_heads, self.dtype, name="self_attn")(
            h, h, self_mask
        )
        h = nn.LayerNorm(dtype=self.dtype)(y)
        y = y + MultiHeadAttention(self.d_model, self.n_heads, self.dtype, name="cross_attn")(
            h, enc, cross_mask
        )
        h = nn.LayerNorm(dtype=self.dtype)(y)
        return y + FeedForward(self.d_model, self.d_ff, self.dtype)(h)


def causal_mask(length: int):
    return jnp.tril(jnp.ones((1, 1, length, length), bool))


class Transformer(nn.Module):
    """Encoder-decoder transformer (WMT-shape, BASELINE config #4)."""

    vocab: int
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048
    n_enc_layers: int = 6
    n_dec_layers: int = 6
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, src, tgt):
        """``src``: (B, S) int tokens; ``tgt``: (B, T) int tokens (shifted
        right by the caller). Returns (B, T, vocab) fp32 logits."""
        embed = nn.Embed(self.vocab, self.d_model, dtype=self.dtype, name="embed")
        pe = jnp.asarray(sinusoidal_positions(self.max_len, self.d_model))

        x = embed(src) + pe[None, : src.shape[1]].astype(self.dtype)
        src_mask = (src != 0)[:, None, None, :]
        for i in range(self.n_enc_layers):
            x = EncoderLayer(
                self.d_model, self.n_heads, self.d_ff, self.dtype,
                self.attention_fn, name=f"enc_{i}",
            )(x, src_mask)
        x = nn.LayerNorm(dtype=self.dtype, name="enc_norm")(x)

        y = embed(tgt) + pe[None, : tgt.shape[1]].astype(self.dtype)
        self_mask = causal_mask(tgt.shape[1]) & (tgt != 0)[:, None, None, :]
        for i in range(self.n_dec_layers):
            y = DecoderLayer(
                self.d_model, self.n_heads, self.d_ff, self.dtype, name=f"dec_{i}"
            )(y, x, self_mask, src_mask)
        y = nn.LayerNorm(dtype=self.dtype, name="dec_norm")(y)
        logits = embed.attend(y.astype(jnp.float32))
        return logits


class TransformerLM(nn.Module):
    """Decoder-only LM — the long-context workhorse for the
    sequence-parallel (ring attention / Ulysses) layers.

    Its layers come from a per-layer block table
    (:mod:`chainermn_tpu.models.block_table`).  ``table=None`` is the
    GPT-2-style row in every layer, written from ``n_layers``,
    ``n_heads``, ``d_ff`` and ``n_kv_heads``; a given ``table`` replaces
    those four (``block_table.table_from_config`` writes one from a
    published config)."""

    vocab: int
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048
    n_layers: int = 6
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    decode: bool = False        # KV-cache incremental decoding (generate())
    remat: bool = False         # rematerialize each layer in backward
    n_kv_heads: Optional[int] = None  # GQA/MQA (divides n_heads)
    paged: Optional[str] = None  # paged KV cache (serving engine):
                                 # "prefill" | "decode" — see
                                 # MultiHeadAttention.paged
    page_count: int = 0
    page_size: int = 0
    kv_dtype: Optional[str] = None  # quantized pages ("int8") — see
                                    # MultiHeadAttention.kv_dtype
    sp_axis: Optional[str] = None   # sequence-parallel chunk prefill —
                                    # see MultiHeadAttention.sp_axis
    table: Optional[BlockTable] = None  # the per-layer block table

    @property
    def block_table(self) -> BlockTable:
        return self.table or gpt2_table(
            self.n_layers, self.n_heads, self.d_ff, self.n_kv_heads)

    @nn.compact
    def __call__(self, tokens, position_offset=None, return_hidden=False,
                 inputs_embeds=None, block_tables=None, seq_lens=None,
                 router_state=None):
        """``position_offset``: global position of this shard's first token —
        for a table with rotary positions whose rows are all plain
        attention rows, the positions those rows turn by (a scalar, or an
        ``(S,)`` array: block-diffusion training runs ``0 .. L-1`` twice);
        for sinusoidal positions,
        pass ``axis_index * S_local`` when the sequence dimension is sharded
        (sequence parallelism); requires a sequence-aware ``attention_fn``
        (ring/Ulysses), since the dense path's causal mask is local.
        Alternatively a ``(S_local,)`` int array of explicit global
        positions, for non-contiguous shard layouts (zigzag ring) — or a
        ``(B, S)`` int array of PER-SEQUENCE positions, which is how the
        serving engine's paged decode step places each sequence's next
        token at its own context length.

        ``block_tables``/``seq_lens``: the paged-KV-cache routing inputs,
        required (and only meaningful) when ``paged`` is set — see
        :class:`MultiHeadAttention` and docs/serving.md.

        ``return_hidden=True`` returns the final-norm hidden states
        ``(B, S, d_model)`` instead of logits — the input for
        :func:`chainermn_tpu.ops.fused_cross_entropy`, which never
        materializes the ``(B*S, vocab)`` logits the default
        ``embed.attend`` path does.  A table's ``logits_scaling`` is
        divided into the hidden states then, so that ``hidden @ E^T`` are
        the logits on both paths.  A table with an untied head
        (``tied_head=False``) keeps its matrix as the parameter
        ``lm_head``, (vocab, d_model) like the table: hand that one to
        the loss.

        ``inputs_embeds``: optional pre-computed ``(B, S, d_model)`` token
        embeddings replacing the internal table lookup (positions are
        still added here) — the entry point for a VOCAB-SHARDED embedding
        (``parallel.sharding.vocab_parallel_embed``), whose table lives
        outside this module's replicated parameters.  Combine with
        ``return_hidden=True`` so the (equally vocab-sharded) LM head
        runs outside too.

        ``router_state``: for a table whose routers keep a state
        (``BlockTable.d_router_state`` wide; ``zaya``), what the pipeline
        stage before this one handed over, ``(B, S, d_router_state)``
        float32; None starts from zeros (the first stage).  The layers
        pass it on beside ``x``; what the last layer hands on is ``sow``n
        as ``intermediates/router_state`` for the stage after.

        ``remat=True`` wraps every layer in ``jax.checkpoint`` under the
        one policy of :func:`remat_policy`: backward recomputes a layer's
        activations from its input but for the few it keeps by name
        (:func:`remat_names`) — the standard long-context memory/FLOP
        trade."""
        import jax.lax as _lax

        table = self.block_table
        S = tokens.shape[1]
        positions = None
        if table.positions == "rotary" and position_offset is not None:
            if any(row.mixer not in ("attention", "none")
                   or row.mla is not None for row in table.layers):
                raise ValueError(
                    "rotary positions are built from 0 inside the mixers "
                    "that read the token before (the cca mixer's "
                    "convolutions and value; the gdn, kda and mamba2 "
                    "mixers' convolutions and state) and inside a "
                    "latent-attention row: a sharded or offset sequence "
                    "is not built for them")
            # Plain attention rows turn by the positions they are handed:
            # an (S,) array as it is, a scalar as the first token's.
            positions = (position_offset
                         if getattr(position_offset, "ndim", 0) == 1
                         else position_offset + jnp.arange(S))
            if positions.shape != (S,):
                raise ValueError(
                    f"rotary positions are one (S,) array for every row "
                    f"of the batch, got {positions.shape}")
        if table.block_diffusion is not None:
            if positions is None or S % 2:
                raise ValueError(
                    "a table trained by block diffusion runs 2 L rows, a "
                    "document's clean copy then its noised one, at the "
                    "positions it is handed (0 .. L-1 twice: "
                    "models.block_diffusion.block_diffusion_loss builds "
                    "them)")
            if self.decode or self.paged is not None:
                raise ValueError(
                    "generation by iterative unmasking inside a block is "
                    "not built: the serving step yields a token, not a "
                    "block")
        if inputs_embeds is not None and not return_hidden:
            raise ValueError(
                "inputs_embeds requires return_hidden=True: the tied "
                "embed.attend head has no table when the lookup is "
                "external (vocab-sharded) — compute the head with "
                "the same external table"
            )
        with named_scope("embed"):
            pos = None
            if table.positions == "sinusoidal":
                pe = jnp.asarray(
                    sinusoidal_positions(self.max_len, self.d_model))
                if position_offset is None:
                    pos = pe[:S]
                elif getattr(position_offset, "ndim", 0):
                    # (S,) explicit per-token or (B, S) per-sequence
                    # positions
                    pos = pe[position_offset]
                else:
                    pos = _lax.dynamic_slice_in_dim(
                        pe, position_offset, S, axis=0)
            if inputs_embeds is None:
                embed = nn.Embed(
                    self.vocab, self.d_model, dtype=self.dtype, name="embed"
                )
                x = embed(tokens)
            else:
                embed = None
                x = inputs_embeds.astype(self.dtype)
            if table.embedding_multiplier != 1.0:
                x = x * jnp.asarray(table.embedding_multiplier, x.dtype)
            if pos is not None:
                # (B, S, d) is already per-batch
                x = x + (pos if pos.ndim == 3 else pos[None]).astype(
                    self.dtype)
        # Pluggable attention (flash/ring/ulysses) imposes its own
        # causality and ignores the mask argument — skip materializing
        # the (S, S) mask, which at long context is the largest host
        # constant in the program (S=16k: 256 MiB as bool).
        mask = None if self.attention_fn is not None else causal_mask(S)
        layer_cls = Block
        if self.remat:
            layer_cls = nn.remat(Block, static_argnums=(),
                                 policy=remat_policy())
            if telemetry_active():
                from chainermn_tpu.ops.ssd import publish_geometry

                publish_geometry("remat_geometry", "remat", remat_kept(
                    table, self.d_model, x.shape[0] * S,
                    jnp.dtype(self.dtype).itemsize,
                    flash=self.attention_fn is not None, seq=S))
        if table.d_router_state and router_state is None:
            router_state = jnp.zeros(
                x.shape[:2] + (table.d_router_state,), jnp.float32)
        if router_state is not None and not table.d_router_state:
            raise ValueError("router_state given to a table whose routers "
                             "keep none")
        for i, row in enumerate(table.layers):
            layer = layer_cls(
                self.d_model, row, self.dtype, self.attention_fn,
                name=f"layer_{i}", decode=self.decode,
                cache_len=self.max_len if self.decode else 0,
                paged=self.paged, page_count=self.page_count,
                page_size=self.page_size, kv_dtype=self.kv_dtype,
                sp_axis=self.sp_axis, block_diffusion=table.block_diffusion,
            )
            # (a row is handed positions only where the table has them to
            # hand: every other call is the one it was)
            placed = {} if positions is None else {"positions": positions}
            if router_state is None:
                x = layer(x, mask, block_tables=block_tables,
                          seq_lens=seq_lens, **placed)
            else:
                x, router_state = layer(x, mask, router_state, **placed)
        if router_state is not None:
            self.sow("intermediates", "router_state", router_state)
        with named_scope("norm"):
            x = NORM_CLASSES[table.final_norm](
                epsilon=table.norm_eps, dtype=self.dtype,
                name="final_norm")(x)
        head = None if table.tied_head else self.param(
            "lm_head", nn.initializers.normal(0.02),
            (self.vocab, self.d_model), jnp.float32)
        if return_hidden:
            if table.logits_scaling != 1.0:
                x = x / jnp.asarray(table.logits_scaling, x.dtype)
            return x
        logits = (embed.attend(x.astype(jnp.float32)) if head is None
                  else x.astype(jnp.float32) @ head.T)
        if table.logits_scaling != 1.0:
            logits = logits / table.logits_scaling
        return logits


def remat_names():
    """The names (``jax.ad_checkpoint.checkpoint_name``) a rematerialised
    layer keeps beside its input, whatever its row: the expert layers'
    routing and grouped products (``moe_dropless.REMAT_SAVES``), the
    flash kernel's output and row statistics
    (``flash_attention.FLASH_RESIDUALS``: one (tokens, heads x d_head)
    activation and 4 bytes a token and head a flash layer, for a forward
    kernel call a layer-step not run twice) and the gated delta rule's
    output and tile states (``gated_delta.GDN_RESIDUALS``: one (tokens,
    value heads x d_v) activation and a float32 state a head and tile of
    512 tokens, 201 MB a layer of the ``qwen3_next`` cell for a forward
    kernel call of 6.3 ms, +0.054 GB on the compiled step and 5% of the
    measured one: PERF.md section 6, PR 37) and, the same for a
    Kimi-Delta-Attention row, ``kda.KDA_RESIDUALS`` (201 MB a layer of
    the ``ling3flash`` cell for a forward kernel call that makes the
    heads' norms, decay and running sums too: PERF.md section 6, PR 44
    and PR 52; what the row recomputes is its projections and its
    convolution, whose ``q``, ``k``, ``v``, ``f`` the backward kernel
    reads in the activations' type — no float32 gate side is run again,
    nothing of it was ever kept).  A name no layer
    of a table emits is harmless.  NOT kept, each 20 to 100 times dearer a byte than
    flash's 67 MB for 9.7 ms a step (granite, PERF.md section 6, PR 35):
    the scan's ``y`` and block starts (268 MB a layer for 0.7 ms), the
    convolution's output (142 MB for ~1 ms), the dense FFN's gate and up
    (512 MB a layer)."""
    from chainermn_tpu.ops.flash_attention import FLASH_RESIDUALS
    from chainermn_tpu.ops.gated_delta import GDN_RESIDUALS
    from chainermn_tpu.ops.kda import KDA_RESIDUALS
    from chainermn_tpu.parallel.moe_dropless import REMAT_SAVES

    return (*REMAT_SAVES, FLASH_RESIDUALS, GDN_RESIDUALS, KDA_RESIDUALS)


def remat_policy():
    """The one ``jax.checkpoint`` policy of ``TransformerLM(remat=True)``:
    save :func:`remat_names`, recompute everything else."""
    return jax.checkpoint_policies.save_only_these_names(*remat_names())


def remat_kept(table: BlockTable, d_model: int, tokens: int, itemsize: int,
               flash: bool = True, seq: Optional[int] = None) -> dict:
    """What the policy keeps of ``table``'s layers, ``d_model`` wide, over
    a step of ``tokens`` tokens in rows of ``seq`` (one row if not
    given), from shapes: layers wrapped, flash and expert layers among
    them, and ``<name>_bytes`` kept a step for every name of
    :func:`remat_names` (activations ``itemsize`` bytes an element).
    ``flash``: whether the attention rows reach the flash kernels (a
    model without an ``attention_fn`` runs the dense path, which names
    nothing).  A row with a ``window`` keeps what a full row keeps: the
    kernel's ``o`` and ``lse`` are a token's, whatever it attended."""
    from chainermn_tpu.ops.gated_delta import gdn_tiles
    from chainermn_tpu.ops.grouped_matmul import TILE_ROWS
    from chainermn_tpu.ops.kda import kda_tiles
    from chainermn_tpu.parallel import moe_dropless as moe

    choice, products, flash_names, gdn_names, kda_names = remat_names()
    seq = seq or tokens
    dtype = jnp.float32 if itemsize == 4 else jnp.bfloat16
    kept = {"layers": len(table.layers), "flash_layers": 0,
            "expert_layers": 0, f"{choice}_bytes": 0,
            f"{products}_bytes": 0, f"{flash_names}_bytes": 0,
            f"{gdn_names}_bytes": 0, f"{kda_names}_bytes": 0}

    def delta_rule_bytes(z, tile, n_heads):
        """A delta rule's ``o`` and the float32 state each tile of
        ``tile`` tokens started from, ``n_heads`` value heads (a KDA
        row's kernels take ``q``, ``k``, ``f`` as its convolution and
        projection hand them over and make the float32 side in VMEM:
        the names keep nothing of it, and the row recomputes none)."""
        chunk = min(z.chunk, seq)
        tiles = tokens // seq * (-(-seq // chunk) * chunk // tile)
        return n_heads * z.d_v * (tokens * itemsize + tiles * z.d_k * 4)

    for row in table.layers:
        heads = {"attention": row, "cca": row.cca}.get(row.mixer)
        if flash and heads is not None:
            d_head = row.mla.d_v if row.mla is not None else (
                heads.d_head or d_model // heads.n_heads)
            kept["flash_layers"] += 1
            kept[f"{flash_names}_bytes"] += tokens * heads.n_heads * (
                d_head * itemsize + 4)
        if row.mixer == "gdn":
            z = row.gdn
            tile, _, _ = gdn_tiles(
                seq, min(z.chunk, seq), z.n_k_heads, z.n_v_heads, z.d_k,
                z.d_v, dtype)
            kept[f"{gdn_names}_bytes"] += delta_rule_bytes(
                z, tile, z.n_v_heads)
        if row.mixer == "kda":
            z = row.kda
            tile, _, _ = kda_tiles(
                seq, min(z.chunk, seq), z.n_heads, z.d_k, z.d_v, dtype)
            kept[f"{kda_names}_bytes"] += delta_rule_bytes(
                z, tile, z.n_heads)
        if row.ffn == "experts":
            z = row.experts
            count = z.experts_held[1]
            rows = TILE_ROWS * moe.buffer_tiles(moe.rows_bound(
                tokens * z.top_k, count, z.n_experts), count)
            kept["expert_layers"] += 1
            kept[f"{choice}_bytes"] += tokens * z.top_k * 4
            kept[f"{products}_bytes"] += rows * itemsize * (
                d_model + z.d_expert * (1 if z.expert == "relu2" else 2))
    return kept


def generate(
    lm: "TransformerLM",
    params,
    prompt,
    max_new_tokens: int,
    rng=None,
    temperature: float = 0.0,
):
    """Autoregressive generation with a KV cache — O(T·max_len) attention
    instead of the O(T²·max_len) of re-running the prefix per token.

    ``lm``: the TransformerLM the ``params`` were trained with (any
    ``decode`` value — a decode twin is constructed here).
    ``prompt``: (B, T) int32.  Greedy at ``temperature=0`` (default),
    otherwise softmax sampling with ``rng``.
    Returns (B, T + max_new_tokens) — prompt with the continuation.
    """
    import jax
    from jax import lax

    B, T = prompt.shape
    total = T + max_new_tokens
    if total > lm.max_len:
        raise ValueError(
            f"prompt + new tokens ({total}) exceed max_len {lm.max_len}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 requires rng")

    dec = TransformerLM(
        vocab=lm.vocab, d_model=lm.d_model, n_heads=lm.n_heads,
        d_ff=lm.d_ff, n_layers=lm.n_layers, max_len=lm.max_len,
        dtype=lm.dtype, decode=True, table=lm.table,
    )
    # eval_shape: cache geometry without allocating (and then discarding)
    # a second full parameter set; zeros ARE the empty cache (index 0).
    cache_shapes = jax.eval_shape(
        lambda: dec.init(
            jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32),
            position_offset=0,
        )["cache"]
    )
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes)

    pad = jnp.zeros((B, max_new_tokens), prompt.dtype)
    prompt_padded = jnp.concatenate([prompt, pad], axis=1)

    def step(carry, t):
        cache, prev = carry
        # Feed the prompt while it lasts, then the previous sample.
        tok = jnp.where(t < T, prompt_padded[:, t], prev)
        logits, upd = dec.apply(
            {"params": params["params"] if "params" in params else params,
             "cache": cache},
            tok[:, None], position_offset=t, mutable=["cache"],
        )
        logits = logits[:, 0]                       # (B, vocab)
        if temperature > 0.0:
            key = jax.random.fold_in(rng, t)
            nxt = jax.random.categorical(key, logits / temperature, axis=-1)
        else:
            nxt = logits.argmax(-1)
        return (upd["cache"], nxt.astype(prompt.dtype)), nxt.astype(prompt.dtype)

    (_, _), ys = lax.scan(
        step, (cache, jnp.zeros((B,), prompt.dtype)), jnp.arange(total - 1)
    )
    # ys[t] is the model's prediction AFTER consuming token t; the
    # continuation is ys[T-1 : T-1+max_new_tokens].
    gen = ys[T - 1 :].T                              # (B, max_new_tokens)
    return jnp.concatenate([prompt, gen], axis=1)
