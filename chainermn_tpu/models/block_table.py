"""The per-layer block table of :class:`~chainermn_tpu.models.transformer.
TransformerLM`, and the functions that write one.

A decoder is a list of layers, each one or two residual branches: ``x +
rm * mixer(norm(x))``, then ``x + rm * ffn(norm(x))``; a row whose
``mixer`` or ``ffn`` is ``"none"`` has the other branch only.  What
differs between architectures is *which* mixer, norm and FFN each layer
has and a handful of scalars; a :class:`BlockTable` states exactly that,
one :class:`LayerSpec` a layer, and the model builds its layers from it.
The GPT-2-style block the repo started with is one row
(:func:`gpt2_table`); :func:`table_from_config` turns a published
``config.json`` into its table, by ``model_type``: ``granitemoehybrid``
(Mamba-2 mixers with an attention layer among every few, RMSNorm, SwiGLU,
no positions, scalar multipliers, a tied head), ``nemotron_h``
(single-branch layers — a Mamba-2 mixer, a GQA layer or a sparse-expert
FFN each — RMSNorm, squared ReLU, no positions, an untied head), ``zaya``
(every layer a compressed-convolutional-attention mixer with partial
rotary positions and a top-1 sparse-expert FFN whose MLP router hands a
state on to the next layer's, RMSNorm, gated experts, a tied head) and
``qwen3_next`` (three Gated DeltaNet linear-attention mixers to one gated
attention row — QK-norm, rotary positions on part of a head, a sigmoid
gate on the output — every layer followed by a softmax top-k
sparse-expert FFN with a gated shared expert; the zero-centred RMSNorm,
an untied head) and ``mellum`` (sliding-window attention rows to one
full-attention row whose rotary positions are YaRN-scaled — two rows of
one table that see and rotate differently — QK-norm, every layer followed
by a softmax top-k sparse-expert FFN with no shared expert; RMSNorm, an
untied head) and ``bailing_hybrid`` (five Kimi-Delta-Attention rows — a
delta rule under a decay a key channel — to one latent-attention (MLA)
row whose scores are wider than its values, a head-wise sigmoid gate on
both; two leading dense SwiGLU FFNs, then a group-limited sigmoid top-k
sparse-expert FFN with a shared expert; RMSNorm, an untied head) and
``sdar_moe`` (identical layers of a GQA row with QK-norm and whole-head
rotary positions and a softmax top-k sparse-expert FFN with no shared
expert; RMSNorm, an untied head — trained by block diffusion, which the
table states once: every attention row sees a document's clean and
noised copies through one block-causal mask) and ``laguna`` (one
full-attention row to three sliding-window rows whose SHAPES differ —
the query heads a layer are a list — each with a sigmoid gate a head on
the attention output, rotary positions on the whole head in a sliding
row and YaRN-scaled on half of it in a full one; a leading dense SwiGLU
FFN, then a sigmoid top-k sparse-expert FFN with an ungated shared
expert; RMSNorm, an untied head).

Plain frozen dataclasses: hashable, so a table is a static field of the
flax module.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Tuple

import numpy as np

MIXERS = ("attention", "mamba2", "cca", "gdn", "kda", "none")
#: "rmsnorm_zc" is the zero-centred RMSNorm: the learned ``w`` starts at
#: 0 and scales by ``1 + w``.
NORMS = ("layernorm", "rmsnorm", "rmsnorm_zc")
FFNS = ("gelu", "swiglu", "relu2", "experts", "none")
#: "sinusoidal" is added to the embedding; "rotary" is applied inside the
#: mixers, to queries and keys (the row's own spec says to how much of a
#: head and at what base), and nothing is added to the embedding.
POSITIONS = ("sinusoidal", "rotary", "none")
ROUTERS = ("sigmoid", "mlp_softmax", "softmax")
EXPERTS = ("relu2", "swiglu")


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """Geometry of a Mamba-2 mixer: ``n_heads`` heads of ``d_head``
    channels, each with a ``d_head x d_state`` state; ``n_groups`` sets of
    B/C shared by ``n_heads / n_groups`` heads; a causal depthwise
    convolution of ``d_conv`` taps; ``chunk`` tokens a block of the
    chunked scan (the sequence length must be a multiple of it);
    ``norm_groups`` equal parts of the channels the gated norm runs over
    one by one (1: over all of them)."""

    n_heads: int
    d_head: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    norm_groups: int = 1

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


@dataclasses.dataclass(frozen=True)
class CCASpec:
    """Geometry of a compressed-convolutional-attention mixer
    (arXiv:2510.04476): ``n_heads`` query and ``n_kv_heads`` key/value
    heads of ``d_head`` in a latent narrower than the model; over the
    ``(n_heads + n_kv_heads) x d_head`` query and key channels a causal
    depthwise convolution of ``time0`` taps, then one of ``time1`` taps
    grouped by head; rotary positions on the first ``rotary_dim`` of a
    head's dimensions at base ``rope_theta``."""

    n_heads: int
    n_kv_heads: int
    d_head: int
    time0: int = 2
    time1: int = 2
    rotary_dim: int = 0
    rope_theta: float = 10000.0

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_kv_heads ({self.n_kv_heads}) must divide "
                             f"n_heads ({self.n_heads})")
        if (self.n_kv_heads * self.d_head) % 2:
            raise ValueError("the value's two halves (this token's, the "
                             "one before's) split n_kv_heads x d_head")
        if self.rotary_dim % 2 or not 0 <= self.rotary_dim <= self.d_head:
            raise ValueError(f"rotary_dim {self.rotary_dim} is no even "
                             f"part of a head of {self.d_head}")

    @property
    def conv_dim(self) -> int:
        return (self.n_heads + self.n_kv_heads) * self.d_head


@dataclasses.dataclass(frozen=True)
class GDNSpec:
    """Geometry of a Gated DeltaNet mixer (arXiv:2412.06464): ``n_k_heads``
    query/key heads of ``d_k`` and ``n_v_heads`` value heads of ``d_v``
    (value head ``j`` reads key head ``j // (n_v_heads / n_k_heads)``),
    each value head with a ``d_k x d_v`` state; a causal depthwise
    convolution of ``d_conv`` taps over the query, key and value channels;
    ``chunk`` tokens a chunk of the chunked gated delta rule."""

    n_k_heads: int
    n_v_heads: int
    d_k: int
    d_v: int
    d_conv: int = 4
    chunk: int = 64

    def __post_init__(self):
        if self.n_v_heads % self.n_k_heads:
            raise ValueError(f"n_k_heads ({self.n_k_heads}) must divide "
                             f"n_v_heads ({self.n_v_heads})")

    @property
    def key_dim(self) -> int:
        return self.n_k_heads * self.d_k

    @property
    def value_dim(self) -> int:
        return self.n_v_heads * self.d_v

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim


@dataclasses.dataclass(frozen=True)
class KDASpec:
    """Geometry of a Kimi-Delta-Attention mixer (Kimi Linear,
    arXiv:2510.26692): ``n_heads`` heads with keys of ``d_k`` and values
    of ``d_v``, each with a ``d_k x d_v`` state that decays by a factor of
    its own A KEY CHANNEL; a causal depthwise convolution of ``d_conv``
    taps over the query, key and value channels, queries and keys then
    L2-normalised a head; the log-decay
    ``lower_bound * sigmoid(.)``, in ``(lower_bound, 0)``; ``chunk``
    tokens a chunk of the chunked rule."""

    n_heads: int
    d_k: int
    d_v: int
    d_conv: int = 4
    chunk: int = 64
    lower_bound: float = -5.0

    def __post_init__(self):
        if not self.lower_bound < 0.0:
            raise ValueError(f"the log-decay's lower bound is negative, "
                             f"got {self.lower_bound}")

    @property
    def key_dim(self) -> int:
        return self.n_heads * self.d_k

    @property
    def value_dim(self) -> int:
        return self.n_heads * self.d_v

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim


@dataclasses.dataclass(frozen=True)
class MLASpec:
    """An attention row as multi-head latent attention (DeepSeek-V2,
    arXiv:2405.04434), for training (no cache, no absorbed form): keys
    and values come up from one latent of ``kv_rank`` a token, RMSNormed;
    a query and key head is ``d_nope`` dimensions without positions and
    ``d_rope`` with rotary positions at ``rope_theta`` (the pairs
    interleaved, ``(2i, 2i + 1)``, where ``interleave``; else ``(i, i +
    d_rope / 2)``), the rotary part of the key ONE vector a token that
    every head shares; values are ``d_v`` wide, which need not be the
    scores' ``d_nope + d_rope``; the softmax scale is ``1 / sqrt(d_nope +
    d_rope)``."""

    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    rope_theta: float = 10000.0
    interleave: bool = True

    def __post_init__(self):
        if self.d_rope % 2 or min(self.kv_rank, self.d_nope, self.d_rope,
                                  self.d_v) < 1:
            raise ValueError(f"an MLA row has a latent, nope and value "
                             f"widths and an even rope width, got {self}")

    @property
    def d_qk(self) -> int:
        return self.d_nope + self.d_rope


@dataclasses.dataclass(frozen=True)
class YarnSpec:
    """YaRN scaling of a row's rotary positions (arXiv:2309.00071): a
    context of ``original_max_position`` stretched ``factor`` times.  The
    dimensions that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn less than ``beta_slow``
    times are slowed ``factor`` times, a linear ramp blends between; and
    ``cos`` and ``sin`` are both multiplied by ``attention_factor`` (None:
    ``0.1 ln(factor) + 1``), so the logits carry its square.  The ramp's
    two ends are rounded outward to whole dimensions (the published
    implementations' ``truncate``, on)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        if self.factor < 1.0 or self.original_max_position < 1:
            raise ValueError(f"yarn stretches a context of at least one "
                             f"position by a factor >= 1, got {self}")

    @property
    def scale(self) -> float:
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * float(np.log(self.factor)) + 1.0


def rotary_frequencies(rotary_dim: int, theta: float,
                       yarn: Optional[YarnSpec] = None):
    """``(inv_freq, scale)`` of a row's rotary positions: the
    ``rotary_dim / 2`` float64 inverse frequencies ``theta^(-2 i /
    rotary_dim)`` and 1.0 — or, under ``yarn``, the blend of those with
    their ``factor``-th and the spec's ``scale`` for ``cos`` and ``sin``.

    With ``c(r) = rotary_dim ln(L / (2 pi r)) / (2 ln theta)`` the
    dimension that turns ``r`` times over the original context ``L``:
    ``ramp_i = clip((i - low) / (high - low), 0, 1)`` from ``low =
    floor(c(beta_fast))`` to ``high = ceil(c(beta_slow))``, held inside
    the head, ``inv_freq_i *= (1 - ramp_i) + ramp_i / factor``."""
    half = rotary_dim // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rotary_dim)
    if yarn is None:
        return freq, 1.0

    def turns(r):
        return (rotary_dim * np.log(yarn.original_max_position
                                    / (2.0 * np.pi * r))
                / (2.0 * np.log(theta)))

    low = max(np.floor(turns(yarn.beta_fast)), 0.0)
    high = min(np.ceil(turns(yarn.beta_slow)), rotary_dim - 1.0)
    if low == high:
        high += 0.001                  # a step, not a division by zero
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return freq * ((1.0 - ramp) + ramp / yarn.factor), yarn.scale


@dataclasses.dataclass(frozen=True)
class ExpertsSpec:
    """A sparse-expert FFN: a router over ``n_experts`` (the published
    count: its width), ``top_k`` chosen a token, none dropped; the
    experts ``held`` here, ``(first, count)`` of the ``n_experts`` (all
    of them, or one expert-parallel rank's share), each at width
    ``d_expert``; and a shared expert of the same form at ``d_shared``
    that every token passes (0: none), times ``sigmoid(h w_s)``, one
    learned vector, where ``shared_gate`` says so.

    ``router``: ``"sigmoid"`` scores by sigmoid of one matrix, chooses by
    score + a per-expert correction bias, and weighs the chosen by their
    scores, normalised over the chosen, times ``scaling``.
    ``"mlp_softmax"`` (``top_k`` 1) brings the token down to ``d_router``,
    adds the router state of the layer before times a learned vector,
    and puts that — the state it hands on to the next layer — through an
    RMSNorm and a three-matrix GELU MLP to a softmax over the experts;
    the choice is by probability + a balancing bias, the weight the
    chosen probability itself.  A layer with this router takes and
    returns ``(x, r)``.  ``"softmax"`` takes a softmax of one matrix over
    all the experts, chooses the ``top_k`` most probable (no bias), and
    weighs them by their probabilities over the chosen ones' sum, times
    ``scaling``.

    ``n_group`` > 0 (the ``sigmoid`` router only) limits the choice to
    groups (DeepSeek-V3, arXiv:2412.19437): the ``n_experts`` are
    ``n_group`` equal runs, a group's score is the sum of its two largest
    ``score + bias``, a token keeps its ``topk_group`` best groups and
    chooses its ``top_k`` among their experts; 0 is no groups.  The
    groups are the router's, over the published experts: a held share
    ``(first, count)`` need not align with one.

    ``expert``: ``"relu2"`` is ``w_down relu(w_up h)^2``, ``"swiglu"``
    ``w_down (silu(w_gate h) * (w_up h))``, three matrices."""

    n_experts: int
    top_k: int
    d_expert: int
    d_shared: int
    held: Optional[Tuple[int, int]] = None      # None = (0, n_experts)
    scaling: float = 1.0
    router: str = "sigmoid"            # one of ROUTERS
    expert: str = "relu2"              # one of EXPERTS
    d_router: int = 0                  # "mlp_softmax": the state's width
    shared_gate: bool = False          # sigmoid(h w_s) on the shared expert
    n_group: int = 0                   # router groups (0: none)
    topk_group: int = 0                # groups a token keeps

    def __post_init__(self):
        if self.router not in ROUTERS or self.expert not in EXPERTS:
            raise ValueError(
                f"router must be one of {ROUTERS} and expert one of "
                f"{EXPERTS}, got {self.router!r} and {self.expert!r}")
        if (self.router == "mlp_softmax") != (self.d_router > 0):
            raise ValueError("an mlp_softmax router, and only it, has a "
                             "d_router")
        if self.router == "mlp_softmax" and (
                self.top_k != 1 or self.scaling != 1.0):
            raise ValueError("the mlp_softmax router chooses one expert a "
                             "token and weighs it by its probability")
        if self.n_group and (
                self.router != "sigmoid" or self.n_experts % self.n_group
                or not 1 <= self.topk_group <= self.n_group
                or self.n_experts // self.n_group < 2
                or self.top_k > self.topk_group
                * (self.n_experts // self.n_group)):
            raise ValueError(
                f"router groups are the sigmoid router's: {self.n_group} "
                f"equal runs of at least two of {self.n_experts} experts, "
                f"{self.topk_group} kept with room for top_k {self.top_k}")
        if self.topk_group and not self.n_group:
            raise ValueError("topk_group without n_group")
        if self.shared_gate and not self.d_shared:
            raise ValueError("a shared_gate gates a shared expert: "
                             "d_shared is 0")
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"held {self.held} is no (first, count) of "
                f"{self.n_experts} experts")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts}")

    @property
    def experts_held(self) -> Tuple[int, int]:
        return self.held or (0, self.n_experts)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One row of the table."""

    mixer: str = "attention"           # one of MIXERS
    norm: str = "layernorm"            # one of NORMS (every norm of the row)
    ffn: str = "gelu"                  # "gelu": wo(gelu(wi x));
                                       # "swiglu": wo(silu(a) * b), [a|b] = wi x
                                       # "relu2": wo(relu(wi x)^2)
                                       # "experts": see ExpertsSpec
    d_ff: int = 2048
    n_heads: int = 8                   # attention rows
    n_kv_heads: Optional[int] = None   # GQA/MQA (divides n_heads)
    d_head: Optional[int] = None       # None = d_model / n_heads
    attn_scale: Optional[float] = None  # softmax scale; None = 1/sqrt(d_head)
    rotary_dim: int = 0                # attention rows: rotary positions on
                                       # the first so many of a head's
                                       # dimensions (0: none), at rope_theta
    rope_theta: float = 10000.0
    yarn: Optional[YarnSpec] = None    # attention rows: the rotary
                                       # positions YaRN-scaled (None: plain)
    window: Optional[int] = None       # attention rows: a query sees its
                                       # ``window`` most recent positions,
                                       # itself among them (None: every
                                       # earlier one)
    qk_norm: bool = False              # attention rows: the row's norm over
                                       # each query and key head
    out_gate: bool = False             # attention rows: [q | gate] = W_q h a
                                       # head, out = W_o (attn * sigmoid(gate))
    mla: Optional[MLASpec] = None      # attention rows: latent attention
                                       # (n_heads heads; qk_norm there is
                                       # over the nope part of a head)
    head_gate: bool = False            # attention rows, plain or mla:
                                       # out = W_o (attn * sigmoid(h W_g)),
                                       # one number a head
    ssm: Optional[SSMSpec] = None      # mamba2 rows
    cca: Optional[CCASpec] = None      # cca rows
    gdn: Optional[GDNSpec] = None      # gdn rows
    kda: Optional[KDASpec] = None      # kda rows
    experts: Optional[ExpertsSpec] = None   # "experts" rows
    residual_multiplier: float = 1.0   # rm above
    norm_eps: float = 1e-6

    def __post_init__(self):
        for value, known, what in ((self.mixer, MIXERS, "mixer"),
                                   (self.norm, NORMS, "norm"),
                                   (self.ffn, FFNS, "ffn")):
            if value not in known:
                raise ValueError(f"{what} must be one of {known}, "
                                 f"got {value!r}")
        if (self.mixer == "mamba2") != (self.ssm is not None):
            raise ValueError("a mamba2 row, and only it, carries an SSMSpec")
        if (self.mixer == "cca") != (self.cca is not None):
            raise ValueError("a cca row, and only it, carries a CCASpec")
        if (self.mixer == "gdn") != (self.gdn is not None):
            raise ValueError("a gdn row, and only it, carries a GDNSpec")
        if (self.mixer == "kda") != (self.kda is not None):
            raise ValueError("a kda row, and only it, carries a KDASpec")
        if self.mixer != "attention" and (
                self.rotary_dim or self.qk_norm or self.out_gate
                or self.window is not None or self.mla is not None
                or self.head_gate):
            raise ValueError("rotary_dim, qk_norm, out_gate, window, mla "
                             "and head_gate are an attention row's")
        if self.mla is not None and (
                self.rotary_dim or self.yarn is not None or self.out_gate
                or self.window is not None or self.attn_scale is not None
                or self.n_kv_heads not in (None, self.n_heads)):
            raise ValueError(
                "an mla row rotates, scales and gates by its MLASpec: "
                "rotary_dim, yarn, out_gate, window, attn_scale and fewer "
                "kv heads are a plain attention row's")
        if self.head_gate and self.out_gate:
            raise ValueError("a row gates its attention output a head "
                             "(head_gate) or a channel (out_gate), not "
                             "both")
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.yarn is not None and not self.rotary_dim:
            raise ValueError("yarn scales rotary positions: the row has "
                             "no rotary_dim")
        if self.rotary_dim % 2 or self.rotary_dim < 0 or (
                self.d_head is not None and self.rotary_dim > self.d_head):
            raise ValueError(f"rotary_dim {self.rotary_dim} is no even "
                             f"part of a head of {self.d_head}")
        if (self.ffn == "experts") != (self.experts is not None):
            raise ValueError("an experts row, and only it, carries an "
                             "ExpertsSpec")
        if self.mixer == "none" and self.ffn == "none":
            raise ValueError("a row has a mixer, an ffn or both")


@dataclasses.dataclass(frozen=True)
class BlockTable:
    """The layers in order, and what surrounds them: the positions added
    to the embedding, ``x = embedding_multiplier * E[token]``, the final
    norm and ``logits = norm(x) W^T / logits_scaling``, ``W`` the
    embedding table itself (``tied_head``) or a matrix of its own."""

    layers: Tuple[LayerSpec, ...]
    positions: str = "sinusoidal"      # one of POSITIONS
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    final_norm: str = "layernorm"
    norm_eps: float = 1e-6
    tied_head: bool = True
    block_diffusion: Optional[int] = None   # the table is trained by
    # block diffusion with this block length: a step runs a document's
    # clean copy and its noised one as 2 L rows, and EVERY attention row
    # sees them through the block-diffusion mask
    # (``ops.flash_attention.blockdiff_mask``), whatever else the rows say

    def __post_init__(self):
        if self.block_diffusion is not None and (
                self.block_diffusion < 1 or any(
                    r.mixer not in ("attention", "none") or r.mla is not None
                    or r.window is not None for r in self.layers)):
            raise ValueError(
                "block_diffusion is a block length >= 1 of a table whose "
                "mixers are plain attention rows without a window: the "
                "mixers that read the token before (cca, gdn, kda, mamba2) "
                "and a latent or a windowed row have no such mask")
        if self.positions not in POSITIONS:
            raise ValueError(f"positions must be one of {POSITIONS}, "
                             f"got {self.positions!r}")
        if self.final_norm not in NORMS:
            raise ValueError(f"final_norm must be one of {NORMS}, "
                             f"got {self.final_norm!r}")
        if not self.layers:
            raise ValueError("a block table has at least one layer")
        if self.positions != "rotary" and any(
                r.rotary_dim or r.mla is not None for r in self.layers):
            raise ValueError("an attention row with a rotary_dim or an "
                             "MLASpec belongs to a table whose positions "
                             "are 'rotary'")
        if len({r.experts.d_router for r in self.layers
                if r.experts is not None}) > 1:
            raise ValueError("every expert row of a table has the same "
                             "router state (d_router), or none has one")

    @property
    def d_router_state(self) -> int:
        """Width of the router state the layers hand on beside the
        residual stream; 0 where no row's router keeps one."""
        return max((r.experts.d_router for r in self.layers
                    if r.experts is not None), default=0)


def gpt2_table(n_layers: int, n_heads: int, d_ff: int,
               n_kv_heads: Optional[int] = None) -> BlockTable:
    """The block this repo started with, in every layer: sinusoidal
    positions, LayerNorm, attention scaled by 1/sqrt(d_head), a two-matrix
    GELU FFN, a LayerNorm before the tied head."""
    row = LayerSpec(n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=d_ff)
    return BlockTable(layers=(row,) * n_layers)


def _refuse(refused):
    for bad, what in refused:
        if bad:
            raise ValueError(f"table_from_config: this system does not "
                             f"build {what}")


def _first_layers(kinds, n_layers):
    if n_layers is None:
        return kinds
    if not 1 <= n_layers <= len(kinds):
        raise ValueError(f"n_layers must be in [1, {len(kinds)}]")
    return kinds[:n_layers]


def table_from_config(config: Mapping, n_layers: Optional[int] = None,
                      experts_held: Optional[Tuple[int, int]] = None
                      ) -> BlockTable:
    """The table of a published ``config.json``, by its own keys.  Eight
    families are read, by ``model_type``: ``granitemoehybrid`` (its dense
    members: ``num_local_experts`` 0), ``nemotron_h``, ``zaya``,
    ``qwen3_next``, ``mellum``, ``bailing_hybrid``, ``sdar_moe`` and
    ``laguna`` (the rows' query heads, windows and rotary positions a
    layer, by ``num_attention_heads_per_layer``, ``layer_types`` and
    ``rope_parameters``; a gate a head; dense then sparse FFNs by
    ``mlp_layer_types``, with a shared expert).  ``n_layers``
    keeps the first so many layers (a pipeline stage, a cut to fit); None
    keeps ``num_hidden_layers``.  ``experts_held`` is the ``(first,
    count)`` of the published experts this rank holds in every expert
    layer (None: all): the deployment's, not a published key.

    What this system cannot build raises here, by key."""
    readers = {"granitemoehybrid": _granite_table,
               "nemotron_h": _nemotron_h_table, "zaya": _zaya_table,
               "qwen3_next": _qwen3_next_table, "mellum": _mellum_table,
               "bailing_hybrid": _bailing_hybrid_table,
               "sdar_moe": _sdar_moe_table, "laguna": _laguna_table}
    reader = readers.get(config.get("model_type"))
    if reader is None:
        raise ValueError(
            f"table_from_config reads {sorted(readers)} configs, got "
            f"model_type {config.get('model_type')!r}")
    if reader is _granite_table:
        if experts_held is not None:
            raise ValueError("table_from_config: a granitemoehybrid table "
                             "has no experts to hold")
        return reader(config, n_layers)
    return reader(config, n_layers, experts_held)


def _granite_table(config, n_layers):
    """``granitemoehybrid``.  Refused by key: sparse experts, rotary
    positions, biases on the projections, another norm or activation, an
    untied head, ``mamba_n_heads x mamba_d_head`` that is not
    ``mamba_expand x hidden_size``."""
    _refuse([
        (config.get("num_local_experts", 0) != 0, "num_local_experts > 0 "
         "(sparse experts)"),
        (config.get("position_embedding_type") != "nope",
         f"position_embedding_type "
         f"{config.get('position_embedding_type')!r} (only 'nope')"),
        (config.get("normalization_function", "rmsnorm") != "rmsnorm",
         "normalization_function other than rmsnorm"),
        (config.get("hidden_act") != "silu", "hidden_act other than silu"),
        (bool(config.get("attention_bias")), "attention_bias"),
        (bool(config.get("mamba_proj_bias")), "mamba_proj_bias"),
        (not config.get("mamba_conv_bias", True), "no mamba_conv_bias"),
        (not config.get("tie_word_embeddings", True),
         "an untied output head"),
        (config.get("mamba_expand", 2) * config["hidden_size"]
         != config["mamba_n_heads"] * config["mamba_d_head"],
         "mamba_n_heads x mamba_d_head != mamba_expand x hidden_size"),
    ])
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers "
                         "entries")
    kinds = _first_layers(kinds, n_layers)
    ssm = SSMSpec(
        n_heads=config["mamba_n_heads"], d_head=config["mamba_d_head"],
        d_state=config["mamba_d_state"], n_groups=config["mamba_n_groups"],
        d_conv=config["mamba_d_conv"], chunk=config["mamba_chunk_size"])
    common = dict(
        norm="rmsnorm", ffn="swiglu", d_ff=config["intermediate_size"],
        residual_multiplier=float(config["residual_multiplier"]),
        norm_eps=float(config["rms_norm_eps"]))
    rows = {
        "attention": LayerSpec(
            mixer="attention", n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            attn_scale=float(config["attention_multiplier"]), **common),
        "mamba": LayerSpec(mixer="mamba2", ssm=ssm, **common),
    }
    unknown = sorted(set(kinds) - set(rows))
    if unknown:
        raise ValueError(f"layer_types holds kinds this system does not "
                         f"build: {unknown}")
    return BlockTable(
        layers=tuple(rows[k] for k in kinds), positions="none",
        embedding_multiplier=float(config["embedding_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        final_norm="rmsnorm", norm_eps=float(config["rms_norm_eps"]))


def _nemotron_h_table(config, n_layers, experts_held):
    """``nemotron_h``: one branch a layer, by ``hybrid_override_pattern``
    — ``M`` a Mamba-2 mixer at ``mamba_num_heads x mamba_head_dim``
    channels, its gated norm over each of the ``n_groups`` parts; ``*``
    GQA at the config's own ``head_dim``, no rotary embedding; ``E`` the
    sparse experts; ``-`` a squared-ReLU FFN at ``intermediate_size`` —
    RMSNorm, no positions, no multipliers.  Refused by key: any bias but
    the convolution's, another activation, router groups, weights not
    normalised over the chosen, more or fewer than one shared expert."""
    _refuse([
        (bool(config.get("attention_bias")), "attention_bias"),
        (bool(config.get("mlp_bias")), "mlp_bias"),
        (bool(config.get("use_bias")), "use_bias"),
        (bool(config.get("mamba_proj_bias")), "mamba_proj_bias"),
        (not config.get("use_conv_bias", True), "no use_conv_bias"),
        (config.get("mlp_hidden_act") != "relu2",
         "mlp_hidden_act other than relu2"),
        (config.get("mamba_hidden_act") != "silu",
         "mamba_hidden_act other than silu"),
        (config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1,
         "n_group / topk_group other than 1 (router groups)"),
        (config.get("n_shared_experts", 1) != 1,
         "n_shared_experts other than 1"),
        (not config.get("norm_topk_prob", True),
         "norm_topk_prob false (router weights not normalised)"),
        (config.get("norm_eps", config["layer_norm_epsilon"])
         != config["layer_norm_epsilon"],
         "norm_eps other than layer_norm_epsilon"),
    ])
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not list "
                         "num_hidden_layers entries")
    kinds = _first_layers(pattern, n_layers)
    eps = float(config["layer_norm_epsilon"])
    groups = config["n_groups"]
    one_branch = dict(norm="rmsnorm", norm_eps=eps)
    rows = {
        "M": LayerSpec(mixer="mamba2", ffn="none", ssm=SSMSpec(
            n_heads=config["mamba_num_heads"],
            d_head=config["mamba_head_dim"],
            d_state=config["ssm_state_size"], n_groups=groups,
            d_conv=config["conv_kernel"], chunk=config["chunk_size"],
            norm_groups=groups), **one_branch),
        "*": LayerSpec(mixer="attention", ffn="none",
                       n_heads=config["num_attention_heads"],
                       n_kv_heads=config["num_key_value_heads"],
                       d_head=config["head_dim"], **one_branch),
        "-": LayerSpec(mixer="none", ffn="relu2",
                       d_ff=config["intermediate_size"], **one_branch),
    }
    if "E" in kinds:
        rows["E"] = LayerSpec(mixer="none", ffn="experts", experts=ExpertsSpec(
            n_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"],
            d_shared=config["moe_shared_expert_intermediate_size"],
            held=experts_held,
            scaling=float(config["routed_scaling_factor"])), **one_branch)
    unknown = sorted(set(kinds) - set(rows))
    if unknown:
        raise ValueError(f"hybrid_override_pattern holds kinds this system "
                         f"does not build: {unknown}")
    return BlockTable(
        layers=tuple(rows[k] for k in kinds), positions="none",
        final_norm="rmsnorm", norm_eps=eps,
        tied_head=bool(config["tie_word_embeddings"]))


def _zaya_table(config, n_layers, experts_held):
    """``zaya``: every layer (``layer_types`` ``hybrid``) a CCA mixer —
    ``num_attention_heads`` query and ``num_key_value_heads`` key/value
    heads of ``head_dim`` in the latent, convolutions of ``cca_time0`` and
    ``cca_time1`` taps, rotary positions on ``partial_rotary_factor`` of
    a head at ``rope_parameters.hybrid.rope_theta`` — then ``num_experts``
    gated experts of ``moe_intermediate_size``, one a token by the MLP
    router at ``router_hidden_size``, no shared expert; RMSNorm, a tied
    head.  Refused by key: a window (``sliding_window``, a
    ``hybrid_sliding`` layer), more than one expert a token, biases,
    another activation or rope type, an untied head."""
    rope = config.get("rope_parameters", {}).get("hybrid", {})
    factor = rope.get("partial_rotary_factor",
                      config.get("partial_rotary_factor", 1.0))
    _refuse([
        (config.get("sliding_window") is not None,
         "sliding_window (windowed attention in the cca mixer)"),
        (config.get("num_experts_per_tok", 1) != 1,
         "num_experts_per_tok > 1 with the zaya router (it weighs its one "
         "expert by the softmax probability itself)"),
        (bool(config.get("attention_bias")), "attention_bias"),
        (bool(config.get("lm_head_bias")), "lm_head_bias"),
        (config.get("hidden_act") != "silu", "hidden_act other than silu"),
        (not config.get("tie_word_embeddings", True),
         "an untied output head"),
        (rope.get("rope_type", "default") != "default",
         "rope_type other than default"),
        (factor != config.get("partial_rotary_factor", factor),
         "partial_rotary_factor other than rope_parameters.hybrid's"),
    ])
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers "
                         "entries")
    kinds = _first_layers(kinds, n_layers)
    unknown = sorted(set(kinds) - {"hybrid"})
    if unknown:
        raise ValueError(f"layer_types holds kinds this system does not "
                         f"build: {unknown}")
    eps = float(config["rms_norm_eps"])
    row = LayerSpec(
        mixer="cca", norm="rmsnorm", ffn="experts", norm_eps=eps,
        cca=CCASpec(
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            d_head=config["head_dim"], time0=config["cca_time0"],
            time1=config["cca_time1"],
            rotary_dim=int(config["head_dim"] * factor),
            rope_theta=float(rope.get("rope_theta", config.get(
                "rope_theta", 10000.0)))),
        experts=ExpertsSpec(
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"], d_shared=0,
            held=experts_held, router="mlp_softmax", expert="swiglu",
            d_router=config["router_hidden_size"]))
    return BlockTable(layers=(row,) * len(kinds), positions="rotary",
                      final_norm="rmsnorm", norm_eps=eps)


def _qwen3_next_table(config, n_layers, experts_held):
    """``qwen3_next``: layer ``i`` a gated attention row where ``(i + 1) %
    full_attention_interval == 0`` — ``num_attention_heads`` query and
    ``num_key_value_heads`` key/value heads of ``head_dim``, QK-norm,
    rotary positions on ``partial_rotary_factor`` of a head at
    ``rope_theta``, a sigmoid gate on the output — else a Gated DeltaNet
    mixer (``linear_num_key_heads`` / ``linear_num_value_heads`` heads of
    ``linear_key_head_dim`` / ``linear_value_head_dim``, a convolution of
    ``linear_conv_kernel_dim`` taps, chunks of 64); every layer then
    ``num_experts`` gated experts of ``moe_intermediate_size``,
    ``num_experts_per_tok`` a token by a softmax router renormalised over
    the chosen, and a sigmoid-gated shared expert of
    ``shared_expert_intermediate_size``; the zero-centred RMSNorm, an
    untied head.  Refused by key: a window, scaled rotary positions,
    dense layers among the sparse ones, router weights not renormalised,
    biases, another activation, a tied head."""
    _refuse([
        (bool(config.get("use_sliding_window")),
         "use_sliding_window (windowed attention)"),
        (config.get("rope_scaling") is not None,
         "rope_scaling (scaled rotary positions)"),
        (config.get("decoder_sparse_step", 1) != 1,
         "decoder_sparse_step other than 1 (dense layers between the "
         "sparse ones)"),
        (bool(config.get("mlp_only_layers")),
         "mlp_only_layers (dense layers in place of sparse ones)"),
        (not config.get("norm_topk_prob", True),
         "norm_topk_prob false (router weights not renormalised over the "
         "chosen)"),
        (bool(config.get("attention_bias")), "attention_bias"),
        (config.get("hidden_act") != "silu", "hidden_act other than silu"),
        (bool(config.get("tie_word_embeddings")), "a tied output head"),
    ])
    every = config["full_attention_interval"]
    kinds = _first_layers(
        ["attention" if (i + 1) % every == 0 else "gdn"
         for i in range(config["num_hidden_layers"])], n_layers)
    eps = float(config["rms_norm_eps"])
    common = dict(
        norm="rmsnorm_zc", ffn="experts", norm_eps=eps,
        experts=ExpertsSpec(
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"],
            d_shared=config["shared_expert_intermediate_size"],
            held=experts_held, router="softmax", expert="swiglu",
            shared_gate=True))
    rows = {
        "attention": LayerSpec(
            mixer="attention", n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            d_head=config["head_dim"],
            rotary_dim=int(config["head_dim"]
                           * config.get("partial_rotary_factor", 1.0)),
            rope_theta=float(config.get("rope_theta", 10000.0)),
            qk_norm=True, out_gate=True, **common),
        "gdn": LayerSpec(mixer="gdn", gdn=GDNSpec(
            n_k_heads=config["linear_num_key_heads"],
            n_v_heads=config["linear_num_value_heads"],
            d_k=config["linear_key_head_dim"],
            d_v=config["linear_value_head_dim"],
            d_conv=config["linear_conv_kernel_dim"]), **common),
    }
    return BlockTable(
        layers=tuple(rows[k] for k in kinds), positions="rotary",
        final_norm="rmsnorm_zc", norm_eps=eps, tied_head=False)


def _yarn_of(rope):
    """The :class:`YarnSpec` of one ``rope_parameters`` entry (None where
    its ``rope_type`` is ``default``)."""
    if rope.get("rope_type", "default") != "yarn":
        return None
    return YarnSpec(
        factor=float(rope["factor"]),
        original_max_position=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope.get("beta_fast", 32.0)),
        beta_slow=float(rope.get("beta_slow", 1.0)),
        attention_factor=rope.get("attention_factor"))


def _mellum_table(config, n_layers, experts_held):
    """``mellum``: layer ``i`` by ``layer_types[i]`` — a
    ``sliding_attention`` row sees ``sliding_window`` positions, a
    ``full_attention`` row every earlier one; both ``num_attention_heads``
    query and ``num_key_value_heads`` key/value heads of ``head_dim``,
    QK-norm, rotary positions on the whole head as ``rope_parameters``
    says for the row's kind (``default``: plain at ``rope_theta``;
    ``yarn``: :class:`YarnSpec`) — every layer then ``num_experts`` gated
    experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a token
    by a softmax router renormalised over the chosen, no shared expert;
    RMSNorm, an untied head.  Refused by key: dense layers among the
    sparse ones, sliding rows with ``use_sliding_window`` off or no
    ``sliding_window``, another rope type or an untruncated YaRN ramp,
    router weights not renormalised, biases, another activation, a tied head."""
    kinds = list(config["layer_types"])
    ropes = config.get("rope_parameters", {})
    _refuse([
        (len(kinds) != config["num_hidden_layers"]
         or len(config.get("mlp_layer_types", kinds)) != len(kinds),
         "layer_types / mlp_layer_types that do not list "
         "num_hidden_layers entries"),
        (set(config.get("mlp_layer_types", ())) - {"sparse"},
         "mlp_layer_types other than sparse (dense layers among the "
         "sparse ones)"),
        (set(kinds) - {"sliding_attention", "full_attention"},
         f"layer_types other than sliding_attention and full_attention: "
         f"{sorted(set(kinds))}"),
        ("sliding_attention" in kinds and not (
            config.get("use_sliding_window")
            and config.get("sliding_window")),
         "sliding_attention rows without use_sliding_window and a "
         "sliding_window"),
        (set(kinds) - set(ropes), "a layer type rope_parameters has no "
         "entry for"),
        (any(r.get("rope_type", "default") not in ("default", "yarn")
             for r in ropes.values()),
         "rope_type other than default and yarn"),
        (not all(r.get("truncate", True) for r in ropes.values()),
         "a yarn ramp that is not truncated to whole dimensions"),
        (not config.get("norm_topk_prob", True),
         "norm_topk_prob false (router weights not renormalised over the "
         "chosen)"),
        (bool(config.get("attention_bias")), "attention_bias"),
        (config.get("hidden_act") != "silu", "hidden_act other than silu"),
        (bool(config.get("tie_word_embeddings")), "a tied output head"),
    ])
    eps = float(config["rms_norm_eps"])

    def row(kind):
        rope = ropes[kind]
        return LayerSpec(
            mixer="attention", norm="rmsnorm", ffn="experts", norm_eps=eps,
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            d_head=config["head_dim"], rotary_dim=config["head_dim"],
            rope_theta=float(rope["rope_theta"]), yarn=_yarn_of(rope),
            qk_norm=True,
            window=(int(config["sliding_window"])
                    if kind == "sliding_attention" else None),
            experts=ExpertsSpec(
                n_experts=config["num_experts"],
                top_k=config["num_experts_per_tok"],
                d_expert=config["moe_intermediate_size"], d_shared=0,
                held=experts_held, router="softmax", expert="swiglu"))

    rows = {kind: row(kind) for kind in set(kinds)}
    return BlockTable(
        layers=tuple(rows[k] for k in _first_layers(kinds, n_layers)),
        positions="rotary", final_norm="rmsnorm", norm_eps=eps,
        tied_head=False)


def _laguna_table(config, n_layers, experts_held):
    """``laguna``: layer ``i`` by ``layer_types[i]`` — a
    ``sliding_attention`` row sees ``sliding_window`` positions, a
    ``full_attention`` row every earlier one — with
    ``num_attention_heads_per_layer[i]`` query heads (where the list is
    absent: ``num_attention_heads``) over ``num_key_value_heads``
    key/value heads of ``head_dim``, so the rows of one table differ in
    SHAPE; rotary positions as ``rope_parameters`` says for the row's
    kind, on the first ``partial_rotary_factor`` of the head
    (``default``: plain at ``rope_theta``; ``yarn``: :class:`YarnSpec`
    over those dimensions); ``gating`` ``per-head``: the attention output
    of every head times ``sigmoid(h W_g)``, one number a head
    (``LayerSpec.head_gate``); no QK-norm (no key names one).  Layer
    ``i``'s FFN by ``mlp_layer_types[i]`` (where absent: ``dense`` for
    the layers of ``mlp_only_layers``, else ``sparse``): ``dense`` a
    SwiGLU of ``intermediate_size``, ``sparse`` ``num_experts`` gated
    experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a token
    by a sigmoid router with a correction bias on the choice, weights
    over the chosen ones' sum times ``moe_routed_scaling_factor``, and an
    ungated shared expert of ``shared_expert_intermediate_size``;
    RMSNorm, an untied head.  Refused by key: a ``gating`` other than
    ``per-head`` or a ``gating_types`` entry that differs, lists that do
    not have ``num_hidden_layers`` entries or disagree with each other,
    layer or FFN kinds it does not know, sliding rows without a
    ``sliding_window``, another rope type, an untruncated YaRN ramp, a
    ``partial_rotary_factor`` that leaves no even part of the head, a
    ``decoder_sparse_step`` other than 1, ``norm_topk_prob`` false,
    ``moe_apply_router_weight_on_input``, a router soft-cap, biases,
    another activation, a tied head."""
    n = config["num_hidden_layers"]
    kinds = list(config["layer_types"])
    heads = list(config.get("num_attention_heads_per_layer",
                            [config["num_attention_heads"]] * n))
    dense_at = set(config.get("mlp_only_layers") or ())
    ffns = list(config.get(
        "mlp_layer_types",
        ["dense" if i in dense_at else "sparse" for i in range(n)]))
    gates = list(config.get("gating_types", ["per_head"] * n))
    ropes = config.get("rope_parameters", {})
    d_head = config["head_dim"]

    def rotary_dim(rope):
        return int(d_head * rope.get("partial_rotary_factor", 1.0))

    _refuse([
        (config.get("gating") != "per-head",
         f"gating {config.get('gating')!r} (only 'per-head')"),
        (set(gates) - {"per_head"},
         f"gating_types other than per_head: {sorted(set(gates))}"),
        (any(len(x) != n for x in (kinds, heads, ffns, gates)),
         "layer_types / num_attention_heads_per_layer / mlp_layer_types / "
         "gating_types that do not list num_hidden_layers entries"),
        (set(kinds) - {"sliding_attention", "full_attention"},
         f"layer_types other than sliding_attention and full_attention: "
         f"{sorted(set(kinds))}"),
        (set(ffns) - {"dense", "sparse"},
         f"mlp_layer_types other than dense and sparse: "
         f"{sorted(set(ffns))}"),
        ({i for i, f in enumerate(ffns) if f == "dense"} != dense_at
         and "mlp_only_layers" in config,
         "mlp_only_layers that disagrees with mlp_layer_types"),
        (config.get("decoder_sparse_step", 1) != 1,
         "decoder_sparse_step other than 1"),
        ("sliding_attention" in kinds and not config.get("sliding_window"),
         "sliding_attention rows without a sliding_window"),
        (set(kinds) - set(ropes), "a layer type rope_parameters has no "
         "entry for"),
        (any(r.get("rope_type", "default") not in ("default", "yarn")
             for r in ropes.values()),
         "rope_type other than default and yarn"),
        (not all(r.get("truncate", True) for r in ropes.values()),
         "a yarn ramp that is not truncated to whole dimensions"),
        (any(rotary_dim(r) < 2 or rotary_dim(r) % 2
             or rotary_dim(r) > d_head for r in ropes.values()),
         "a partial_rotary_factor that leaves no even part of a head"),
        (not config.get("norm_topk_prob", True),
         "norm_topk_prob false (router weights not renormalised over the "
         "chosen)"),
        (bool(config.get("moe_apply_router_weight_on_input")),
         "moe_apply_router_weight_on_input true"),
        (config.get("moe_router_logit_softcapping", 0) != 0,
         "a non-zero moe_router_logit_softcapping"),
        (bool(config.get("attention_bias")), "attention_bias"),
        (bool(config.get("mlp_bias")), "mlp_bias"),
        (config.get("hidden_act", "silu") != "silu",
         "hidden_act other than silu"),
        (bool(config.get("tie_word_embeddings")), "a tied output head"),
    ])
    eps = float(config["rms_norm_eps"])
    kept = _first_layers(list(range(n)), n_layers)
    sparse = dict(ffn="experts", experts=ExpertsSpec(
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config.get("shared_expert_intermediate_size", 0),
        held=experts_held,
        scaling=float(config.get("moe_routed_scaling_factor", 1.0)),
        router="sigmoid", expert="swiglu")) if any(
            ffns[i] == "sparse" for i in kept) else None

    def row(i):
        rope = ropes[kinds[i]]
        return LayerSpec(
            mixer="attention", norm="rmsnorm", norm_eps=eps,
            n_heads=heads[i], n_kv_heads=config["num_key_value_heads"],
            d_head=d_head, rotary_dim=rotary_dim(rope),
            rope_theta=float(rope["rope_theta"]), yarn=_yarn_of(rope),
            window=(int(config["sliding_window"])
                    if kinds[i] == "sliding_attention" else None),
            head_gate=True,
            **(sparse if ffns[i] == "sparse" else dict(
                ffn="swiglu", d_ff=config["intermediate_size"])))

    return BlockTable(
        layers=tuple(row(i) for i in kept), positions="rotary",
        final_norm="rmsnorm", norm_eps=eps, tied_head=False)


#: The block length an ``sdar_moe`` table trains with where the config
#: states none: the family's released ``block_length`` (its generation
#: config's; ``config.json`` has no key for it).
SDAR_BLOCK_LENGTH = 4


def _sdar_moe_table(config, n_layers, experts_held):
    """``sdar_moe``: ``num_hidden_layers`` identical layers — a GQA
    attention row (``num_attention_heads`` query and
    ``num_key_value_heads`` key/value heads of ``head_dim``, no biases,
    the family's QK-norm, rotary positions on the whole head at
    ``rope_theta``) and ``num_experts`` gated experts of
    ``moe_intermediate_size``, ``num_experts_per_tok`` a token by a
    softmax router renormalised over the chosen, no shared expert;
    RMSNorm, an untied head.  The model is trained by block diffusion:
    the TABLE says so (``block_diffusion``, the block length: the
    config's ``block_length`` where it states one, else
    :data:`SDAR_BLOCK_LENGTH`), and every attention row then runs under
    that mask, at the positions it is handed.  Refused by key:
    ``use_sliding_window``, dense layers among the sparse ones
    (``mlp_only_layers``, a ``decoder_sparse_step`` other than 1), a
    ``rope_scaling``, router weights not renormalised, biases, another
    activation, a tied head."""
    _refuse([
        (bool(config.get("use_sliding_window")), "use_sliding_window true"),
        (bool(config.get("mlp_only_layers")),
         "a non-empty mlp_only_layers (dense layers among the sparse "
         "ones)"),
        (config.get("decoder_sparse_step", 1) != 1,
         "decoder_sparse_step other than 1"),
        (config.get("rope_scaling") is not None, "a rope_scaling"),
        (not config.get("norm_topk_prob", True),
         "norm_topk_prob false (router weights not renormalised over the "
         "chosen)"),
        (bool(config.get("attention_bias")), "attention_bias"),
        (config.get("hidden_act") != "silu", "hidden_act other than silu"),
        (bool(config.get("tie_word_embeddings")), "a tied output head"),
    ])
    eps = float(config["rms_norm_eps"])
    row = LayerSpec(
        mixer="attention", norm="rmsnorm", ffn="experts", norm_eps=eps,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"], rotary_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]), qk_norm=True,
        experts=ExpertsSpec(
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"], d_shared=0,
            held=experts_held, router="softmax", expert="swiglu"))
    return BlockTable(
        layers=tuple(_first_layers(
            [row] * config["num_hidden_layers"], n_layers)),
        positions="rotary", final_norm="rmsnorm", norm_eps=eps,
        tied_head=False,
        block_diffusion=int(config.get("block_length", SDAR_BLOCK_LENGTH)))


#: Every key the ``bailing_hybrid`` reader takes: read, held to the one
#: value this system builds, or ignored (the last line: a context length,
#: a window count with no window key beside it, a loss the config gives
#: no coefficient for, and the multi-token-prediction layer, which lives
#: on the last pipeline stage and whose loss weight must be 0).
BAILING_HYBRID_KEYS = frozenset((
    "model_type", "num_hidden_layers", "hidden_size", "vocab_size",
    "intermediate_size", "rms_norm_eps", "hidden_act",
    "tie_word_embeddings", "layer_group_size", "first_k_dense_replace",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "kv_lora_rank", "q_lora_rank", "qk_head_dim", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_interleave",
    "rope_scaling", "rotary_dim", "partial_rotary_factor", "use_qk_norm",
    "use_mla_nope", "gated_attention_proj_granularity_type",
    "short_conv_kernel_size", "linear_silu", "kda_safe_gate",
    "kda_lower_bound", "no_kda_lora", "use_kda_lora", "group_norm_size",
    "num_kv_heads_for_linear_attn", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "moe_router_enable_expert_bias",
    "n_group", "topk_group", "topk_method", "norm_topk_prob",
    "routed_scaling_factor", "score_function", "scoring_func",
    "scale_router_input", "expert_swiglu_limit_list",
    "share_expert_swiglu_limit_list", "use_nGPT", "value_norm",
    "up_proj_norm", "use_bias", "use_qkv_bias", "mtp_use_kda",
    "mtp_loss_scaling_factor", "num_nextn_predict_layers",
    "max_position_embeddings", "max_window_layers", "seq_aux",
))


def _bailing_hybrid_table(config, n_layers, experts_held):
    """``bailing_hybrid`` (Ling-3.0): layer ``i`` a latent-attention row
    where ``(i + 1) % layer_group_size == 0`` — ``num_attention_heads``
    heads scoring over ``qk_nope_head_dim + qk_rope_head_dim`` and summing
    values of ``v_head_dim``, keys and values from a latent of
    ``kv_lora_rank``, rotary positions on the rope part at ``rope_theta``
    (``rope_interleave``: pairs ``(2i, 2i + 1)``), a head-wise sigmoid
    gate — else a Kimi-Delta-Attention row (``num_attention_heads`` heads
    of ``head_dim`` keys and values, a convolution of
    ``short_conv_kernel_size`` taps, the log-decay bounded at
    ``kda_lower_bound``, chunks of 64, the same head-wise gate);
    ``use_qk_norm``: the KDA rows' L2 norms and an RMSNorm over the nope
    part of the MLA row's query and key heads.  The first
    ``first_k_dense_replace`` layers carry a SwiGLU of
    ``intermediate_size``; the others ``num_experts`` gated experts of
    ``moe_intermediate_size``, ``num_experts_per_tok`` a token by a
    sigmoid router with an expert bias, limited to ``topk_group`` of
    ``n_group`` groups, renormalised over the chosen times
    ``routed_scaling_factor``, and one shared expert of
    ``moe_shared_expert_intermediate_size``; RMSNorm, an untied head.
    The two ``*_swiglu_limit_list``s are carried, and a KEPT layer whose
    entry is not 0 is refused: the clamp's form is not in the config.
    Refused by key: an unknown key, every switch the published row gives
    as off at any other value, a query latent, scaled rotary positions,
    fewer key/value heads, no QK-norm, another gate granularity, norm
    group, router
    score or method, several or no shared experts, a multi-token
    prediction loss, a tied head."""
    unknown = sorted(set(config) - BAILING_HYBRID_KEYS)
    _refuse([(unknown, f"bailing_hybrid keys {unknown}; the reader takes "
                       f"{sorted(BAILING_HYBRID_KEYS)}")])
    off = ("use_nGPT", "value_norm", "up_proj_norm", "scale_router_input",
           "use_mla_nope", "use_bias", "use_qkv_bias", "use_kda_lora",
           "mtp_use_kda")
    n = config["num_hidden_layers"]
    heads, d_head = config["num_attention_heads"], config["head_dim"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    clamps = {key: list(config.get(key, [0] * n)) for key in (
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list")}
    _refuse([(bool(config.get(key)), f"{key} true") for key in off] + [
        (config.get("num_kv_heads_for_linear_attn", 0) != 0,
         "num_kv_heads_for_linear_attn other than 0"),
        (config.get("rope_scaling") is not None,
         "rope_scaling (scaled rotary positions)"),
        (config.get("q_lora_rank") is not None,
         "q_lora_rank (a latent query projection)"),
        (not config.get("no_kda_lora", True), "no_kda_lora false"),
        (not config.get("kda_safe_gate", False),
         "kda_safe_gate false (an unbounded decay gate)"),
        (not config.get("linear_silu", True), "linear_silu false"),
        (not config.get("use_qk_norm", True),
         "use_qk_norm false (KDA rows without their L2 norms)"),
        (config.get("hidden_act") != "silu", "hidden_act other than silu"),
        (config.get("group_norm_size", 1) != 1,
         "group_norm_size other than 1"),
        (config.get("gated_attention_proj_granularity_type") != "head_wise",
         "gated_attention_proj_granularity_type other than head_wise"),
        (config.get("num_key_value_heads", heads) != heads,
         "num_key_value_heads other than num_attention_heads"),
        (config.get("qk_head_dim", nope + rope) != nope + rope
         or config.get("rotary_dim", rope) != rope
         or int(d_head * config.get("partial_rotary_factor", rope / d_head))
         != rope,
         "qk_head_dim, rotary_dim or partial_rotary_factor that disagree "
         "with qk_nope_head_dim and qk_rope_head_dim"),
        ({config.get("score_function", "sigmoid"),
          config.get("scoring_func", "sigmoid")} != {"sigmoid"},
         "score_function / scoring_func other than sigmoid"),
        (config.get("topk_method") != "noaux_tc",
         "topk_method other than noaux_tc"),
        (not config.get("moe_router_enable_expert_bias", True),
         "moe_router_enable_expert_bias false"),
        (not config.get("norm_topk_prob", True),
         "norm_topk_prob false (router weights not renormalised over the "
         "chosen)"),
        (config.get("num_shared_experts", 1) != 1,
         "num_shared_experts other than 1"),
        (config.get("mtp_loss_scaling_factor", 0) != 0,
         "mtp_loss_scaling_factor other than 0 (a multi-token-prediction "
         "loss)"),
        (bool(config.get("tie_word_embeddings")), "a tied output head"),
        (any(len(v) != n for v in clamps.values()),
         "*_swiglu_limit_list that do not list num_hidden_layers entries"),
    ])
    every, dense = config["layer_group_size"], config["first_k_dense_replace"]
    kept = _first_layers(list(range(n)), n_layers)
    clamped = [i for i in kept if i >= dense and any(
        v[i] != 0 for v in clamps.values())]
    _refuse([(clamped, f"a SwiGLU clamp (layers {clamped}: a non-zero "
                       f"*_swiglu_limit_list entry, its form not given)")])
    eps = float(config["rms_norm_eps"])
    mixers = {
        "mla": dict(
            mixer="attention", n_heads=heads, qk_norm=True,
            head_gate=True, mla=MLASpec(
                kv_rank=config["kv_lora_rank"], d_nope=nope, d_rope=rope,
                d_v=config["v_head_dim"],
                rope_theta=float(config.get("rope_theta", 10000.0)),
                interleave=bool(config.get("rope_interleave", False)))),
        "kda": dict(mixer="kda", kda=KDASpec(
            n_heads=heads, d_k=d_head, d_v=d_head,
            d_conv=config["short_conv_kernel_size"],
            lower_bound=float(config["kda_lower_bound"]))),
    }
    ffns = {"dense": dict(ffn="swiglu", d_ff=config["intermediate_size"])}
    if kept[-1] >= dense:
        ffns["sparse"] = dict(ffn="experts", experts=ExpertsSpec(
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"],
            d_shared=config["moe_shared_expert_intermediate_size"],
            held=experts_held,
            scaling=float(config.get("routed_scaling_factor", 1.0)),
            router="sigmoid", expert="swiglu",
            n_group=config.get("n_group", 0),
            topk_group=config.get("topk_group", 0)))
    return BlockTable(
        layers=tuple(LayerSpec(
            norm="rmsnorm", norm_eps=eps,
            **mixers["mla" if (i + 1) % every == 0 else "kda"],
            **ffns["dense" if i < dense else "sparse"]) for i in kept),
        positions="rotary", final_norm="rmsnorm", norm_eps=eps,
        tied_head=False)
