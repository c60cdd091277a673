"""The per-layer block table of :class:`~chainermn_tpu.models.transformer.
TransformerLM`, and the functions that write one.

A decoder is a list of residual blocks ``x + rm * mixer(norm(x))``, ``x +
rm * ffn(norm(x))``.  What differs between architectures is *which* mixer,
norm and FFN each layer has and a handful of scalars; a :class:`BlockTable`
states exactly that, one :class:`LayerSpec` a layer, and the model builds
its layers from it.  The GPT-2-style block the repo started with is one
row (:func:`gpt2_table`); a published ``granitemoehybrid`` config (Mamba-2
mixers with an attention layer among every few, RMSNorm, SwiGLU, no
positions, scalar multipliers) is turned into its table by
:func:`table_from_config`.

Plain frozen dataclasses: hashable, so a table is a static field of the
flax module.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Tuple

MIXERS = ("attention", "mamba2")
NORMS = ("layernorm", "rmsnorm")
FFNS = ("gelu", "swiglu")
POSITIONS = ("sinusoidal", "none")


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """Geometry of a Mamba-2 mixer: ``n_heads`` heads of ``d_head``
    channels, each with a ``d_head x d_state`` state; ``n_groups`` sets of
    B/C shared by ``n_heads / n_groups`` heads; a causal depthwise
    convolution of ``d_conv`` taps; ``chunk`` tokens a block of the
    chunked scan (the sequence length must be a multiple of it)."""

    n_heads: int
    d_head: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One row of the table."""

    mixer: str = "attention"           # one of MIXERS
    norm: str = "layernorm"            # one of NORMS (both norms of the row)
    ffn: str = "gelu"                  # "gelu": wo(gelu(wi x));
                                       # "swiglu": wo(silu(a) * b), [a|b] = wi x
    d_ff: int = 2048
    n_heads: int = 8                   # attention rows
    n_kv_heads: Optional[int] = None   # GQA/MQA (divides n_heads)
    attn_scale: Optional[float] = None  # softmax scale; None = 1/sqrt(d_head)
    ssm: Optional[SSMSpec] = None      # mamba2 rows
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


@dataclasses.dataclass(frozen=True)
class BlockTable:
    """The layers in order, and what surrounds them: the positions added
    to the embedding, ``x = embedding_multiplier * E[token]``, the final
    norm and ``logits = norm(x) E^T / logits_scaling``."""

    layers: Tuple[LayerSpec, ...]
    positions: str = "sinusoidal"      # one of POSITIONS
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    final_norm: str = "layernorm"
    norm_eps: float = 1e-6

    def __post_init__(self):
        if self.positions not in POSITIONS:
            raise ValueError(f"positions must be one of {POSITIONS}, "
                             f"got {self.positions!r}")
        if self.final_norm not in NORMS:
            raise ValueError(f"final_norm must be one of {NORMS}, "
                             f"got {self.final_norm!r}")
        if not self.layers:
            raise ValueError("a block table has at least one layer")


def gpt2_table(n_layers: int, n_heads: int, d_ff: int,
               n_kv_heads: Optional[int] = None) -> BlockTable:
    """The block this repo started with, in every layer: sinusoidal
    positions, LayerNorm, attention scaled by 1/sqrt(d_head), a two-matrix
    GELU FFN, a LayerNorm before the tied head."""
    row = LayerSpec(n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=d_ff)
    return BlockTable(layers=(row,) * n_layers)


def table_from_config(config: Mapping, n_layers: Optional[int] = None
                      ) -> BlockTable:
    """The table of a published ``granitemoehybrid`` ``config.json`` (the
    dense members of the family: ``num_local_experts`` 0), by its own keys.
    ``n_layers`` keeps the first so many entries of ``layer_types`` (a
    pipeline stage, a cut to fit); None keeps ``num_hidden_layers``.

    What this system cannot build raises here, by key: sparse experts,
    rotary positions, biases on the projections, another norm or
    activation, an attention head that is not ``hidden_size /
    num_attention_heads`` wide."""
    if config.get("model_type") != "granitemoehybrid":
        raise ValueError(
            f"table_from_config reads granitemoehybrid configs, got "
            f"model_type {config.get('model_type')!r}")
    refused = [
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
    ]
    for bad, what in refused:
        if bad:
            raise ValueError(f"table_from_config: this system does not "
                             f"build {what}")
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers "
                         "entries")
    if n_layers is not None:
        if not 1 <= n_layers <= len(kinds):
            raise ValueError(f"n_layers must be in [1, {len(kinds)}]")
        kinds = kinds[:n_layers]
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
