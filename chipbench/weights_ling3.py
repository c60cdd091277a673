"""Seeded weights of a ``bailing_hybrid`` (Ling-3.0) configuration, made by
the benchmark and handed to both sides, as ``chipbench/weights_qwen3next.py``
does for the ``qwen3_next`` tree: one jitted call builds the float32
parameter tree on the device from ``--seed``, under the names
``models/transformer.py`` gives the parameters of this family's block
table, so the program takes it as its parameters and the plain reference
(``chipbench/refs/ling3.py``) reads the same arrays by name.  Nothing here
imports the program.

Distribution (the configuration file lists it under ``assumed``): every
matrix N(0, 0.02) — the table, the head, the projections, the latent's
two matrices, the router, the held experts' stacks — but each branch's
OUTPUT matrix (the mixers' ``out_proj`` / ``out``, ``experts_down``, the
dense and shared FFNs' ``wo``) N(0, 0.02 / sqrt(2 x num_hidden_layers)),
the published depth's residual scaling; every RMSNorm scale (the layers',
the final one, the latent's, the heads', the KDA rows' own) 1 + 0.1
N(0,1); the convolution's taps U(-0.5, 0.5); the expert bias 0.01 N(0,1),
so that it takes part in the choice; the KDA decay's rate ``A_log`` = log
U(0.5, 2) and its ``dt_bias`` -4 + N(0,1): a log-decay around -0.1 a token
with channels from -0.005 to -1 under unit-scale inputs, a memory of ten
tokens or so whose channels differ.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import _nest


def kinds(config):
    """``("mla" | "kda", "dense" | "sparse")`` for each of the ``n_layer``
    layers kept: layer ``i`` is latent attention where ``(i + 1) %
    layer_group_size == 0``, dense below ``first_k_dense_replace``."""
    every, dense = config["layer_group_size"], config["first_k_dense_replace"]
    return tuple(("mla" if (i + 1) % every == 0 else "kda",
                  "dense" if i < dense else "sparse")
                 for i in range(config["n_layer"]))


def shapes(config):
    """name path -> shape, in the program's layout."""
    d, H, D = (config["hidden_size"], config["num_attention_heads"],
               config["head_dim"])
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    V, f = config["vocab_size"], config["moe_intermediate_size"]
    out = {("embed", "embedding"): (V, d), ("final_norm", "scale"): (d,),
           ("lm_head",): (V, d)}
    for i, (mixer, ffn) in enumerate(kinds(config)):
        L = f"layer_{i}"
        out[(L, "RMSNorm_0", "scale")] = (d,)
        out[(L, "RMSNorm_1", "scale")] = (d,)
        if mixer == "mla":
            m = "MLAMixer_0"
            out[(L, m, "query", "kernel")] = (d, H, nope + rope)
            out[(L, m, "kv_a", "kernel")] = (d, rank + rope)
            out[(L, m, "kv_norm")] = (rank,)
            out[(L, m, "kv_b", "kernel")] = (rank, H, nope + dv)
            if config["use_qk_norm"]:
                out[(L, m, "q_norm")] = (nope,)
                out[(L, m, "k_norm")] = (nope,)
            out[(L, m, "gate", "kernel")] = (d, H)
            out[(L, m, "out", "kernel")] = (H, dv, d)
        else:
            m = "KDAMixer_0"
            out[(L, m, "in_proj_qkvf", "kernel")] = (d, 4 * H * D)
            out[(L, m, "in_proj_bg", "kernel")] = (d, 2 * H)
            out[(L, m, "conv_kernel")] = (
                config["short_conv_kernel_size"], 3 * H * D)
            out[(L, m, "A_log")] = (H,)
            out[(L, m, "dt_bias")] = (H * D,)
            out[(L, m, "norm_scale")] = (D,)
            out[(L, m, "out_proj", "kernel")] = (H * D, d)
        if ffn == "dense":
            w = config["intermediate_size"]
            out[(L, "GatedFeedForward_0", "wi", "kernel")] = (d, 2 * w)
            out[(L, "GatedFeedForward_0", "wo", "kernel")] = (w, d)
            continue
        e, s = "ExpertLayer_0", config["moe_shared_expert_intermediate_size"]
        out[(L, e, "router")] = (d, config["num_experts_published"])
        out[(L, e, "router_bias")] = (config["num_experts_published"],)
        for name in ("experts_gate", "experts_up", "experts_down"):
            out[(L, e, name)] = (config["num_experts"], f, d)
        out[(L, e, "shared", "wi", "kernel")] = (d, 2 * s)
        out[(L, e, "shared", "wo", "kernel")] = (s, d)
    return out


def n_params(config):
    return sum(math.prod(shape) for shape in shapes(config).values())


#: Leaves that are an RMSNorm's scale.
_SCALES = ("scale", "norm_scale", "kv_norm", "q_norm", "k_norm")


def make(config, seed, sharding=None):
    """The float32 parameter tree, on the device, in one jitted call."""
    table = shapes(config)
    paths = sorted(table)
    resid = (2.0 * config["num_hidden_layers"]) ** -0.5

    def build(key):
        flat = {}
        for i, path in enumerate(paths):
            k = jax.random.fold_in(key, i)
            name, shape = path[-1], table[path]
            if name in ("A_log", "conv_kernel"):
                u = jax.random.uniform(k, shape, jnp.float32)
                flat[path] = (jnp.log(0.5 + 1.5 * u) if name == "A_log"
                              else u - 0.5)
                continue
            noise = jax.random.normal(k, shape, jnp.float32)
            if name == "dt_bias":
                flat[path] = noise - 4.0
            elif name in _SCALES:
                flat[path] = 1.0 + 0.1 * noise
            elif name == "router_bias":
                flat[path] = 0.01 * noise
            elif name == "experts_down" or path[-2:] in (
                    ("out", "kernel"), ("out_proj", "kernel"),
                    ("wo", "kernel")):
                flat[path] = 0.02 * resid * noise
            else:
                flat[path] = 0.02 * noise
        return _nest(flat)

    # threefry keys take 32 bits; the driver's seeds are wider.
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=sharding)(key)
