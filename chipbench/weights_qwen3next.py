"""Seeded weights of a ``qwen3_next`` configuration, made by the benchmark
and handed to both sides, as ``chipbench/weights_nemotron.py`` does for
the ``nemotron_h`` tree: one jitted call builds the float32 parameter tree
on the device from ``--seed``, under the names ``models/transformer.py``
gives the parameters of this family's block table, so the program takes
it as its parameters and the plain reference
(``chipbench/refs/qwen3_next.py``) reads the same arrays by name.  Nothing
here imports the program.

Distribution (the configuration file lists it under ``assumed``): every
matrix N(0, 0.02) — the table, the head, the projections, the router, the
held experts' stacks — but each branch's OUTPUT matrix (the mixers'
``out_proj`` / ``out``, ``experts_down``, the shared expert's ``wo``)
N(0, 0.02 / sqrt(2 x num_hidden_layers)), the published depth's residual
scaling, as ``weights_zaya.py`` has it; the zero-centred norms' ``w``
(every layer norm, the final norm, the queries' and keys') 0.1 N(0,1),
the Gated DeltaNet's own gated norm a plain scale 1 + 0.1 N(0,1); the
convolution's taps U(-0.5, 0.5); ``A_log`` = log U(1, 16) and ``dt_bias``
the inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1], as
the Mamba-2 cells seed theirs.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import _nest

#: ``dt_bias``: the Mamba-2 cells' step range (the source's config has no
#: key for it).
DT_MIN, DT_MAX = 1e-3, 1e-1


def kinds(config):
    """``"gdn"`` or ``"attention"`` for each of the ``n_layer`` layers
    kept: layer ``i`` is full attention where ``(i + 1) %
    full_attention_interval == 0``."""
    every = config["full_attention_interval"]
    return tuple("attention" if (i + 1) % every == 0 else "gdn"
                 for i in range(config["n_layer"]))


def sizes(config):
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    D = config["head_dim"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layers=config["n_layer"], kinds=kinds(config),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], d_head=D,
        rotary_dim=int(D * config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        k_heads=hk, v_heads=hv, d_k=dk, d_v=dv,
        key_dim=hk * dk, value_dim=hv * dv,
        conv_dim=2 * hk * dk + hv * dv,
        d_conv=config["linear_conv_kernel_dim"],
        experts=config["num_experts_published"],
        held_first=config["experts_held_first"],
        held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"])


def shapes(config):
    """name path -> shape, in the program's layout."""
    z = sizes(config)
    d, D = z["d"], z["d_head"]
    out = {("embed", "embedding"): (z["vocab"], d),
           ("final_norm", "scale"): (d,)}
    if not config["tie_word_embeddings"]:
        out[("lm_head",)] = (z["vocab"], d)
    for i, kind in enumerate(z["kinds"]):
        L, e = f"layer_{i}", "ExpertLayer_0"
        out[(L, "ZeroCentredRMSNorm_0", "scale")] = (d,)
        out[(L, "ZeroCentredRMSNorm_1", "scale")] = (d,)
        if kind == "attention":
            att = "MultiHeadAttention_0"
            out[(L, att, "query", "kernel")] = (d, z["heads"], 2 * D)
            out[(L, att, "key", "kernel")] = (d, z["kv_heads"], D)
            out[(L, att, "value", "kernel")] = (d, z["kv_heads"], D)
            out[(L, att, "q_norm", "scale")] = (D,)
            out[(L, att, "k_norm", "scale")] = (D,)
            out[(L, att, "out", "kernel")] = (z["heads"], D, d)
        else:
            m = "GatedDeltaNetMixer_0"
            out[(L, m, "in_proj_qkvz", "kernel")] = (
                d, z["conv_dim"] + z["value_dim"])
            out[(L, m, "in_proj_ba", "kernel")] = (d, 2 * z["v_heads"])
            out[(L, m, "conv_kernel")] = (z["d_conv"], z["conv_dim"])
            out[(L, m, "dt_bias")] = (z["v_heads"],)
            out[(L, m, "A_log")] = (z["v_heads"],)
            out[(L, m, "norm_scale")] = (z["d_v"],)
            out[(L, m, "out_proj", "kernel")] = (z["value_dim"], d)
        out[(L, e, "router")] = (d, z["experts"])
        for name in ("experts_gate", "experts_up", "experts_down"):
            out[(L, e, name)] = (z["held"], z["d_expert"], d)
        out[(L, e, "shared", "wi", "kernel")] = (d, 2 * z["d_shared"])
        out[(L, e, "shared", "wo", "kernel")] = (z["d_shared"], d)
        out[(L, e, "shared_gate", "kernel")] = (d, 1)
    return out


def n_params(config):
    return sum(math.prod(shape) for shape in shapes(config).values())


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
            if name in ("A_log", "dt_bias", "conv_kernel"):
                u = jax.random.uniform(k, shape, jnp.float32)
                if name == "A_log":
                    flat[path] = jnp.log(1.0 + 15.0 * u)
                elif name == "conv_kernel":
                    flat[path] = u - 0.5
                else:
                    dt = jnp.exp(math.log(DT_MIN)
                                 + u * math.log(DT_MAX / DT_MIN))
                    flat[path] = dt + jnp.log(-jnp.expm1(-dt))
                continue
            noise = jax.random.normal(k, shape, jnp.float32)
            if name == "scale":                  # zero-centred: (1 + w)
                flat[path] = 0.1 * noise
            elif name == "norm_scale":           # the mixer's gated norm
                flat[path] = 1.0 + 0.1 * noise
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
