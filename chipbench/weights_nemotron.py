"""Seeded weights of a ``nemotron_h`` configuration, made by the benchmark
and handed to both sides, as ``chipbench/weights_hybrid.py`` does for the
``granitemoehybrid`` tree: one jitted call builds the float32 parameter
tree on the device from ``--seed``, under the names ``models/
transformer.py`` gives the parameters of this family's block table, so the
program takes it as its parameters and the plain reference
(``chipbench/refs/nemotron_h.py``) reads the same arrays by name.  Nothing
here imports the program.

Distribution (the configuration file lists it under ``assumed``): every
matrix N(0, 0.02), the router and the held experts' stacked matrices
among them; norm scales 1 + 0.1 N(0,1); the convolution's taps
U(-0.5, 0.5) and its bias 0.1 N(0,1); ``A_log`` = log U(1, 16);
``dt_bias`` the inverse softplus of a step drawn log-uniformly from
[``time_step_min``, ``time_step_max``], no smaller than
``time_step_floor``; ``D`` = 1; the router's correction bias
0.01 N(0,1): small beside the scores' spread, large beside the gap
between the sixth and the seventh score, so that it takes part in the
choice.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import _nest

KINDS = {"M": "mamba", "E": "experts", "*": "attention", "-": "mlp"}


def sizes(config):
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        layers=config["n_layer"],
        kinds=tuple(KINDS[c] for c in
                    config["hybrid_override_pattern"][:config["n_layer"]]),
        ssm_heads=H, ssm_d_head=P, ssm_groups=G, ssm_state=N,
        d_conv=config["conv_kernel"], d_inner=H * P,
        conv_dim=H * P + 2 * G * N, d_ff=config["intermediate_size"],
        experts=config["n_routed_experts_published"],
        held_first=config["experts_held_first"],
        held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"])


def shapes(config):
    """name path -> shape, in the program's layout."""
    z = sizes(config)
    d = z["d"]
    out = {("embed", "embedding"): (z["vocab"], d),
           ("final_norm", "scale"): (d,)}
    if not config["tie_word_embeddings"]:
        out[("lm_head",)] = (z["vocab"], d)
    for i, kind in enumerate(z["kinds"]):
        L = f"layer_{i}"
        out[(L, "RMSNorm_0", "scale")] = (d,)
        if kind == "attention":
            att = "MultiHeadAttention_0"
            out[(L, att, "query", "kernel")] = (d, z["heads"], z["d_head"])
            out[(L, att, "key", "kernel")] = (d, z["kv_heads"], z["d_head"])
            out[(L, att, "value", "kernel")] = (
                d, z["kv_heads"], z["d_head"])
            out[(L, att, "out", "kernel")] = (z["heads"], z["d_head"], d)
        elif kind == "mamba":
            m = "Mamba2Mixer_0"
            out[(L, m, "in_proj", "kernel")] = (
                d, z["d_inner"] + z["conv_dim"] + z["ssm_heads"])
            out[(L, m, "conv_kernel")] = (z["d_conv"], z["conv_dim"])
            out[(L, m, "conv_bias")] = (z["conv_dim"],)
            for name in ("dt_bias", "A_log", "D"):
                out[(L, m, name)] = (z["ssm_heads"],)
            out[(L, m, "norm", "scale")] = (z["d_inner"],)
            out[(L, m, "out_proj", "kernel")] = (z["d_inner"], d)
        elif kind == "experts":
            e = "ExpertLayer_0"
            out[(L, e, "router")] = (d, z["experts"])
            out[(L, e, "router_bias")] = (z["experts"],)
            out[(L, e, "experts_up")] = (z["held"], z["d_expert"], d)
            out[(L, e, "experts_down")] = (z["held"], z["d_expert"], d)
            out[(L, e, "shared", "wi", "kernel")] = (d, z["d_shared"])
            out[(L, e, "shared", "wo", "kernel")] = (z["d_shared"], d)
        else:
            f = "Relu2FeedForward_0"
            out[(L, f, "wi", "kernel")] = (d, z["d_ff"])
            out[(L, f, "wo", "kernel")] = (z["d_ff"], d)
    return out


def n_params(config):
    return sum(math.prod(shape) for shape in shapes(config).values())


def make(config, seed, sharding=None):
    """The float32 parameter tree, on the device, in one jitted call."""
    table = shapes(config)
    paths = sorted(table)
    lo, hi = config["time_step_min"], config["time_step_max"]

    def build(key):
        flat = {}
        for i, path in enumerate(paths):
            k = jax.random.fold_in(key, i)
            name, shape = path[-1], table[path]
            if name == "D":
                flat[path] = jnp.ones(shape, jnp.float32)
                continue
            if name in ("A_log", "dt_bias", "conv_kernel"):
                u = jax.random.uniform(k, shape, jnp.float32)
                if name == "A_log":
                    flat[path] = jnp.log(1.0 + 15.0 * u)
                elif name == "conv_kernel":
                    flat[path] = u - 0.5
                else:
                    dt = jnp.maximum(
                        jnp.exp(math.log(lo) + u * math.log(hi / lo)),
                        config["time_step_floor"])
                    flat[path] = dt + jnp.log(-jnp.expm1(-dt))
                continue
            noise = jax.random.normal(k, shape, jnp.float32)
            if name == "scale":
                flat[path] = 1.0 + 0.1 * noise
            elif name == "conv_bias":
                flat[path] = 0.1 * noise
            elif name == "router_bias":
                flat[path] = 0.01 * noise
            else:
                flat[path] = 0.02 * noise
        return _nest(flat)

    # threefry keys take 32 bits; the driver's seeds are wider.
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=sharding)(key)
