"""Seeded weights of a ``granitemoehybrid`` configuration, made by the
benchmark and handed to both sides, as ``chipbench/weights.py`` does for
the GPT-2 tree: one jitted call builds the float32 parameter tree on the
device from ``--seed``, under the names ``models/transformer.py`` gives the
parameters of a block table, so the program takes it as its parameters
and the plain reference (``chipbench/refs/granite_hybrid.py``) reads the
same arrays by name.  Nothing here imports the program.

Distribution (the configuration file lists it under ``assumed``): every
matrix N(0, 0.02); norm scales 1 + 0.1 N(0,1); the convolution's taps
U(-0.5, 0.5) (PyTorch's default for 4 taps a channel) and its bias
0.1 N(0,1); ``A_log`` = log U(1, 16); ``dt_bias`` the inverse softplus of
a step drawn log-uniformly from [1e-3, 1e-1]; ``D`` = 1 — so that a fault
in any of them shows.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import _nest


def sizes(config):
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"], heads=h,
        kv_heads=hk, d_head=config["hidden_size"] // h,
        d_ff=config["intermediate_size"], layers=config["n_layer"],
        kinds=tuple(config["layer_types"][:config["n_layer"]]),
        ssm_heads=H, ssm_d_head=P, ssm_groups=G, ssm_state=N,
        d_conv=config["mamba_d_conv"], d_inner=H * P,
        conv_dim=H * P + 2 * G * N)


def shapes(config):
    """name path -> shape, in the program's layout."""
    z = sizes(config)
    d, f = z["d"], z["d_ff"]
    out = {("embed", "embedding"): (z["vocab"], d),
           ("final_norm", "scale"): (d,)}
    for i, kind in enumerate(z["kinds"]):
        L = f"layer_{i}"
        out[(L, "RMSNorm_0", "scale")] = (d,)
        out[(L, "RMSNorm_1", "scale")] = (d,)
        out[(L, "GatedFeedForward_0", "wi", "kernel")] = (d, 2 * f)
        out[(L, "GatedFeedForward_0", "wo", "kernel")] = (f, d)
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
        else:
            raise ValueError(f"unknown layer type {kind!r}")
    return out


def n_params(config):
    return sum(math.prod(shape) for shape in shapes(config).values())


def make(config, seed, sharding=None):
    """The float32 parameter tree, on the device, in one jitted call."""
    table = shapes(config)
    paths = sorted(table)

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
                    dt = jnp.exp(math.log(1e-3) + u * math.log(1e2))
                    flat[path] = dt + jnp.log(-jnp.expm1(-dt))
                continue
            noise = jax.random.normal(k, shape, jnp.float32)
            if name == "scale":
                flat[path] = 1.0 + 0.1 * noise
            elif name == "conv_bias":
                flat[path] = 0.1 * noise
            else:
                flat[path] = 0.02 * noise
        return _nest(flat)

    # threefry keys take 32 bits; the driver's seeds are wider.
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=sharding)(key)
