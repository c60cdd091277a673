"""Seeded weights of an ``sdar_moe`` configuration, made by the benchmark
and handed to both sides, as ``chipbench/weights_mellum2.py`` does for its
family (the same tree: a GQA row with QK-norm and a sparse-expert FFN a
layer, an untied head): one jitted call builds the float32 parameter tree
on the device from ``--seed``, under the names ``models/transformer.py``
gives the parameters of this family's block table.  Nothing here imports
the program.

Distribution (the configuration file lists it under ``assumed``):
``weights_mellum2.py``'s, for its reasons — every matrix N(0, 0.02), each
branch's OUTPUT matrix N(0, 0.02 / sqrt(2 x num_hidden_layers)), the
norms' scales 1 + 0.1 N(0,1), the TABLE N(0, 1) (a token's routing is then
its own; the mask id's row is one row among them, so the half of the noisy
rows that carry it route alike in the first layer whatever the scale: the
objective's load, not the seeding's).

:func:`placement` is ``weights_mellum2.placement`` by this family's
reference: every layer's experts placed on the layer's chips by their
load under the seed's first batch (its ``2 L`` rows, the mask id's
concentration among them), this chip the first rank's.
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.refs import sdar_moe as reference
from chipbench.weights import _nest
from chipbench.weights_mellum2 import (  # noqa: F401  (the runner's)
    TABLE_STD,
    place_experts,
    with_placement,
)


def sizes(config):
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layers=config["n_layer"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"], block=config["block_length"],
        experts=config["num_experts_published"],
        held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"])


def shapes(config):
    """name path -> shape, in the program's layout."""
    z = sizes(config)
    d, D = z["d"], z["d_head"]
    out = {("embed", "embedding"): (z["vocab"], d),
           ("final_norm", "scale"): (d,), ("lm_head",): (z["vocab"], d)}
    for i in range(z["layers"]):
        L, att, e = f"layer_{i}", "MultiHeadAttention_0", "ExpertLayer_0"
        out[(L, "RMSNorm_0", "scale")] = (d,)
        out[(L, "RMSNorm_1", "scale")] = (d,)
        out[(L, att, "query", "kernel")] = (d, z["heads"], D)
        out[(L, att, "key", "kernel")] = (d, z["kv_heads"], D)
        out[(L, att, "value", "kernel")] = (d, z["kv_heads"], D)
        out[(L, att, "q_norm", "scale")] = (D,)
        out[(L, att, "k_norm", "scale")] = (D,)
        out[(L, att, "out", "kernel")] = (z["heads"], D, d)
        out[(L, e, "router")] = (d, z["experts"])
        for name in ("experts_gate", "experts_up", "experts_down"):
            out[(L, e, name)] = (z["held"], z["d_expert"], d)
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
            noise = jax.random.normal(
                jax.random.fold_in(key, i), table[path], jnp.float32)
            if path[-1] == "scale":
                flat[path] = 1.0 + 0.1 * noise
            elif path == ("embed", "embedding"):
                flat[path] = TABLE_STD * noise
            elif path[-1] == "experts_down" or path[-2:] == (
                    "out", "kernel"):
                flat[path] = 0.02 * resid * noise
            else:
                flat[path] = 0.02 * noise
        return _nest(flat)

    # threefry keys take 32 bits; the driver's seeds are wider.
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=sharding)(key)


def placement(params, rows, config):
    """``{layer name: (E,) expert order}`` on the host: every layer's
    experts placed on the layer's chips by their load under ``rows`` (B,
    2L), the step's own ``[x0 ; xt]``, layer by layer, each layer fed
    what the layers before it give under their placement
    (``weights_mellum2.placement`` says why a cell places at all).
    Float32 at ``highest``, by the reference's own layer: the program is
    not asked."""
    eps = config["rms_norm_eps"]
    ranks = config["num_experts_published"] // config["num_experts"]

    @jax.jit
    def to_router(x, p):
        def one_row(row):
            mid = row + reference.attention(
                reference.rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                p["MultiHeadAttention_0"], config, "float32")
            h = reference.rms_norm(mid, p["RMSNorm_1"]["scale"], eps)
            chosen = reference.router(h, p["ExpertLayer_0"], config)[0]
            return mid, h, jnp.sum(chosen, axis=0)

        mid, h, loads = jax.lax.map(one_row, x)
        return mid, h, jnp.sum(loads, axis=0)

    @jax.jit
    def from_router(mid, h, e):
        return mid + jax.lax.map(
            lambda row: reference.experts(row, e, config, "float32"), h)

    x = reference.embed(params, jnp.asarray(rows), config)
    order = {}
    for i in range(config["n_layer"]):
        name = f"layer_{i}"
        mid, h, loads = to_router(x, params[name])
        order[name] = place_experts(jax.device_get(loads), ranks)
        e = params[name]["ExpertLayer_0"]
        x = from_router(mid, h, dict(
            e, router=jnp.take(e["router"], order[name], axis=1)))
    return order
