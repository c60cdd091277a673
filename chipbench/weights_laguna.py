"""Seeded weights of a ``laguna`` configuration, made by the benchmark and
handed to both sides, as ``chipbench/weights_mellum2.py`` does for its
family: one jitted call builds the float32 parameter tree on the device
from ``--seed``, under the names ``models/transformer.py`` gives the
parameters of this family's block table, so the program takes it as its
parameters and the plain reference (``chipbench/refs/laguna.py``) reads
the same arrays by name.  Nothing here imports the program.

Distribution (the configuration file lists it under ``assumed``): as
``weights_mellum2.py`` and for its reasons — every matrix N(0, 0.02) (the
head, the projections, the gates, the routers, the dense FFN, the held
and the shared experts), each branch's OUTPUT matrix (attention's
``out``, the dense FFN's and the shared expert's ``wo``,
``experts_down``) N(0, 0.02 / sqrt(2 x num_hidden_layers)), the norms'
scales 1 + 0.1 N(0,1), the TABLE N(0, 1) — and the routers' correction
bias zero: it is a balancing controller's state, which a seeded run has
not stepped.

:func:`placement` says on which of the layer's chips an expert-parallel
load balancer would put each expert, and the cell's runner puts the
routers' columns (and the bias) in that order
(:func:`with_placement`): this chip holds the first rank's experts.
The tier-1 tests take the tree as :func:`make` gives it.
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.refs import laguna as reference
from chipbench.weights import _nest
from chipbench.weights_mellum2 import TABLE_STD, place_experts

ATT, FFN, EXPERTS = reference.ATT, reference.FFN, reference.EXPERTS


def kept(config, key):
    """The ``n_layer`` first entries of one of the config's lists a
    layer."""
    return tuple(config[key][:config["n_layer"]])


def sizes(config):
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layers=config["n_layer"], kinds=kept(config, "layer_types"),
        heads=kept(config, "num_attention_heads_per_layer"),
        ffns=kept(config, "mlp_layer_types"),
        kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"], window=config["sliding_window"],
        d_ff=config["intermediate_size"],
        experts=config["num_experts_published"],
        held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"])


def shapes(config):
    """name path -> shape, in the program's layout."""
    z = sizes(config)
    d, D = z["d"], z["d_head"]
    out = {("embed", "embedding"): (z["vocab"], d),
           ("final_norm", "scale"): (d,), ("lm_head",): (z["vocab"], d)}
    for i in range(z["layers"]):
        L, H = f"layer_{i}", z["heads"][i]
        out[(L, "RMSNorm_0", "scale")] = (d,)
        out[(L, "RMSNorm_1", "scale")] = (d,)
        out[(L, ATT, "query", "kernel")] = (d, H, D)
        out[(L, ATT, "key", "kernel")] = (d, z["kv_heads"], D)
        out[(L, ATT, "value", "kernel")] = (d, z["kv_heads"], D)
        out[(L, ATT, "gate", "kernel")] = (d, H)
        out[(L, ATT, "out", "kernel")] = (H, D, d)
        if z["ffns"][i] == "dense":
            out[(L, FFN, "wi", "kernel")] = (d, 2 * z["d_ff"])
            out[(L, FFN, "wo", "kernel")] = (z["d_ff"], d)
            continue
        out[(L, EXPERTS, "router")] = (d, z["experts"])
        out[(L, EXPERTS, "router_bias")] = (z["experts"],)
        for name in ("experts_gate", "experts_up", "experts_down"):
            out[(L, EXPERTS, name)] = (z["held"], z["d_expert"], d)
        out[(L, EXPERTS, "shared", "wi", "kernel")] = (d, 2 * z["d_shared"])
        out[(L, EXPERTS, "shared", "wo", "kernel")] = (z["d_shared"], d)
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
            if path[-1] == "router_bias":
                flat[path] = jnp.zeros(table[path], jnp.float32)
                continue
            noise = jax.random.normal(
                jax.random.fold_in(key, i), table[path], jnp.float32)
            if path[-1] == "scale":
                flat[path] = 1.0 + 0.1 * noise
            elif path == ("embed", "embedding"):
                flat[path] = TABLE_STD * noise
            elif path[-1] == "experts_down" or path[-2:] in (
                    ("out", "kernel"), ("wo", "kernel")):
                flat[path] = 0.02 * resid * noise
            else:
                flat[path] = 0.02 * noise
        return _nest(flat)

    # threefry keys take 32 bits; the driver's seeds are wider.
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=sharding)(key)


def placement(params, tokens, config):
    """``{layer name: (E,) expert order}`` on the host: every sparse
    layer's experts placed on the layer's chips by their load under
    ``tokens`` (B, S), layer by layer, each layer fed what the layers
    before it give under their placement (this chip's share of them, as
    the cell runs it): ``weights_mellum2.placement``'s walk and reasons,
    by this family's reference (float32 at ``highest``; the program is
    not asked)."""
    eps = config["rms_norm_eps"]
    ranks = config["num_experts_published"] // config["num_experts"]

    @functools.partial(jax.jit, static_argnames="kind")
    def to_router(x, p, kind):
        def one_row(row):
            mid = row + reference.attention(
                reference.rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                p[ATT], kind, config, "float32")
            h = reference.rms_norm(mid, p["RMSNorm_1"]["scale"], eps)
            if EXPERTS not in p:
                return (mid + reference.dense_ffn(h, p[FFN], "float32"), h,
                        jnp.zeros((), jnp.int32))
            chosen = reference.router(h, p[EXPERTS], config)[0]
            return mid, h, jnp.sum(chosen, axis=0)

        mid, h, loads = jax.lax.map(one_row, x)
        return mid, h, jnp.sum(loads, axis=0)

    @jax.jit
    def from_router(mid, h, e):
        return mid + jax.lax.map(
            lambda row: reference.experts(row, e, config, "float32"), h)

    x = reference.embed(params, jnp.asarray(tokens), config)
    order = {}
    for i, kind in enumerate(kept(config, "layer_types")):
        name = f"layer_{i}"
        x, h, loads = to_router(x, params[name], kind)
        if EXPERTS not in params[name]:
            continue
        order[name] = place_experts(jax.device_get(loads), ranks)
        x = from_router(x, h, _placed(params[name][EXPERTS], order[name]))
    return order


def _placed(e, order, sharding=None):
    """An expert layer's tree with the router's columns and the bias in
    ``order``, each a buffer of its own."""
    order = jnp.asarray(order)
    return dict(
        e, router=jax.device_put(jnp.take(e["router"], order, axis=1),
                                 sharding),
        router_bias=jax.device_put(jnp.take(e["router_bias"], order),
                                   sharding))


def with_placement(params, order, sharding=None):
    """``params`` with the router's columns (and bias) of every layer
    ``order`` names in that order."""
    return {name: layer if name not in order else dict(
        layer, **{EXPERTS: _placed(layer[EXPERTS], order[name], sharding)})
            for name, layer in params.items()}
