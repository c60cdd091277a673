"""Seeded weights of a ``zaya`` configuration, made by the benchmark and
handed to both sides, as ``chipbench/weights_nemotron.py`` does for the
``nemotron_h`` tree: one jitted call builds the float32 parameter tree on
the device from ``--seed``, under the names ``models/transformer.py``
gives the parameters of this family's block table, so the program takes
it as its parameters and the plain reference (``chipbench/refs/zaya1.py``)
reads the same arrays by name.  Nothing here imports the program.

Distribution (the configuration file lists it under ``assumed``): the
projections, the table and the held experts' stacked matrices N(0, 0.02),
but each branch's OUTPUT matrix (the mixer's ``out``, the experts'
``experts_down``) N(0, 0.02 / sqrt(2 x num_hidden_layers)), the published
depth's residual scaling as ``chipbench/weights.py`` has it for the GPT-2
tree — at 0.02 throughout, what the first mixer adds (an average over a
token's past) is ten times the token's own embedding, every token's
stream looks alike, and the routers send 85% of a batch to one expert
(my chip run, PR 32); norm scales 1 + 0.1 N(0,1); the depthwise
convolution's taps U(-0.5, 0.5) and both convolutions' biases 0.1 N(0,1); the grouped convolution's
matrices N(0, 1/sqrt(taps x d_head)), so that what the convolutions add
to a query is of the size of the q-k mean beside it; the keys' learned
scale 1 + 0.1 N(0,1); the router's down-projection N(0, 0.02), its
``gamma`` 0.5 + 0.1 N(0,1), its three MLP matrices N(0, 1/sqrt(r)) — at
0.02 the logits would be some 1e-2 wide, every probability 1/16 and the
choice the bias's alone — and its balancing bias 0.002 N(0,1): small
beside the probabilities' spread, of the size of the gap between the
first and the second, so that it takes part in the choice.

:func:`balanced_biases` says where a balancing controller would hold the
balancing biases, and the cell's runner puts them there
(:func:`with_biases`); the tier-1 tests take the tree as :func:`make`
gives it.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.refs import zaya1 as reference
from chipbench.weights import _nest


def sizes(config):
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    D = config["head_dim"]
    rope = config["rope_parameters"]["hybrid"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"], heads=hq,
        kv_heads=hkv, d_head=D, layers=config["n_layer"],
        taps0=config["cca_time0"], taps1=config["cca_time1"],
        conv_dim=(hq + hkv) * D,
        rotary_dim=int(D * rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        experts=config["num_experts_published"],
        held_first=config["experts_held_first"], held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_router=config["router_hidden_size"])


def shapes(config):
    """name path -> shape, in the program's layout."""
    z = sizes(config)
    d, D, r = z["d"], z["d_head"], z["d_router"]
    hq, hkv, C = z["heads"], z["kv_heads"], z["conv_dim"]
    out = {("embed", "embedding"): (z["vocab"], d),
           ("final_norm", "scale"): (d,)}
    for i in range(z["layers"]):
        L, m, e = f"layer_{i}", "CCAMixer_0", "ExpertLayer_0"
        out[(L, "RMSNorm_0", "scale")] = (d,)
        out[(L, "RMSNorm_1", "scale")] = (d,)
        out[(L, m, "query", "kernel")] = (d, hq * D)
        out[(L, m, "key", "kernel")] = (d, hkv * D)
        out[(L, m, "value_now", "kernel")] = (d, hkv * D // 2)
        out[(L, m, "value_before", "kernel")] = (d, hkv * D // 2)
        out[(L, m, "conv0_kernel")] = (z["taps0"], C)
        out[(L, m, "conv0_bias")] = (C,)
        out[(L, m, "conv1_kernel")] = (z["taps1"], hq + hkv, D, D)
        out[(L, m, "conv1_bias")] = (C,)
        out[(L, m, "temp")] = (hkv,)
        out[(L, m, "out", "kernel")] = (hq * D, d)
        out[(L, e, "router_down")] = (d, r)
        out[(L, e, "router_gamma")] = (r,)
        out[(L, e, "router_norm")] = (r,)
        out[(L, e, "router_w1")] = (r, r)
        out[(L, e, "router_w2")] = (r, r)
        out[(L, e, "router_w3")] = (r, z["experts"])
        out[(L, e, "router_bias")] = (z["experts"],)
        for name in ("experts_gate", "experts_up", "experts_down"):
            out[(L, e, name)] = (z["held"], z["d_expert"], d)
    return out


def n_params(config):
    return sum(math.prod(shape) for shape in shapes(config).values())


def make(config, seed, sharding=None):
    """The float32 parameter tree, on the device, in one jitted call."""
    table = shapes(config)
    paths = sorted(table)
    z = sizes(config)
    conv1 = 1.0 / math.sqrt(z["taps1"] * z["d_head"])
    mlp = 1.0 / math.sqrt(z["d_router"])
    resid = (2.0 * config["num_hidden_layers"]) ** -0.5

    def build(key):
        flat = {}
        for i, path in enumerate(paths):
            k = jax.random.fold_in(key, i)
            name, shape = path[-1], table[path]
            if name == "conv0_kernel":
                flat[path] = jax.random.uniform(
                    k, shape, jnp.float32) - 0.5
                continue
            noise = jax.random.normal(k, shape, jnp.float32)
            if name in ("scale", "temp", "router_norm"):
                flat[path] = 1.0 + 0.1 * noise
            elif name == "router_gamma":
                flat[path] = 0.5 + 0.1 * noise
            elif name in ("conv0_bias", "conv1_bias"):
                flat[path] = 0.1 * noise
            elif name == "conv1_kernel":
                flat[path] = conv1 * noise
            elif name in ("router_w1", "router_w2", "router_w3"):
                flat[path] = mlp * noise
            elif name == "router_bias":
                flat[path] = 0.002 * noise
            elif name == "experts_down" or path[-2:] == ("out", "kernel"):
                flat[path] = 0.02 * resid * noise
            else:
                flat[path] = 0.02 * noise
        return _nest(flat)

    # threefry keys take 32 bits; the driver's seeds are wider.
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=sharding)(key)


#: Steps of :func:`spread_evenly`, and its first and last step widths.
BALANCE_STEPS, BALANCE_RATES = 600, (0.05, 0.0005)


def spread_evenly(p):
    """A bias ``b`` (E,) under which ``argmax(p + b)`` sends an equal
    share of the rows of ``p`` (T, E) to every expert, as nearly as a
    bias can: each step lowers the bias of an expert over its share and
    raises that of one under it, by steps that narrow — the fixed point
    of any controller that balances the load by the bias alone."""
    E = p.shape[-1]
    first, last = BALANCE_RATES

    def step(i, b):
        load = jnp.mean(jax.nn.one_hot(jnp.argmax(p + b, axis=-1), E),
                        axis=0)
        rate = first * (last / first) ** (i / (BALANCE_STEPS - 1.0))
        return b - rate * (load - 1.0 / E)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((E,), jnp.float32))


def balanced_biases(params, tokens, config):
    """``{layer name: (E,) bias}`` on the host: every layer's balancing
    bias where a balancing controller would hold it for ``tokens``
    (B, S): layer by layer, the bias that spreads this batch evenly over
    ALL the published experts, each layer fed what the layers before it
    give under their new biases.  The source trains with such a
    controller (the report's; the cell's loop runs an assumed one from
    here on); without one a seeded top-1 router sends a fifth or four
    fifths of a batch to the held half by the luck of the seed, and the
    cell would measure that luck.  Float32 at ``highest``, by the
    reference's own layer: the program is not asked."""
    eps = config["rms_norm_eps"]

    def run(params, tokens):
        x = reference.embed(params, tokens)
        state = reference.zero_state(x, config)
        biases = {}
        for i in range(config["n_layer"]):
            name = f"layer_{i}"
            p = params[name]

            def probabilities(args, p=p):
                row, r = args
                mid = row + reference.cca(
                    reference.rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                    p["CCAMixer_0"], config, "float32")
                return reference.router_probabilities(
                    reference.rms_norm(mid, p["RMSNorm_1"]["scale"], eps),
                    r, p["ExpertLayer_0"], config)[0]

            probs = jax.lax.map(probabilities, (x, state))
            biases[name] = spread_evenly(
                probs.reshape(-1, probs.shape[-1]))
            p = dict(p, ExpertLayer_0=dict(p["ExpertLayer_0"],
                                           router_bias=biases[name]))
            x, state = jax.lax.map(
                lambda args, p=p: reference.layer(
                    args[0], args[1], p, config, "float32"), (x, state))
        return biases

    return jax.device_get(jax.jit(run)(params, tokens))


def with_biases(params, biases, sharding=None):
    """``params`` with the balancing biases of the layers ``biases``
    names put in place of the seeded ones, each a buffer of its own."""
    return {name: layer if name not in biases else dict(
        layer, ExpertLayer_0=dict(
            layer["ExpertLayer_0"], router_bias=jax.device_put(
                jnp.asarray(biases[name]), sharding)))
            for name, layer in params.items()}
