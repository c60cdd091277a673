"""Seeded weights of a ``mellum`` configuration, made by the benchmark and
handed to both sides, as ``chipbench/weights_qwen3next.py`` does for its
family: one jitted call builds the float32 parameter tree on the device
from ``--seed``, under the names ``models/transformer.py`` gives the
parameters of this family's block table, so the program takes it as its
parameters and the plain reference (``chipbench/refs/mellum2.py``) reads
the same arrays by name.  Nothing here imports the program.

Distribution (the configuration file lists it under ``assumed``): every
matrix N(0, 0.02) — the head, the projections, the router, the held
experts' stacks — but each branch's OUTPUT matrix (attention's ``out``,
``experts_down``) N(0, 0.02 / sqrt(2 x num_hidden_layers)), the published
depth's residual scaling, as ``weights_qwen3next.py`` has it; the norms'
scales (every layer norm, the final norm, the queries' and keys') 1 + 0.1
N(0,1): the family's RMSNorm multiplies by ``w`` itself.

The TABLE is N(0, 1), not N(0, 0.02) (:data:`TABLE_STD`), and the chip
said why (PERF.md section 6, PR 39).  At 0.02 a dimension the token's own
row is no larger than what an untrained attention row adds to every token
alike (a window's near-uniform mean of values: one vector), and than what
ONE sign-like AdamW step at lr 1e-6 moves that row's output by (4096
inputs x 1e-6, the same for every token).  Every token's router then sees
nearly the same input: all 16,384 tokens of a step choose the same 8 of 64
experts, a layer's held pairs are 0 or a multiple of 16,384 by the seed's
luck (read: 30 to 30,167 a layer, drifting through the window) and the
step's time, which follows its held pairs, spread 5.98% over six seeds.
At unit scale a token's routing is its own and the window no longer moves
it.  torch.nn.Embedding's own default is N(0, 1), and the first thing a
layer does to the stream is an RMSNorm, so the table's scale says only
how large the branches are beside a token's row: a hundredth here.

:func:`placement` says on which of the layer's chips an expert-parallel
load balancer would put each expert, and the cell's runner puts the
routers' columns in that order (:func:`with_placement`): this chip holds
the first rank's experts.  The tier-1 tests take the tree as :func:`make`
gives it.
"""

import math

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs import mellum2 as reference
from chipbench.weights import _nest


#: The table's standard deviation (the module's docstring says why not
#: the other matrices' 0.02).
TABLE_STD = 1.0


def kinds(config):
    """``layer_types`` of the ``n_layer`` layers kept."""
    return tuple(config["layer_types"][:config["n_layer"]])


def sizes(config):
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layers=config["n_layer"], kinds=kinds(config),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"], window=config["sliding_window"],
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


def place_experts(loads, ranks):
    """The experts in the order of a placement that evens the ranks'
    loads: ``ranks`` runs of ``len(loads) // ranks`` expert indices, rank
    0's first.  The heaviest expert left goes to the lightest rank with
    room (ties to the lower index), what a static expert-parallel load
    balancer does with a layer's measured loads."""
    loads = np.asarray(loads, np.float64)
    room = len(loads) // ranks
    held, total = [[] for _ in range(ranks)], np.zeros(ranks)
    for e in np.argsort(-loads, kind="stable"):
        r = min((r for r in range(ranks) if len(held[r]) < room),
                key=lambda r: (total[r], r))
        held[r].append(int(e))
        total[r] += loads[e]
    return np.asarray([e for rank in held for e in sorted(rank)], np.int32)


def placement(params, tokens, config):
    """``{layer name: (E,) expert order}`` on the host: every layer's
    experts placed on the layer's chips by their load under ``tokens``
    (B, S), layer by layer, each layer fed what the layers before it give
    under their placement (this chip's share of them, as the cell runs
    it).  A deployment that shares a layer's experts between chips places
    them by load; the seeded routers are not balanced, one id is a tenth
    of all Zipf(1) tokens, and whether its 8 experts are among 8 taken by
    their index is one draw a layer a seed: without a placement the cell
    measures that draw (a layer's held pairs 12,600-18,400 for 16,384
    expected, the step spreading 1.73% over six seeds: PERF.md section 6,
    PR 39).  A placement renames experts and changes no function of the
    model: the router's columns are put in its order, and the experts'
    own matrices are seeded alike.  Float32 at ``highest``, by the
    reference's own layer: the program is not asked."""
    eps = config["rms_norm_eps"]
    ranks = config["num_experts_published"] // config["num_experts"]

    @functools.partial(jax.jit, static_argnames="kind")
    def to_router(x, p, kind):
        def one_row(row):
            mid = row + reference.attention(
                reference.rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                p["MultiHeadAttention_0"], kind, config, "float32")
            h = reference.rms_norm(mid, p["RMSNorm_1"]["scale"], eps)
            chosen = reference.router(h, p["ExpertLayer_0"], config)[0]
            return mid, h, jnp.sum(chosen, axis=0)

        mid, h, loads = jax.lax.map(one_row, x)
        return mid, h, jnp.sum(loads, axis=0)

    @jax.jit
    def from_router(mid, h, e):
        return mid + jax.lax.map(
            lambda row: reference.experts(row, e, config, "float32"), h)

    x = reference.embed(params, jnp.asarray(tokens), config)
    order = {}
    for i, kind in enumerate(kinds(config)):
        name = f"layer_{i}"
        mid, h, loads = to_router(x, params[name], kind)
        order[name] = place_experts(jax.device_get(loads), ranks)
        e = params[name]["ExpertLayer_0"]
        x = from_router(mid, h, dict(
            e, router=jnp.take(e["router"], order[name], axis=1)))
    return order


def with_placement(params, order, sharding=None):
    """``params`` with the router's columns of every layer ``order``
    names in that order, each a buffer of its own."""
    return {name: layer if name not in order else dict(
        layer, ExpertLayer_0=dict(
            layer["ExpertLayer_0"], router=jax.device_put(
                jnp.take(layer["ExpertLayer_0"]["router"],
                         jnp.asarray(order[name]), axis=1), sharding)))
            for name, layer in params.items()}
