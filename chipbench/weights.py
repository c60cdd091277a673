"""Seeded weights, made by the benchmark and handed to both sides.

One jitted call builds the whole float32 parameter tree on the device from
``--seed``, laid out under the names ``models/transformer.py`` gives its
parameters, so the program takes it as its parameters and the plain
reference (``chipbench/refs``) reads the very same arrays by name.  Nothing
here imports the program.

Distribution (the configuration files list it under ``assumed``): every
matrix N(0, 0.02), the two residual output matrices (``out``, ``wo``)
scaled by 1/sqrt(2 n_layer) as GPT-2 does, LayerNorm scale 1 + 0.1 N(0,1)
and bias 0.1 N(0,1) so that a fault in a norm shows.
"""

import jax
import jax.numpy as jnp


def sizes(config):
    d, h = config["n_embd"], config["n_head"]
    return dict(vocab=config["vocab_size"], d=d, heads=h, d_head=d // h,
                d_ff=config["n_inner"], layers=config["n_layer"])


def shapes(config):
    """name path -> shape, in the program's layout."""
    z = sizes(config)
    d, h, dh, f = z["d"], z["heads"], z["d_head"], z["d_ff"]
    out = {("embed", "embedding"): (z["vocab"], d),
           ("final_norm", "scale"): (d,), ("final_norm", "bias"): (d,)}
    for i in range(z["layers"]):
        L = f"layer_{i}"
        att = "MultiHeadAttention_0"
        out[(L, "LayerNorm_0", "scale")] = (d,)
        out[(L, "LayerNorm_0", "bias")] = (d,)
        out[(L, "LayerNorm_1", "scale")] = (d,)
        out[(L, "LayerNorm_1", "bias")] = (d,)
        for name in ("query", "key", "value"):
            out[(L, att, name, "kernel")] = (d, h, dh)
        out[(L, att, "out", "kernel")] = (h, dh, d)
        out[(L, "FeedForward_0", "wi", "kernel")] = (d, f)
        out[(L, "FeedForward_0", "wo", "kernel")] = (f, d)
    return out


def n_params(config):
    total = 0
    for shape in shapes(config).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def _nest(flat):
    tree = {}
    for path, leaf in flat.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    return tree


def flatten(tree, prefix=()):
    """Nested dict -> {name path: leaf}, sorted by path."""
    out = {}
    for key in sorted(tree):
        value = tree[key]
        if isinstance(value, dict):
            out.update(flatten(value, prefix + (key,)))
        else:
            out[prefix + (key,)] = value
    return out


def leaf_name(path):
    return "/".join(path)


def make(config, seed, sharding=None):
    """The float32 parameter tree, on the device, in one jitted call."""
    table = shapes(config)
    paths = sorted(table)
    resid = (2.0 * config["n_layer"]) ** -0.5

    def build(key):
        flat = {}
        for i, path in enumerate(paths):
            k = jax.random.fold_in(key, i)
            noise = jax.random.normal(k, table[path], jnp.float32)
            if path[-1] == "scale":
                flat[path] = 1.0 + 0.1 * noise
            elif path[-1] == "bias":
                flat[path] = 0.1 * noise
            elif len(path) > 2 and path[-2] in ("out", "wo"):
                flat[path] = 0.02 * resid * noise
            else:
                flat[path] = 0.02 * noise
        return _nest(flat)

    # threefry keys take 32 bits; the driver's seeds are wider.
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=sharding)(key)
