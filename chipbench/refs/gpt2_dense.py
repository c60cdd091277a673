"""Plain reference of the dense GPT-2-style decoder the configuration
runs: forward, loss, gradients and AdamW in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, no batching
tricks.  It imports nothing of the program and reads the weight tree
``chipbench/weights.py`` makes, by name.

Written from the published GPT-2 block (pre-LayerNorm residual block:
x + Attn(LN(x)), then x + MLP(LN(x)); final LayerNorm; output head tied to
the token table), with the configurations' departures, each as the program
has it: sinusoidal additive positions, no biases on the matrices,
tanh-approximated GELU, LayerNorm epsilon 1e-6, attention scaled by
1/sqrt(d_head).

``precision`` selects how matmul operands are rounded: ``float32`` is the
reference proper; ``bfloat16`` and ``fp8_e4m3`` are the controls of "How
correct is decided": the same mathematics with every matmul operand
rounded to that type (fp8: 4 exponent and 3 mantissa bits with one scale
a tensor, amax/240), products
accumulated in float32.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6
PRECISIONS = ("float32", "bfloat16", "fp8_e4m3")


def _round(x, precision):
    """Round to ``precision`` going forward; the gradient passes straight
    through (a cast would round the cotangent to the type too, and flush a
    small gradient to zero in fp8)."""
    if precision == "float32":
        return x
    # lax.reduce_precision, not a pair of casts: XLA removes a cast to a
    # narrower type and back (read on the chip, PR 23: a bfloat16 control
    # made of casts differed from float32 by 1e-7).
    if precision == "bfloat16":
        q = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif precision == "fp8_e4m3":
        # 4 exponent and 3 mantissa bits, largest finite value 240
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
        q = jax.lax.reduce_precision(
            x / scale, exponent_bits=4, mantissa_bits=3) * scale
    else:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def positions(n, d_model):
    pos = np.arange(n)[:, None]
    div = np.exp(np.arange(0, d_model, 2) * (-math.log(10000.0) / d_model))
    pe = np.zeros((n, d_model), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return pe


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, precision):
    """One pre-LN block on ``x`` (B, S, d)."""
    att = p["MultiHeadAttention_0"]
    h = layer_norm(x, p["LayerNorm_0"])
    q = _mm("bsd,dhk->bshk", h, att["query"]["kernel"], precision)
    k = _mm("bsd,dhk->bshk", h, att["key"]["kernel"], precision)
    v = _mm("bsd,dhk->bshk", h, att["value"]["kernel"], precision)
    S, d_head = x.shape[1], q.shape[-1]
    scores = _mm("bqhk,bshk->bhqs", q, k, precision) / math.sqrt(d_head)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("bhqs,bshk->bqhk", probs, v, precision)
    x = x + _mm("bqhk,hkd->bqd", ctx, att["out"]["kernel"], precision)
    h = layer_norm(x, p["LayerNorm_1"])
    ff = p["FeedForward_0"]
    h = gelu(_mm("bsd,df->bsf", h, ff["wi"]["kernel"], precision))
    return x + _mm("bsf,fd->bsd", h, ff["wo"]["kernel"], precision)


def hidden(params, tokens, precision="float32", remat=False):
    """Final-norm hidden states (B, S, d) of int tokens (B, S)."""
    table = params["embed"]["embedding"]
    x = table[tokens] + positions(tokens.shape[1], table.shape[1])[None]
    n_layers = sum(1 for k in params if k.startswith("layer_"))
    # Backward keeps each block's projections and recomputes only its
    # attention scores and elementwise parts, so that one block of rows
    # fits beside the float32 parameters, moments and gradients.
    fn = jax.checkpoint(
        block, static_argnums=(2,),
        policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    ) if remat else block
    for i in range(n_layers):
        x = fn(x, params[f"layer_{i}"], precision)
    return layer_norm(x, params["final_norm"])


def loss_sum(params, tokens, labels, precision="float32"):
    """Sum over tokens of the softmax cross-entropy against ``labels``."""
    h = hidden(params, tokens, precision, remat=True)
    z = _mm("bsd,vd->bsv", h, params["embed"]["embedding"], precision)
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


# --------------------------------------------------------------- training

def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


@functools.partial(jax.jit, static_argnames=("precision",),
                   donate_argnums=(0,))
def _accumulate(acc, params, tokens, labels, precision):
    total, grads = jax.value_and_grad(loss_sum)(
        params, tokens, labels, precision)
    return jax.tree.map(jnp.add, acc, grads), total


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(params, m, v, grads, t, lr, wd, b1, b2, eps):
    def one(p, m, v, g):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        mhat = m / (1.0 - b1 ** t)
        vhat = v / (1.0 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v

    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda t3: t3[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def train_steps(make_params, batches, opt, precision="float32",
                block_rows=1, place=lambda x: x):
    """Follow ``len(batches)`` AdamW steps from seeded weights.

    ``make_params()`` builds the float32 tree (called twice: the start and
    again at the end to difference against).  Each batch is walked in
    blocks of ``block_rows`` rows, gradients of the summed loss
    accumulated, so one block's activations are all that is alive.
    ``place`` puts a block on the devices (rows over chips on four).

    Returns host numbers only: the loss of each step, the norm of each
    leaf of the first mean gradient, and the norm of each leaf's change
    after the last step."""
    params = make_params()
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    m, v = zeros(), zeros()
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        acc, total = zeros(), 0.0
        for r in range(0, tokens.shape[0], block_rows):
            acc, part = _accumulate(
                acc, params, place(tokens[r:r + block_rows]),
                place(labels[r:r + block_rows]), precision)
            total += float(part)
        n = float(tokens.size)
        losses.append(total / n)
        grads = jax.tree.map(lambda g: g / n, acc)
        del acc
        if t == 1:
            grad_norms = jax.device_get(jax.jit(_leaf_norms)(grads))
        params, m, v = _adamw(
            params, m, v, grads, float(t), opt["learning_rate"],
            opt["weight_decay"], opt["b1"], opt["b2"], opt["eps"])
        del grads
    del m, v
    start = make_params()
    delta = jax.device_get(jax.jit(lambda a, b: _leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}
