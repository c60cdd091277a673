"""Plain reference of the ``granitemoehybrid`` decoder (dense members:
Mamba-2 mixers with a grouped-query attention layer among every few):
forward, loss, gradients and AdamW in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, no batching
tricks.  It imports nothing of the program and reads the weight tree
``chipbench/weights_hybrid.py`` makes, by name; the sizes and scalars come
from the configuration's published keys.

Written from the published model (``config.json`` keys in quotes):

* ``x = embedding_multiplier * E[token]``; no positions (``nope``);
* every layer ``x += residual_multiplier * mixer(RMSNorm(x))`` then
  ``x += residual_multiplier * W_out(silu(a) * b)``, ``[a | b] = W_in
  RMSNorm(x)``;
* attention: grouped-query, causal, softmax scale
  ``attention_multiplier``;
* Mamba-2: ``[z | xBC | dt] = W_in h``; ``xBC = silu(causal depthwise
  conv(xBC) + bias)``; ``[x | B | C] = xBC``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head ``H_t = exp(dt_t A) H_(t-1) +
  dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``; ``y = RMSNorm(y *
  silu(z))`` over all channels; ``W_out y``;
* ``logits = RMSNorm(x) E^T / logits_scaling`` (tied table).

The state-space scan is the recurrence itself, one ``lax.scan`` step a
token — the program computes the chunked dual form, so the two are
derived independently.

Departures, all to make one 8192-token row fit beside the float32
parameters, moments and gradients, none changing the mathematics: each
layer is rematerialised in backward (``jax.checkpoint``); the scan over
time is nested (blocks of steps, each block rematerialised) so backward
keeps one state a block and not one a token; attention walks its queries
and the head its tokens in row blocks (``lax.map``, each block
rematerialised); AdamW's moments wait on the host between updates.

``precision`` is ``gpt2_dense``'s: ``float32`` is the reference proper,
``bfloat16`` and ``fp8_e4m3`` round every matrix-product operand
(projections, attention, and the scan's ``dt x``, ``B`` and ``C``).
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.refs.gpt2_dense import _adamw, _leaf_norms, _mm, _round

ATTENTION_ROWS = 256     # queries a block
HEAD_ROWS = 1024         # tokens a block of the output head
SCAN_BLOCK = 128         # time steps a rematerialised block


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _row_blocks(fn, arrays, rows):
    """``fn(*arrays)`` over blocks of ``rows`` of the arrays' shared
    leading axis, one block after another (``lax.map``), each recomputed
    in backward; whole where the axis is no longer than ``rows`` or no
    multiple of it."""
    n = arrays[0].shape[0]
    k = n // rows if n > rows and not n % rows else 1
    blocks = tuple(a.reshape((k, n // k) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(jax.checkpoint(lambda blk: fn(*blk)), blocks)
    return out.reshape((n,) + out.shape[2:])


def attention(h, p, config, precision):
    """Grouped-query causal attention of one row ``h`` (S, d)."""
    att = p["MultiHeadAttention_0"]
    q = _mm("sd,dhk->shk", h, att["query"]["kernel"], precision)
    k = _mm("sd,dhk->shk", h, att["key"]["kernel"], precision)
    v = _mm("sd,dhk->shk", h, att["value"]["kernel"], precision)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    keys = jnp.arange(h.shape[0])

    def block(qb, at):
        scores = _mm("qhk,shk->hqs", qb, k, precision) * config[
            "attention_multiplier"]
        scores = jnp.where(keys[None, None, :] <= at[None, :, None],
                           scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    ctx = _row_blocks(block, (q, keys), ATTENTION_ROWS)
    return _mm("qhk,hkd->qd", ctx, att["out"]["kernel"], precision)


def recurrence(x, dt, A, B, C, D):
    """``y_t = H_t C_t + D x_t``, ``H_t = exp(dt_t A) H_(t-1) + dt_t x_t
    B_t^T``, one step a token.  ``x`` (S, H, P), ``dt`` (S, H), ``A``,
    ``D`` (H,), ``B``, ``C`` (S, G, N), head ``h`` reading group
    ``h // (H / G)``."""
    S, H, P = x.shape
    G, N = B.shape[1:]

    def step(state, now):
        xt, dtt, Bt, Ct = now
        Bh = jnp.repeat(Bt, H // G, axis=0)               # (H, N)
        Ch = jnp.repeat(Ct, H // G, axis=0)
        state = (jnp.exp(dtt * A)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :])
        y = jnp.sum(state * Ch[:, None, :], axis=-1) + D[:, None] * xt
        return state, y

    block = math.gcd(S, SCAN_BLOCK)
    blocks = jax.tree.map(
        lambda a: a.reshape((S // block, block) + a.shape[1:]),
        (x, dt, B, C))
    _, y = jax.lax.scan(
        jax.checkpoint(lambda state, blk: jax.lax.scan(step, state, blk)),
        jnp.zeros((H, P, N), jnp.float32), blocks)
    return y.reshape(S, H, P)


def mamba(h, p, config, precision):
    """The Mamba-2 mixer of one row ``h`` (S, d)."""
    m = p["Mamba2Mixer_0"]
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    K, inner = config["mamba_d_conv"], H * P
    S = h.shape[0]
    proj = _mm("sd,de->se", h, m["in_proj"]["kernel"], precision)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * G * N], axis=-1)
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = m["conv_bias"]
    for j in range(K):           # tap K-1 weighs the current token
        conv = conv + padded[j:j + S] * m["conv_kernel"][j]
    x, B, C = jnp.split(jax.nn.silu(conv), [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y = recurrence(
        _round(x, precision).reshape(S, H, P), dt, -jnp.exp(m["A_log"]),
        _round(B, precision).reshape(S, G, N),
        _round(C, precision).reshape(S, G, N), m["D"])
    y = rms_norm(y.reshape(S, inner) * jax.nn.silu(z), m["norm"]["scale"],
                 config["rms_norm_eps"])
    return _mm("se,ed->sd", y, m["out_proj"]["kernel"], precision)


def layer(x, p, config, precision):
    """One layer on one row ``x`` (S, d); the mixer is the one whose
    parameters the layer holds."""
    rm, eps = config["residual_multiplier"], config["rms_norm_eps"]
    mixer = mamba if "Mamba2Mixer_0" in p else attention
    x = x + rm * mixer(rms_norm(x, p["RMSNorm_0"]["scale"], eps), p,
                       config, precision)
    ff = p["GatedFeedForward_0"]
    a, b = jnp.split(_mm("sd,df->sf", rms_norm(
        x, p["RMSNorm_1"]["scale"], eps), ff["wi"]["kernel"], precision),
        2, axis=-1)
    return x + rm * _mm("sf,fd->sd", jax.nn.silu(a) * b,
                        ff["wo"]["kernel"], precision)


def layers(params, x, config, precision="float32"):
    """The residual stream (B, S, d) through every ``layer_<i>`` of
    ``params`` in order, a row at a time."""
    fn = jax.checkpoint(lambda row, p: layer(row, p, config, precision))
    n = sum(1 for k in params if k.startswith("layer_"))

    def one_row(row):
        for i in range(n):
            row = fn(row, params[f"layer_{i}"])
        return row

    return jax.lax.map(one_row, x)


def embed(params, tokens, config):
    return config["embedding_multiplier"] * params["embed"]["embedding"][
        tokens]


def logits(params, x, config, precision="float32"):
    """(B, S, V) logits of the residual stream ``x`` after the last
    layer."""
    h = rms_norm(x, params["final_norm"]["scale"], config["rms_norm_eps"])
    return _mm("bsd,vd->bsv", h, params["embed"]["embedding"],
               precision) / config["logits_scaling"]


def loss_sum(params, tokens, labels, config, precision="float32"):
    """Sum over tokens of the softmax cross-entropy against ``labels``."""
    x = layers(params, embed(params, tokens, config), config, precision)

    def head(xb, yb):
        z = logits(params, xb[None], config, precision)[0]
        picked = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    return jnp.sum(_row_blocks(
        head, (x.reshape(-1, x.shape[-1]), labels.reshape(-1)), HEAD_ROWS))


# --------------------------------------------------------------- training

def train_steps(make_params, batches, config, precision="float32",
                block_rows=1, place=lambda x: x):
    """Follow ``len(batches)`` AdamW steps from seeded weights, as
    ``gpt2_dense.train_steps`` does: each batch walked in blocks of
    ``block_rows`` rows, the summed loss's gradients accumulated.  Returns
    host numbers: the loss of each step, the norm of each leaf of the
    first mean gradient, the norm of each leaf's change after the last
    step."""
    opt = config["optimizer"]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, params, tokens, labels):
        total, grads = jax.value_and_grad(loss_sum)(
            params, tokens, labels, config, precision)
        return jax.tree.map(jnp.add, acc, grads), total

    params = make_params()
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    where = jax.tree.map(lambda x: x.sharding, params)
    m = v = None
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        acc, total = zeros(), 0.0
        for r in range(0, tokens.shape[0], block_rows):
            acc, part = accumulate(
                acc, params, place(tokens[r:r + block_rows]),
                place(labels[r:r + block_rows]))
            total += float(part)
        n = float(tokens.size)
        losses.append(total / n)
        grads = jax.tree.map(lambda g: g / n, acc)
        del acc
        if t == 1:
            grad_norms = jax.device_get(jax.jit(_leaf_norms)(grads))
        # The moments wait on the host while a gradient is made: with
        # them a row's activations would not fit beside the parameters
        # and the gradient at the cell's size (16 B a parameter).
        m, v = (zeros(), zeros()) if t == 1 else jax.device_put(
            (m, v), (where, where))
        params, m, v = _adamw(
            params, m, v, grads, float(t), opt["learning_rate"],
            opt["weight_decay"], opt["b1"], opt["b2"], opt["eps"])
        del grads
        if t < len(batches):
            m, v = jax.device_get((m, v))
    del m, v
    start = make_params()
    delta = jax.device_get(jax.jit(lambda a, b: _leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}
