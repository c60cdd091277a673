"""Plain reference of the ``nemotron_h`` decoder (Mamba-2 mixers, sparse
experts and a few grouped-query attention layers, ONE of them a layer):
forward, loss, gradients and AdamW in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, no sorting, no
batching tricks.  It imports nothing of the program and reads the weight
tree ``chipbench/weights_nemotron.py`` makes, by name; the sizes and
scalars come from the configuration's published keys.

Written from the published model (``config.json`` keys in quotes):

* ``x = E[token]``; no positions (the family's attention applies no rotary
  embedding), no multipliers;
* every layer ``x += f(RMSNorm(x))``, eps ``layer_norm_epsilon``, ``f``
  the ONE part ``hybrid_override_pattern`` names for the layer;
* ``M``, Mamba-2: ``[z | xBC | dt] = W_in h`` (``mamba_num_heads x
  mamba_head_dim`` channels); ``xBC = silu(causal depthwise conv(xBC) +
  bias)``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  ``H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T``, ``y_t = H_t C_t + D
  x_t``, the heads in ``n_groups`` groups that share B and C; ``y =
  RMSNorm(y * silu(z))`` over each group's channels apart; ``W_out y``;
* ``*``, attention: grouped-query, causal, heads of ``head_dim``, scale
  ``1/sqrt(head_dim)``;
* ``E``, experts: ``s = sigmoid(W_r h)`` over all ``n_routed_experts``
  published, in float32 whatever ``precision`` says; the
  ``num_experts_per_tok`` largest of ``s + b`` chosen, one after another,
  the lowest index on a tie; ``w = routed_scaling_factor * s / (sum of
  the chosen s + 1e-20)`` on the chosen and 0 elsewhere
  (``norm_topk_prob``); ``f(h) = sum_e
  w_e W_down,e relu(W_up,e h)^2 + W_down,s relu(W_up,s h)^2``, the sum
  over the experts HELD (``experts_held_first`` and the file's
  ``n_routed_experts`` of them): every held expert is applied to every
  token and its result taken times the token's weight for it, zero for
  most — a dense masked sum, where the program sorts the pairs and
  multiplies row groups;
* ``-``: ``W_out relu(W_in h)^2`` at ``intermediate_size``;
* ``logits = RMSNorm(x) W_head^T``, ``W_head`` its own matrix.

The state-space scan is ``granite_hybrid``'s recurrence, one ``lax.scan``
step a token.  Departures are that file's, for the same reason (one
8192-token row beside the float32 parameters, moments and gradients):
every layer rematerialised, the scan in rematerialised blocks, attention
and the head in row blocks, the experts one after another in a
rematerialised scan, AdamW's moments on the host between updates.

``precision`` is ``gpt2_dense``'s: ``float32`` is the reference proper,
``bfloat16`` and ``fp8_e4m3`` round every matrix-product operand but the
router's (the configuration states it float32).

``forced``: a router that sits on a near-tie settles it by the last bits
of its input, and a token that goes to another expert moves the loss and
every gradient after it by far more than any rounding does.  So that the
arithmetic can be compared apart from that, every function below takes
the experts another computation chose (``forced``: integer indices,
``num_experts_per_tok`` a token, a layer) in place of its own choice; the
weights are still the reference's own scores of those experts.  What the
reference would have chosen is read beside it (:func:`chosen_experts`)
and compared as a number of its own.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs.gpt2_dense import _adamw, _leaf_norms, _mm, _round
from chipbench.refs.granite_hybrid import (
    ATTENTION_ROWS,
    HEAD_ROWS,
    _row_blocks,
    recurrence,
    rms_norm,
)

HIGHEST = jax.lax.Precision.HIGHEST


def attention(h, att, config, precision):
    """Grouped-query causal attention of one row ``h`` (S, d)."""
    q = _mm("sd,dhk->shk", h, att["query"]["kernel"], precision)
    k = _mm("sd,dhk->shk", h, att["key"]["kernel"], precision)
    v = _mm("sd,dhk->shk", h, att["value"]["kernel"], precision)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    keys = jnp.arange(h.shape[0])
    scale = 1.0 / math.sqrt(config["head_dim"])

    def block(qb, at):
        scores = _mm("qhk,shk->hqs", qb, k, precision) * scale
        scores = jnp.where(keys[None, None, :] <= at[None, :, None],
                           scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    ctx = _row_blocks(block, (q, keys), ATTENTION_ROWS)
    return _mm("qhk,hkd->qd", ctx, att["out"]["kernel"], precision)


def mamba(h, m, config, precision):
    """The Mamba-2 mixer of one row ``h`` (S, d)."""
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    K, inner = config["conv_kernel"], H * P
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
    gated = (y.reshape(S, inner) * jax.nn.silu(z)).reshape(S, G, inner // G)
    y = rms_norm(gated, m["norm"]["scale"].reshape(G, inner // G),
                 config["layer_norm_epsilon"]).reshape(S, inner)
    return _mm("se,ed->sd", y, m["out_proj"]["kernel"], precision)


def router(h, e, config, forced=None):
    """``(chosen, weight)`` of one row ``h`` (S, d): the boolean (S, E)
    mask of the chosen experts — of ``forced`` (S, k) where given — and
    their float32 weights, zero elsewhere.  Float32 at ``highest``
    whatever the run's precision."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "sd,de->se", h, e["router"], precision=HIGHEST))
    if forced is None:
        left = scores + e["router_bias"]
        chosen = jnp.zeros(scores.shape, bool)
        for _ in range(config["num_experts_per_tok"]):
            best = jnp.argmax(left, axis=-1)      # the first of equals
            hit = jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
            chosen, left = chosen | hit, jnp.where(hit, -jnp.inf, left)
    else:
        chosen = jnp.any(jax.nn.one_hot(
            forced, scores.shape[-1], dtype=bool), axis=-2)
    weight = jnp.where(chosen, scores, 0.0)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, weight * config["routed_scaling_factor"]


def relu2_mlp(h, w_up, w_down, precision):
    """``relu(h w_up)^2 w_down``, ``w_up`` (d, f) and ``w_down`` (f, d)."""
    hidden = jnp.square(jax.nn.relu(_mm("sd,df->sf", h, w_up, precision)))
    return _mm("sf,fd->sd", hidden, w_down, precision)


def experts(h, e, config, precision, forced=None):
    """The expert layer's part of this share, of one row ``h`` (S, d)."""
    _, weight = router(h, e, config, forced)
    first, count = config["experts_held_first"], config["n_routed_experts"]
    held = weight[:, first:first + count]              # (S, count)

    @jax.checkpoint
    def one(total, expert):
        w_up, w_down, w = expert      # the stack holds w_up output-major
        return total + w[:, None] * relu2_mlp(h, w_up.T, w_down,
                                              precision), None

    shared = relu2_mlp(h, e["shared"]["wi"]["kernel"],
                       e["shared"]["wo"]["kernel"], precision)
    total, _ = jax.lax.scan(
        one, shared, (e["experts_up"], e["experts_down"], held.T))
    return total


def layer(x, p, config, precision, forced=None):
    """One layer on one row ``x`` (S, d): the one part whose parameters
    the layer holds."""
    h = rms_norm(x, p["RMSNorm_0"]["scale"], config["layer_norm_epsilon"])
    if "Mamba2Mixer_0" in p:
        return x + mamba(h, p["Mamba2Mixer_0"], config, precision)
    if "MultiHeadAttention_0" in p:
        return x + attention(h, p["MultiHeadAttention_0"], config, precision)
    if "ExpertLayer_0" in p:
        return x + experts(h, p["ExpertLayer_0"], config, precision,
                           forced)
    ff = p["Relu2FeedForward_0"]
    return x + relu2_mlp(h, ff["wi"]["kernel"], ff["wo"]["kernel"],
                         precision)


def _n_layers(params):
    return sum(1 for k in params if k.startswith("layer_"))


def layers(params, x, config, precision="float32", forced=None):
    """The residual stream (B, S, d) through every ``layer_<i>`` of
    ``params`` in order, a row at a time.  ``forced``: ``{layer name:
    (B, S, k) int}`` or None."""
    fn = jax.checkpoint(
        lambda row, p, f: layer(row, p, config, precision, f))

    def one_row(args):
        row, f = args
        for i in range(_n_layers(params)):
            row = fn(row, params[f"layer_{i}"], (f or {}).get(f"layer_{i}"))
        return row

    return jax.lax.map(one_row, (x, forced))


def chosen_experts(params, tokens, config, precision="float32",
                   forced=None):
    """``{layer name: (B, S, E) bool}``: which experts every expert
    layer's router chooses for every token, of itself (no gradient is
    asked of it).  With ``forced`` the layers before it have computed
    with the forced experts: each router is then asked about the input
    the other computation's router saw, to this reference's precision."""
    def one_row(args):
        row, f = args
        masks = {}
        for i in range(_n_layers(params)):
            p, name = params[f"layer_{i}"], f"layer_{i}"
            if "ExpertLayer_0" in p:
                h = rms_norm(row, p["RMSNorm_0"]["scale"],
                             config["layer_norm_epsilon"])
                masks[name] = router(h, p["ExpertLayer_0"], config)[0]
            row = layer(row, p, config, precision, (f or {}).get(name))
        return masks

    return jax.lax.map(one_row, (embed(params, tokens), forced))


def embed(params, tokens):
    return params["embed"]["embedding"][tokens]


def head(params, config):
    return (params["embed"]["embedding"] if config["tie_word_embeddings"]
            else params["lm_head"])


def logits(params, x, config, precision="float32"):
    """(B, S, V) logits of the residual stream ``x`` after the last
    layer."""
    h = rms_norm(x, params["final_norm"]["scale"],
                 config["layer_norm_epsilon"])
    return _mm("bsd,vd->bsv", h, head(params, config), precision)


def loss_sum(params, tokens, labels, config, precision="float32",
             forced=None):
    """Sum over tokens of the softmax cross-entropy against ``labels``."""
    x = layers(params, embed(params, tokens), config, precision, forced)

    def head_block(xb, yb):
        z = logits(params, xb[None], config, precision)[0]
        picked = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    return jnp.sum(_row_blocks(
        head_block, (x.reshape(-1, x.shape[-1]), labels.reshape(-1)),
        HEAD_ROWS))


# --------------------------------------------------------------- training

def train_steps(make_params, batches, config, precision="float32",
                block_rows=1, place=lambda x: x, forced=None):
    """Follow ``len(batches)`` AdamW steps from seeded weights, as
    ``granite_hybrid.train_steps`` does (the same walk, this family's
    loss): each batch in blocks of ``block_rows`` rows, the summed loss's
    gradients accumulated.  ``forced``: None, or for every step ``{layer
    name: (B x S, k) int}``, the experts to take in place of the routers'
    own choice.  Returns host numbers — the loss of each step, the norm of
    each leaf of the first mean gradient, the norm of each leaf's change
    after the last step — and ``chosen``, for every step what
    :func:`chosen_experts` gives under the step's parameters."""
    opt = config["optimizer"]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, params, tokens, labels, f):
        total, grads = jax.value_and_grad(loss_sum)(
            params, tokens, labels, config, precision, f)
        return jax.tree.map(jnp.add, acc, grads), total

    own_choice = jax.jit(
        lambda p, t, f: chosen_experts(p, t, config, precision, f))

    params = make_params()
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    where = jax.tree.map(lambda x: x.sharding, params)
    m = v = None
    losses, grad_norms, chosen = [], None, []
    for t, (tokens, labels) in enumerate(batches, start=1):
        f = None if forced is None else {
            name: np.asarray(c).reshape(tokens.shape + (-1,))
            for name, c in forced[t - 1].items()}
        chosen.append(jax.device_get(own_choice(
            params, place(tokens), jax.tree.map(place, f))))
        acc, total = zeros(), 0.0
        for r in range(0, tokens.shape[0], block_rows):
            rows = slice(r, r + block_rows)
            acc, part = accumulate(
                acc, params, place(tokens[rows]), place(labels[rows]),
                jax.tree.map(lambda c: place(c[rows]), f))
            total += float(part)
        n = float(tokens.size)
        losses.append(total / n)
        grads = jax.tree.map(lambda g: g / n, acc)
        del acc
        if t == 1:
            grad_norms = jax.device_get(jax.jit(_leaf_norms)(grads))
        # The moments wait on the host while a gradient is made, as in
        # granite_hybrid.train_steps.
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
            "delta_norms": delta, "chosen": chosen}
