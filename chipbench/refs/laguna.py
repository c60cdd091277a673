"""Plain reference of the ``laguna`` decoder (one full-attention layer to
three sliding-window layers whose query-head counts differ, a sigmoid
gate a head on every attention output, a leading dense SwiGLU FFN, then a
sigmoid top-k sparse-expert FFN with a shared expert; an untied head):
forward, loss, gradients and AdamW in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, no sorting.
It imports nothing of the program and reads the weight tree
``chipbench/weights_laguna.py`` makes, by name; the sizes and scalars come
from the configuration's published keys.

Written from the published config (keys in quotes); what the keys do not
fix is listed under ``assumed`` in the configuration file:

* ``x = E[token]`` (rounded to the configuration's compute precision
  where it states one: ``mellum2.embed``); every layer ``x += attn(norm(x))``,
  ``x += ffn(norm(x))``; ``norm(x) = x rsqrt(mean x^2 + rms_norm_eps) w``;
  ``logits = norm(x) W_head^T`` (``tie_word_embeddings`` false);
* attention of layer ``i``: ``num_attention_heads_per_layer[i]`` query
  heads (read off the layer's own ``query`` matrix) over
  ``num_key_value_heads`` key/value heads of ``head_dim``, no bias, no
  QK-norm; rotary positions 0..S-1 by ``rope_parameters[layer_types[i]]``
  on the FIRST ``head_dim x partial_rotary_factor`` dimensions of a head,
  dimension ``j`` of them paired with ``j + half of them``, the others
  passed through: ``default`` at ``inv_freq_j = rope_theta^(-2 j / r)``
  (``r`` the rotated width); ``yarn`` at ``mellum2.yarn_frequencies``'
  blend over those ``r`` (``ramp_j = clip((j - low) / (high - low), 0,
  1)`` from ``low = floor(c(beta_fast))`` to ``high = ceil(c(beta_slow))``,
  ``c(t) = r ln(L / (2 pi t)) / (2 ln theta)``) with ``cos`` and ``sin`` both times
  ``attention_factor``; softmax at ``1/sqrt(head_dim)`` over the keys
  ``k_pos`` with ``0 <= q_pos - k_pos < reach``, ``reach`` the
  ``sliding_window`` in a ``sliding_attention`` layer and the row's length
  in a ``full_attention`` one; then, ``gating`` ``per-head``, every head's
  result times ``sigmoid(h W_g)``, one number a head of the layer's
  normed input; then ``W_o``;
* FFN of layer ``i`` by ``mlp_layer_types[i]``: ``dense`` is ``W_o (silu(a)
  * b)``, ``[a | b] = W_i h``, at ``intermediate_size``; ``sparse``: ``s =
  sigmoid(h W_r)`` over all ``num_experts`` published, in float32 whatever
  ``precision`` says; the ``num_experts_per_tok`` largest ``s + bias``
  chosen (the lower index on a tie; the bias is the choice's alone); ``w =
  moe_routed_scaling_factor * s / sum of the chosen s`` on the chosen and 0
  elsewhere (``norm_topk_prob``); ``f(h) = sum_e w_e W_down,e (silu(W_gate,e
  h) * W_up,e h) + W_down,s (silu(W_gate,s h) * W_up,s h)``, the sum over
  the experts HELD (``experts_held_first`` and the file's ``num_experts``
  of them) and the shared expert ungated.

Departures, for room (one row of thousands of tokens beside the float32
parameters, moments and gradients): every layer rematerialised, attention
in blocks of :data:`ATTENTION_ROWS` queries against all the keys (each
block's masked softmax is exact: a query's keys are all there), the head
in row blocks, the experts one after another in a rematerialised scan,
AdamW leaf by leaf with the leaf's old buffers given up, its moments on
the host between updates.  None changes a number but by the order of
float32 sums.

``precision`` is ``gpt2_dense``'s: ``float32`` is the reference proper,
``bfloat16`` and ``fp8_e4m3`` round every matrix-product operand (the
projections, the gate's, attention, the FFNs) but the router's (the
configuration states it float32).

``forced``: as ``nemotron_h`` (that file says why): every function below
takes the experts another computation chose in place of its own choice;
the weights are still the reference's own scores of those experts.

``broken``: one of :data:`BROKEN`, a fault in an attention row that the
cell's comparison has to catch (``chipbench/tools/control_train_gswa_moe.py``
reads them at the cell's size): ``no_window`` (a sliding row attends the
whole triangle), ``no_gate`` (the gate left out), ``whole_head_rotation``
(the full row turns all of the head, its blend over all of it),
``no_attention_factor`` (the full row's ``cos`` and ``sin`` times 1).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs.gpt2_dense import _leaf_norms, _mm
from chipbench.refs.granite_hybrid import HEAD_ROWS, _row_blocks, rms_norm
from chipbench.refs.ling3 import _largest, dense_ffn
from chipbench.refs.mellum2 import embed, logits, yarn_frequencies
from chipbench.refs.qwen3_next import swiglu_mlp

HIGHEST = jax.lax.Precision.HIGHEST
#: Queries a block of attention: 72 heads x 256 x 8,192 float32 scores
#: are 0.6 GB.
ATTENTION_ROWS = 256
BROKEN = ("no_window", "no_gate", "whole_head_rotation",
          "no_attention_factor")
#: A layer's modules, by the names the program gives them.
ATT, FFN, EXPERTS = ("MultiHeadAttention_0", "GatedFeedForward_0",
                     "ExpertLayer_0")


# -------------------------------------------------------------- positions

def rotate(x, rope, broken=None):
    """Rotary positions 0..S-1 on the first ``partial_rotary_factor`` of
    each head of ``x`` (S, H, D) by one entry of ``rope_parameters``."""
    D = x.shape[-1]
    r = int(D * rope.get("partial_rotary_factor", 1.0))
    yarn = rope["rope_type"] == "yarn"
    if yarn and broken == "whole_head_rotation":
        r = D
    half = r // 2
    if yarn:
        freq, factor = yarn_frequencies(rope, r), rope["attention_factor"]
        if broken == "no_attention_factor":
            factor = 1.0
    else:
        freq = float(rope["rope_theta"]) ** (-2.0 * np.arange(half) / r)
        factor = 1.0
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)
    cos = factor * jnp.cos(angle)[:, None, :]
    sin = factor * jnp.sin(angle)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


# -------------------------------------------------------------- attention

def attention(h, att, kind, config, precision, broken=None):
    """Gated grouped-query attention of one row ``h`` (S, d) in a layer
    of ``kind`` (an entry of ``layer_types``); the layer's query heads
    are its ``query`` matrix's."""
    D = config["head_dim"]
    rope = config["rope_parameters"][kind]
    q = _mm("sd,dhk->shk", h, att["query"]["kernel"], precision)
    k = _mm("sd,dhk->shk", h, att["key"]["kernel"], precision)
    v = _mm("sd,dhk->shk", h, att["value"]["kernel"], precision)
    q, k = rotate(q, rope, broken), rotate(k, rope, broken)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    keys = jnp.arange(h.shape[0])
    reach = h.shape[0]
    if kind == "sliding_attention" and broken != "no_window":
        reach = config["sliding_window"]
    scale = 1.0 / math.sqrt(D)

    def block(qb, at):
        scores = _mm("qhk,shk->hqs", qb, k, precision) * scale
        behind = at[None, :, None] - keys[None, None, :]     # q_pos - k_pos
        scores = jnp.where((behind >= 0) & (behind < reach), scores,
                           -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    ctx = _row_blocks(block, (q, keys), ATTENTION_ROWS)
    if broken != "no_gate":
        ctx = ctx * jax.nn.sigmoid(_mm(
            "sd,dh->sh", h, att["gate"]["kernel"], precision))[:, :, None]
    return _mm("qhk,hkd->qd", ctx, att["out"]["kernel"], precision)


# ---------------------------------------------------------------- experts

def router(h, e, config, forced=None):
    """``(chosen, weight)`` of one row ``h`` (S, d): the boolean (S, E)
    mask of the chosen experts — of ``forced`` (S, k) where given — and
    their float32 weights, zero elsewhere.  Float32 at ``highest``
    whatever the run's precision."""
    s = jax.nn.sigmoid(jnp.einsum(
        "sd,de->se", h, e["router"], precision=HIGHEST))
    if forced is None:
        chosen = _largest(s + e["router_bias"],
                          config["num_experts_per_tok"])
    else:
        chosen = jnp.any(
            jax.nn.one_hot(forced, s.shape[-1], dtype=bool), axis=-2)
    weight = jnp.where(chosen, s, 0.0)
    return chosen, config["moe_routed_scaling_factor"] * weight / jnp.sum(
        weight, axis=-1, keepdims=True)


def experts(h, e, config, precision, forced=None, shared=True):
    """The expert layer's part of this share, of one row ``h`` (S, d);
    ``shared=False`` leaves the shared expert out (what a further rank
    adds to a layer whose shared expert is counted once)."""
    _, weight = router(h, e, config, forced)
    first, count = config["experts_held_first"], config["num_experts"]
    held = weight[:, first:first + count]              # (S, count)

    @jax.checkpoint
    def one(total, expert):
        w_gate, w_up, w_down, w = expert   # gate and up are output-major
        return total + w[:, None] * swiglu_mlp(
            h, w_gate.T, w_up.T, w_down, precision), None

    start = dense_ffn(h, e["shared"], precision) if shared else (
        jnp.zeros_like(h))
    total, _ = jax.lax.scan(
        one, start, (e["experts_gate"], e["experts_up"], e["experts_down"],
                     held.T))
    return total


# ----------------------------------------------------------------- layers

def _kinds(params, config):
    n = sum(1 for k in params if k.startswith("layer_"))
    return config["layer_types"][:n]


def halves(x, p, kind, config, precision, forced=None, broken=None):
    """One layer on one row ``x`` (S, d): ``(what its attention adds to
    the stream, the stream after the layer)``."""
    eps = config["rms_norm_eps"]
    added = attention(rms_norm(x, p["RMSNorm_0"]["scale"], eps), p[ATT],
                      kind, config, precision, broken)
    x = x + added
    h = rms_norm(x, p["RMSNorm_1"]["scale"], eps)
    if EXPERTS not in p:
        return added, x + dense_ffn(h, p[FFN], precision)
    return added, x + experts(h, p[EXPERTS], config, precision, forced)


def layers(params, x, config, precision="float32", forced=None,
           broken=None, keep=()):
    """The residual stream (B, S, d) through every ``layer_<i>`` of
    ``params`` in order, a row at a time; with ``keep`` (layer names)
    also what those layers' attention rows added to the stream.
    ``forced``: ``{layer name: (B, S, k) int}`` for the sparse layers, or
    None."""
    def one_row(args):
        row, f = args
        added = {}
        for i, kind in enumerate(_kinds(params, config)):
            name = f"layer_{i}"
            a, row = jax.checkpoint(
                lambda row, p, f, kind=kind: halves(
                    row, p, kind, config, precision, f, broken))(
                row, params[name], (f or {}).get(name))
            if name in keep:
                added[name] = a
        return row, added

    out, added = jax.lax.map(one_row, (x, forced))
    return (out, added) if keep else out


def chosen_experts(params, tokens, config, precision="float32",
                   forced=None):
    """``{layer name: (B, S, E) bool}``: which experts every sparse
    layer's router chooses for every token, of itself (no gradient is
    asked of it).  With ``forced`` the layers before it have computed with
    the forced experts: each router is then asked about the input the
    other computation's router saw, to this reference's precision."""
    eps = config["rms_norm_eps"]

    def one_row(args):
        row, f = args
        masks = {}
        for i, kind in enumerate(_kinds(params, config)):
            p, name = params[f"layer_{i}"], f"layer_{i}"
            if EXPERTS in p:
                mid = row + attention(
                    rms_norm(row, p["RMSNorm_0"]["scale"], eps), p[ATT],
                    kind, config, precision)
                masks[name] = router(
                    rms_norm(mid, p["RMSNorm_1"]["scale"], eps),
                    p[EXPERTS], config)[0]
            row = halves(row, p, kind, config, precision,
                         (f or {}).get(name))[1]
        return masks

    return jax.lax.map(one_row, (embed(params, tokens, config), forced))


def attention_rows(params, tokens, config, keep, precision="float32",
                   forced=None, broken=None):
    """``{layer name: (B, S, d)}``: what the attention rows of the layers
    ``keep`` names added to the stream."""
    return layers(params, embed(params, tokens, config), config, precision,
                  forced, broken, tuple(keep))[1]


def loss_sum(params, tokens, labels, config, precision="float32",
             forced=None):
    """Sum over tokens of the softmax cross-entropy against ``labels``."""
    x = layers(params, embed(params, tokens, config), config, precision,
               forced)

    def head_block(xb, yb):
        z = logits(params, xb[None], config, precision)[0]
        picked = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    return jnp.sum(_row_blocks(
        head_block, (x.reshape(-1, x.shape[-1]), labels.reshape(-1)),
        HEAD_ROWS))


# --------------------------------------------------------------- training

@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("lr", "wd", "b1", "b2", "eps"))
def _adamw_leaf(p, m, v, g, t, *, lr, wd, b1, b2, eps):
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v


def adamw(params, m, v, grads, t, opt):
    """One AdamW step, LEAF BY LEAF: a leaf's parameter and moments are
    given up to the update that replaces them and its gradient is deleted
    behind it (``grads`` is spent), so the step
    holds the four trees once and not the new ones beside the old (811 M
    parameters: 13 GB against 22).  ``m`` and ``v``: host or device
    trees, or None before the first step (zeros)."""
    leaves, tree = jax.tree.flatten(params)
    flat = lambda x: [None] * len(leaves) if x is None else (  # noqa: E731
        jax.tree.leaves(x))
    out = []
    for p, m1, v1, g in zip(leaves, flat(m), flat(v), flat(grads),
                            strict=True):
        m1 = jnp.zeros_like(p) if m1 is None else jax.device_put(
            m1, p.sharding)
        v1 = jnp.zeros_like(p) if v1 is None else jax.device_put(
            v1, p.sharding)
        out.append(_adamw_leaf(
            p, m1, v1, g, float(t), lr=opt["learning_rate"],
            wd=opt["weight_decay"], b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"]))
        g.delete()
    return tuple(jax.tree.unflatten(tree, [o[i] for o in out])
                 for i in range(3))


def train_steps(make_params, batches, config, precision="float32",
                block_rows=1, place=lambda x: x, forced=None, keep=()):
    """Follow ``len(batches)`` AdamW steps from seeded weights, as
    ``mellum2.train_steps`` does (the same walk, this family's loss):
    each batch in blocks of ``block_rows`` rows, the summed loss's
    gradients accumulated.  ``forced``: None, or for every step ``{layer
    name: (B x S, k) int}``, the experts to take in place of the routers'
    own choice.  Returns host numbers — the loss of each step, the norm of
    each leaf of the first mean gradient, the norm of each leaf's change
    after the last step — and, for every step under the step's
    parameters, ``chosen`` (what :func:`chosen_experts` gives) and
    ``attention`` (what :func:`attention_rows` gives for ``keep``)."""
    opt = config["optimizer"]

    @jax.jit
    def gradient(params, tokens, labels, f):
        return jax.value_and_grad(loss_sum)(
            params, tokens, labels, config, precision, f)

    add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                  donate_argnums=(0,))
    mean = jax.jit(lambda acc, n: jax.tree.map(lambda g: g / n, acc),
                   donate_argnums=(0,))
    own_choice = jax.jit(
        lambda p, t, f: chosen_experts(p, t, config, precision, f))
    added = jax.jit(lambda p, t, f: attention_rows(
        p, t, config, keep, precision, f))

    params = make_params()
    m = v = None
    losses, grad_norms, chosen, attended = [], None, [], []
    for t, (tokens, labels) in enumerate(batches, start=1):
        f = None if forced is None else {
            name: np.asarray(c).reshape(tokens.shape + (-1,))
            for name, c in forced[t - 1].items()}
        placed = jax.tree.map(place, f)
        chosen.append(jax.device_get(own_choice(
            params, place(tokens), placed)))
        if keep:
            attended.append(jax.device_get(added(
                params, place(tokens), placed)))
        del placed
        acc, total = None, 0.0
        for r in range(0, tokens.shape[0], block_rows):
            rows = slice(r, r + block_rows)
            part, g = gradient(
                params, place(tokens[rows]), place(labels[rows]),
                jax.tree.map(lambda c: place(c[rows]), f))
            acc = g if acc is None else add(acc, g)
            del g
            total += float(part)
        n = float(tokens.size)
        losses.append(total / n)
        grads = mean(acc, n)
        del acc
        if t == 1:
            grad_norms = jax.device_get(jax.jit(_leaf_norms)(grads))
        params, m, v = adamw(params, m, v, grads, t, opt)
        del grads
        # The moments wait on the host while a gradient is made, as in
        # granite_hybrid.train_steps.
        if t < len(batches):
            m, v = jax.device_get((m, v))
    del m, v
    start = make_params()
    delta = jax.device_get(jax.jit(lambda a, b: _leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta, "chosen": chosen, "attention": attended}
