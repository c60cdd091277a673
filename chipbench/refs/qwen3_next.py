"""Plain reference of the ``qwen3_next`` decoder (three Gated DeltaNet
linear-attention layers to one gated softmax-attention layer, every layer
followed by a sparse-expert FFN with a gated shared expert; an untied
head): forward, loss, gradients and AdamW in straightforward
``jax.numpy``, float32, ``highest`` matmul precision, no kernel, no chunked
scan, no cache, no sorting, no batching tricks.  It imports nothing of the
program and reads the weight tree ``chipbench/weights_qwen3next.py`` makes,
by name; the sizes and scalars come from the configuration's published
keys.

Written from the published model (``config.json`` keys in quotes):

* ``x = E[token]``; every layer ``x += mixer(norm(x))``, ``x +=
  moe(norm(x))``; ``norm(x) = x rsqrt(mean x^2 + rms_norm_eps) (1 + w)``
  (the family's zero-centred RMSNorm); ``logits = norm(x) W_head^T``,
  ``W_head`` its own matrix (``tie_word_embeddings`` false);
* layer ``i`` is full attention where ``(i + 1) %
  full_attention_interval == 0``, else Gated DeltaNet;
* Gated DeltaNet (``linear_num_key_heads`` key and
  ``linear_num_value_heads`` value heads of ``linear_key_head_dim`` /
  ``linear_value_head_dim``): ``[q | k | v | z] = h W_qkvz``, ``[b | a] =
  h W_ba``; ``[q, k, v] = silu(causal depthwise conv([q, k, v]))``,
  ``linear_conv_kernel_dim`` taps, no bias; per head ``q <- q / sqrt(sum
  q^2 + 1e-6) / sqrt(d_k)``, ``k <- k / sqrt(sum k^2 + 1e-6)``; value head
  ``j`` reads key head ``j // (value heads / key heads)``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; per value head,
  from ``S_0 = 0`` (``d_k x d_v``), ONE STEP A TOKEN::

      S_t = e^{g_t} S_(t-1) + k_t (beta_t (v_t - e^{g_t} S_(t-1)^T k_t))^T
      o_t = S_t^T q_t

  then ``y = o rsqrt(mean o^2 + rms_norm_eps) w_n silu(z)`` per head
  (``w_n`` one plain scale a channel of a head, shared by the heads) and
  ``W_o y``;
* gated attention (``num_attention_heads`` query and
  ``num_key_value_heads`` key/value heads of ``head_dim``): per head ``[q |
  gate] = h W_q``; ``q <- norm_q(q)``, ``k <- norm_k(k)`` (the zero-centred
  RMSNorm over a head); rotary positions 0..S-1 on the first
  ``partial_rotary_factor x head_dim`` dimensions of a head at
  ``rope_theta``, dimension ``i`` paired with ``i + half``; causal softmax
  at ``1/sqrt(head_dim)``; ``W_o (attn * sigmoid(gate))``;
* experts: ``p = softmax(h W_r)`` over all ``num_experts`` published, in
  float32 whatever ``precision`` says; the ``num_experts_per_tok`` largest
  chosen one after another, the lowest index on a tie; ``w = p / sum of
  the chosen p`` on the chosen and 0 elsewhere (``norm_topk_prob``);
  ``f(h) = sum_e w_e W_down,e (silu(W_gate,e h) * W_up,e h) + sigmoid(h
  w_s) W_down,s (silu(W_gate,s h) * W_up,s h)``, the sum over the experts
  HELD (``experts_held_first`` and the file's ``num_experts`` of them):
  every held expert is applied to every token and its result taken times
  the token's weight for it, zero for most.

Departures are ``granite_hybrid``'s, for the same reason (one 8192-token
row beside the float32 parameters, moments and gradients): every layer
rematerialised, the recurrence in rematerialised blocks of steps,
attention and the head in row blocks, the experts one after another in a
rematerialised scan, AdamW's moments on the host between updates.

``precision`` is ``gpt2_dense``'s: ``float32`` is the reference proper,
``bfloat16`` and ``fp8_e4m3`` round every matrix-product operand (the
projections, attention, the experts, and the recurrence's ``q``, ``k`` and
``v``) but the router's (the configuration states it float32).

``forced``: as ``nemotron_h`` (that file says why): every function below
takes the experts another computation chose in place of its own choice;
the weights are still the reference's own probabilities of those experts.
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
    SCAN_BLOCK,
    _row_blocks,
)

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def rms_norm(x, w, eps):
    """The zero-centred RMSNorm: ``(1 + w)``."""
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


# ---------------------------------------------------------- Gated DeltaNet

def delta_rule(q, k, v, g, beta):
    """The gated delta rule, one ``lax.scan`` step a token.  ``q``, ``k``
    (S, H, d_k), ``v`` (S, H, d_v), ``g`` (the log of the decay, <= 0) and
    ``beta`` (S, H).  Returns ``o`` (S, H, d_v) and the last state
    (H, d_k, d_v)."""
    S, H, dk = q.shape
    dv = v.shape[-1]

    def step(state, now):
        qt, kt, vt, gt, bt = now
        state = jnp.exp(gt)[:, None, None] * state
        seen = jnp.sum(state * kt[:, :, None], axis=1)           # S^T k
        new = bt[:, None] * (vt - seen)
        state = state + kt[:, :, None] * new[:, None, :]
        return state, jnp.sum(state * qt[:, :, None], axis=1)

    block = math.gcd(S, SCAN_BLOCK)
    blocks = jax.tree.map(
        lambda a: a.reshape((S // block, block) + a.shape[1:]),
        (q, k, v, g, beta))
    last, o = jax.lax.scan(
        jax.checkpoint(lambda state, blk: jax.lax.scan(step, state, blk)),
        jnp.zeros((H, dk, dv), jnp.float32), blocks)
    return o.reshape(S, H, dv), last


def l2_norm(x):
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def gated_delta_net(h, m, config, precision):
    """The Gated DeltaNet mixer of one row ``h`` (S, d)."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    K, S = config["linear_conv_kernel_dim"], h.shape[0]
    key_dim, value_dim = hk * dk, hv * dv
    proj = _mm("sd,de->se", h, m["in_proj_qkvz"]["kernel"], precision)
    qkv, z = jnp.split(proj, [2 * key_dim + value_dim], axis=-1)
    b, a = jnp.split(
        _mm("sd,de->se", h, m["in_proj_ba"]["kernel"], precision), 2,
        axis=-1)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    conv = 0.0
    for j in range(K):           # tap K-1 weighs the current token
        conv = conv + padded[j:j + S] * m["conv_kernel"][j]
    q, k, v = jnp.split(jax.nn.silu(conv), [key_dim, 2 * key_dim], axis=-1)
    q = l2_norm(q.reshape(S, hk, dk)) / math.sqrt(dk)
    k = l2_norm(k.reshape(S, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(m["A_log"]) * jax.nn.softplus(a + m["dt_bias"])
    o, _ = delta_rule(_round(q, precision), _round(k, precision),
                      _round(v.reshape(S, hv, dv), precision), g, beta)
    y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + config["rms_norm_eps"]) * m["norm_scale"]
    y = y * jax.nn.silu(z.reshape(S, hv, dv))
    return _mm("se,ed->sd", y.reshape(S, value_dim),
               m["out_proj"]["kernel"], precision)


# ------------------------------------------------------- gated attention

def rotate(x, config):
    """Rotary positions 0..S-1 on the first part of each head of ``x``
    (S, H, D)."""
    rot = int(config["head_dim"] * config["partial_rotary_factor"])
    half = rot // 2
    freq = float(config["rope_theta"]) ** (-2.0 * np.arange(half) / rot)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def attention(h, att, config, precision):
    """The gated grouped-query causal attention of one row ``h`` (S, d)."""
    D, eps = config["head_dim"], config["rms_norm_eps"]
    qg = _mm("sd,dhk->shk", h, att["query"]["kernel"], precision)
    q, gate = qg[..., :D], qg[..., D:]
    k = _mm("sd,dhk->shk", h, att["key"]["kernel"], precision)
    v = _mm("sd,dhk->shk", h, att["value"]["kernel"], precision)
    q = rotate(rms_norm(q, att["q_norm"]["scale"], eps), config)
    k = rotate(rms_norm(k, att["k_norm"]["scale"], eps), config)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    keys = jnp.arange(h.shape[0])
    scale = 1.0 / math.sqrt(D)

    def block(qb, at):
        scores = _mm("qhk,shk->hqs", qb, k, precision) * scale
        scores = jnp.where(keys[None, None, :] <= at[None, :, None],
                           scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    ctx = _row_blocks(block, (q, keys), ATTENTION_ROWS)
    return _mm("qhk,hkd->qd", ctx * jax.nn.sigmoid(gate),
               att["out"]["kernel"], precision)


# ---------------------------------------------------------------- experts

def router(h, e, config, forced=None):
    """``(chosen, weight)`` of one row ``h`` (S, d): the boolean (S, E)
    mask of the chosen experts — of ``forced`` (S, k) where given — and
    their float32 weights, zero elsewhere.  Float32 at ``highest``
    whatever the run's precision."""
    p = jax.nn.softmax(jnp.einsum(
        "sd,de->se", h, e["router"], precision=HIGHEST), axis=-1)
    if forced is None:
        left = p
        chosen = jnp.zeros(p.shape, bool)
        for _ in range(config["num_experts_per_tok"]):
            best = jnp.argmax(left, axis=-1)      # the first of equals
            hit = jax.nn.one_hot(best, p.shape[-1], dtype=bool)
            chosen, left = chosen | hit, jnp.where(hit, -jnp.inf, left)
    else:
        chosen = jnp.any(jax.nn.one_hot(
            forced, p.shape[-1], dtype=bool), axis=-2)
    weight = jnp.where(chosen, p, 0.0)
    return chosen, weight / jnp.sum(weight, axis=-1, keepdims=True)


def swiglu_mlp(h, w_gate, w_up, w_down, precision):
    """``(silu(h w_gate) * (h w_up)) w_down``, ``w_gate``, ``w_up``
    (d, f) and ``w_down`` (f, d)."""
    hidden = jax.nn.silu(_mm("sd,df->sf", h, w_gate, precision)) * _mm(
        "sd,df->sf", h, w_up, precision)
    return _mm("sf,fd->sd", hidden, w_down, precision)


def shared_expert(h, e, precision):
    """``sigmoid(h w_s)`` times the shared expert, of one row ``h``."""
    f = e["shared"]["wo"]["kernel"].shape[0]
    wi = e["shared"]["wi"]["kernel"]                      # [gate | up]
    gate = jax.nn.sigmoid(
        _mm("sd,do->so", h, e["shared_gate"]["kernel"], precision))
    return gate * swiglu_mlp(h, wi[:, :f], wi[:, f:],
                             e["shared"]["wo"]["kernel"], precision)


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

    start = shared_expert(h, e, precision) if shared else jnp.zeros_like(h)
    total, _ = jax.lax.scan(
        one, start, (e["experts_gate"], e["experts_up"], e["experts_down"],
                     held.T))
    return total


# ----------------------------------------------------------------- layers

def mixer(h, p, config, precision):
    if "GatedDeltaNetMixer_0" in p:
        return gated_delta_net(h, p["GatedDeltaNetMixer_0"], config,
                               precision)
    return attention(h, p["MultiHeadAttention_0"], config, precision)


def layer(x, p, config, precision, forced=None):
    """One layer on one row ``x`` (S, d)."""
    eps = config["rms_norm_eps"]
    x = x + mixer(rms_norm(x, p["ZeroCentredRMSNorm_0"]["scale"], eps), p,
                  config, precision)
    return x + experts(
        rms_norm(x, p["ZeroCentredRMSNorm_1"]["scale"], eps),
        p["ExpertLayer_0"], config, precision, forced)


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
    """``{layer name: (B, S, E) bool}``: which experts every layer's
    router chooses for every token, of itself (no gradient is asked of
    it).  With ``forced`` the layers before it have computed with the
    forced experts: each router is then asked about the input the other
    computation's router saw, to this reference's precision."""
    eps = config["rms_norm_eps"]

    def one_row(args):
        row, f = args
        masks = {}
        for i in range(_n_layers(params)):
            p, name = params[f"layer_{i}"], f"layer_{i}"
            mid = row + mixer(
                rms_norm(row, p["ZeroCentredRMSNorm_0"]["scale"], eps), p,
                config, precision)
            h = rms_norm(mid, p["ZeroCentredRMSNorm_1"]["scale"], eps)
            masks[name] = router(h, p["ExpertLayer_0"], config)[0]
            row = mid + experts(h, p["ExpertLayer_0"], config, precision,
                                (f or {}).get(name))
        return masks

    return jax.lax.map(one_row, (embed(params, tokens), forced))


def embed(params, tokens):
    return params["embed"]["embedding"][tokens]


def logits(params, x, config, precision="float32"):
    """(B, S, V) logits of the residual stream ``x`` after the last
    layer."""
    h = rms_norm(x, params["final_norm"]["scale"], config["rms_norm_eps"])
    return _mm("bsd,vd->bsv", h, params["lm_head"], precision)


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
    ``nemotron_h.train_steps`` does (the same walk, this family's loss):
    each batch in blocks of ``block_rows`` rows, the summed loss's
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
