"""Plain reference of the ``bailing_hybrid`` decoder (Ling-3.0: five
Kimi-Delta-Attention layers to one latent-attention layer, two leading
dense SwiGLU FFNs, then a group-limited sigmoid top-k sparse-expert FFN
with a shared expert; an untied head): forward, loss, gradients and AdamW
in straightforward ``jax.numpy``, float32, ``highest`` matmul precision,
no kernel, no chunked scan, no cache, no batching tricks.  It imports
nothing of the program and reads the weight tree
``chipbench/weights_ling3.py`` makes, by name; the sizes and scalars come
from the configuration's published keys.

Written from the published config (keys in quotes) and three published
descriptions: the KDA recurrence of Kimi Linear (arXiv:2510.26692), MLA
of DeepSeek-V2 (arXiv:2405.04434), the bias-corrected group-limited router
of DeepSeek-V3 (arXiv:2412.19437).  What the keys do not fix is listed
under ``assumed`` in the configuration file.

* ``x = E[token]``; every layer ``x += mixer(norm(x))``, ``x +=
  ffn(norm(x))``; ``norm(x) = x rsqrt(mean x^2 + rms_norm_eps) w``;
  ``logits = norm(x) W_head^T`` (``tie_word_embeddings`` false);
* layer ``i`` is latent attention where ``(i + 1) % layer_group_size ==
  0``, else KDA; its FFN is dense where ``i < first_k_dense_replace``;
* KDA (``num_attention_heads`` heads, keys and values of ``head_dim``):
  ``[q | k | v | f] = h W_qkvf``, ``[b | a] = h W_bg``; ``[q, k, v] =
  silu(causal depthwise conv([q, k, v]))``, ``short_conv_kernel_size``
  taps, no bias (``linear_silu``); per head ``q <- q / sqrt(sum q^2 +
  1e-6) / sqrt(d_k)``, ``k <- k / sqrt(sum k^2 + 1e-6)``
  (``use_qk_norm``); ``beta = sigmoid(b)`` a head; the log-decay a head
  AND KEY CHANNEL ``g = kda_lower_bound * sigmoid(exp(A_log_h) (f +
  dt_bias))`` (``kda_safe_gate``); per head, from ``S_0 = 0`` (``d_k x
  d_v``), ONE STEP A TOKEN::

      S'_t = Diag(e^{g_t}) S_(t-1)
      S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
      o_t  = S_t^T q_t

  then per head ``y = o rsqrt(mean o^2 + rms_norm_eps) w_n sigmoid(a)``
  (``w_n`` one scale a channel of a head: ``group_norm_size`` 1;
  ``sigmoid(a)`` ONE number a head: ``head_wise``) and ``W_o y``;
* latent attention (``num_attention_heads`` heads): per head ``[q_nope |
  q_rope] = h W_q`` (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c |
  k_rope] = h W_kva`` (``kv_lora_rank`` | ``qk_rope_head_dim``), ``c <-
  norm(c)``; per head ``[k_nope | v] = c W_kvb`` (``qk_nope_head_dim`` |
  ``v_head_dim``); ``q_nope <- norm_q(q_nope)``, ``k_nope <-
  norm_k(k_nope)`` a head (``use_qk_norm``); rotary positions 0..S-1 at
  ``rope_theta`` on ``q_rope`` and on ``k_rope``, which every head
  shares, dimension ``2i`` paired with ``2i + 1`` (``rope_interleave``);
  causal softmax of ``[q_nope | q_rope] . [k_nope | k_rope] /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)`` over the values; times
  ``sigmoid(h W_g)``, one number a head; ``W_o``;
* dense FFN: ``W_o (silu(a) * b)``, ``[a | b] = W_i h``,
  ``intermediate_size``;
* experts: ``s = sigmoid(h W_r)`` over all ``num_experts`` published, in
  float32 whatever ``precision`` says; for the CHOICE only ``s' = s +
  bias``; the experts are ``n_group`` equal runs, a group's score the sum
  of its two largest ``s'``, the ``topk_group`` best groups stay (ties to
  the lower index), the ``num_experts_per_tok`` largest ``s'`` among
  their experts are chosen (ties to the lower index); ``w =
  routed_scaling_factor * s / sum of the chosen s`` on the chosen and 0
  elsewhere; ``f(h) = sum_e w_e W_down,e (silu(W_gate,e h) * W_up,e h) +
  W_down,s (silu(W_gate,s h) * W_up,s h)``, the sum over the experts HELD
  (``experts_held_first`` and the file's ``num_experts`` of them) and the
  shared expert ungated.  No clamp (both ``*_swiglu_limit_list``s are 0
  on the layers built), no auxiliary loss, no multi-token-prediction
  layer.

Departures are ``qwen3_next``'s, for the same reason (one 16,384-token
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
the weights are still the reference's own scores of those experts.  The
balancing controller on the expert bias (``config["balancing"]``, the
form assumed) steps after AdamW by the experts the step computed with
(``zaya1.rebalanced``: the same proportional step).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs.gpt2_dense import _adamw, _leaf_norms, _mm, _round
from chipbench.refs.granite_hybrid import (
    HEAD_ROWS,
    SCAN_BLOCK,
    _row_blocks,
    rms_norm,
)
from chipbench.refs.qwen3_next import l2_norm, swiglu_mlp
from chipbench.refs.zaya1 import rebalanced

HIGHEST = jax.lax.Precision.HIGHEST
ATTENTION_ROWS = 256      # queries a block of the masked softmax


# -------------------------------------------------- Kimi Delta Attention

def kda_recurrence(q, k, v, g, beta):
    """The delta rule under a decay a key channel, one ``lax.scan`` step
    a token.  ``q``, ``k``, ``g`` (the log of the decay, <= 0) (S, H,
    d_k), ``v`` (S, H, d_v), ``beta`` (S, H).  Returns ``o`` (S, H,
    d_v)."""
    S, H, dk = q.shape
    dv = v.shape[-1]

    def step(state, now):
        qt, kt, vt, gt, bt = now
        state = jnp.exp(gt)[:, :, None] * state              # Diag(e^g) S
        seen = jnp.sum(state * kt[:, :, None], axis=1)       # S'^T k
        new = bt[:, None] * (vt - seen)
        state = state + kt[:, :, None] * new[:, None, :]
        return state, jnp.sum(state * qt[:, :, None], axis=1)

    block = math.gcd(S, SCAN_BLOCK)
    blocks = jax.tree.map(
        lambda a: a.reshape((S // block, block) + a.shape[1:]),
        (q, k, v, g, beta))
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, blk: jax.lax.scan(step, state, blk)),
        jnp.zeros((H, dk, dv), jnp.float32), blocks)
    return o.reshape(S, H, dv)


def kda(h, m, config, precision):
    """The KDA mixer of one row ``h`` (S, d)."""
    H, D = config["num_attention_heads"], config["head_dim"]
    K, S = config["short_conv_kernel_size"], h.shape[0]
    proj = _mm("sd,de->se", h, m["in_proj_qkvf"]["kernel"], precision)
    qkv, f = jnp.split(proj, [3 * H * D], axis=-1)
    b, a = jnp.split(
        _mm("sd,de->se", h, m["in_proj_bg"]["kernel"], precision), 2,
        axis=-1)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    conv = 0.0
    for j in range(K):           # tap K-1 weighs the current token
        conv = conv + padded[j:j + S] * m["conv_kernel"][j]
    q, k, v = (x.reshape(S, H, D)
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    if config["use_qk_norm"]:
        q, k = l2_norm(q), l2_norm(k)
    q = q / math.sqrt(D)
    g = config["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(m["A_log"])[:, None] * (f + m["dt_bias"]).reshape(S, H, D))
    o = kda_recurrence(_round(q, precision), _round(k, precision),
                       _round(v, precision), g, jax.nn.sigmoid(b))
    y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + config["rms_norm_eps"]) * m["norm_scale"]
    y = y * jax.nn.sigmoid(a)[:, :, None]
    return _mm("se,ed->sd", y.reshape(S, H * D), m["out_proj"]["kernel"],
               precision)


# ------------------------------------------------------ latent attention

def rotate(x, config):
    """Rotary positions 0..S-1 on the whole last axis of ``x`` (S, H, R),
    dimension ``2i`` paired with ``2i + 1`` where ``rope_interleave``,
    else ``i`` with ``i + R / 2``."""
    R = x.shape[-1]
    freq = float(config["rope_theta"]) ** (-2.0 * np.arange(R // 2) / R)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if config["rope_interleave"]:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mla(h, att, config, precision):
    """The latent attention of one row ``h`` (S, d)."""
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    q = _mm("sd,dhk->shk", h, att["query"]["kernel"], precision)
    kva = _mm("sd,de->se", h, att["kv_a"]["kernel"], precision)
    c = rms_norm(kva[:, :rank], att["kv_norm"], eps)
    kv = _mm("sr,rhk->shk", c, att["kv_b"]["kernel"], precision)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if config["use_qk_norm"]:
        q_nope = rms_norm(q_nope, att["q_norm"], eps)
        k_nope = rms_norm(k_nope, att["k_norm"], eps)
    k_rope = rotate(kva[:, None, rank:], config)          # (S, 1, rope)
    q = jnp.concatenate([q_nope, rotate(q_rope, config)], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, k_nope.shape[:2] + (rope,))], axis=-1)
    keys = jnp.arange(h.shape[0])
    scale = 1.0 / math.sqrt(nope + rope)

    def block(qb, at):
        scores = _mm("qhk,shk->hqs", qb, k, precision) * scale
        scores = jnp.where(keys[None, None, :] <= at[None, :, None],
                           scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    ctx = _row_blocks(block, (q, keys), ATTENTION_ROWS)
    gate = jax.nn.sigmoid(
        _mm("sd,dh->sh", h, att["gate"]["kernel"], precision))
    return _mm("qhk,hkd->qd", ctx * gate[:, :, None], att["out"]["kernel"],
               precision)


# ---------------------------------------------------------------- experts

def _largest(x, k):
    """Boolean mask of the ``k`` largest of the last axis, the lower
    index first among equals: a stable sort of the negated values."""
    order = jnp.argsort(-x, axis=-1, stable=True)[..., :k]
    return jnp.any(jax.nn.one_hot(order, x.shape[-1], dtype=bool), axis=-2)


def router(h, e, config, forced=None):
    """``(chosen, weight)`` of one row ``h`` (S, d): the boolean (S, E)
    mask of the chosen experts — of ``forced`` (S, k) where given — and
    their float32 weights, zero elsewhere.  Float32 at ``highest``
    whatever the run's precision."""
    s = jax.nn.sigmoid(jnp.einsum(
        "sd,de->se", h, e["router"], precision=HIGHEST))
    E, G = s.shape[-1], config["n_group"]
    if forced is None:
        biased = s + e["router_bias"]
        if G:
            per_group = biased.reshape(-1, G, E // G)
            best_two = jnp.sort(per_group, axis=-1)[..., -2:].sum(axis=-1)
            kept = _largest(best_two, config["topk_group"])      # (S, G)
            biased = jnp.where(jnp.repeat(kept, E // G, axis=-1), biased,
                               -jnp.inf)
        chosen = _largest(biased, config["num_experts_per_tok"])
    else:
        chosen = jnp.any(jax.nn.one_hot(forced, E, dtype=bool), axis=-2)
    weight = jnp.where(chosen, s, 0.0)
    return chosen, config["routed_scaling_factor"] * weight / jnp.sum(
        weight, axis=-1, keepdims=True)


def dense_ffn(h, p, precision):
    """``wo (silu(a) * b)``, ``[a | b] = wi h``, of one row ``h``."""
    f = p["wo"]["kernel"].shape[0]
    wi = p["wi"]["kernel"]
    return swiglu_mlp(h, wi[:, :f], wi[:, f:], p["wo"]["kernel"], precision)


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

def mixer(h, p, config, precision):
    if "KDAMixer_0" in p:
        return kda(h, p["KDAMixer_0"], config, precision)
    return mla(h, p["MLAMixer_0"], config, precision)


def layer(x, p, config, precision, forced=None):
    """One layer on one row ``x`` (S, d)."""
    eps = config["rms_norm_eps"]
    x = x + mixer(rms_norm(x, p["RMSNorm_0"]["scale"], eps), p, config,
                  precision)
    h = rms_norm(x, p["RMSNorm_1"]["scale"], eps)
    if "ExpertLayer_0" not in p:
        return x + dense_ffn(h, p["GatedFeedForward_0"], precision)
    return x + experts(h, p["ExpertLayer_0"], config, precision, forced)


def _n_layers(params):
    return sum(1 for k in params if k.startswith("layer_"))


def layers(params, x, config, precision="float32", forced=None):
    """The residual stream (B, S, d) through every ``layer_<i>`` of
    ``params`` in order, a row at a time.  ``forced``: ``{layer name:
    (B, S, k) int}`` for the sparse layers, or None."""
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
    """``{layer name: (B, S, E) bool}``: which experts every sparse
    layer's router chooses for every token, of itself (no gradient is
    asked of it).  With ``forced`` the layers before it have computed with
    the forced experts: each router is then asked about the input the
    other computation's router saw, to this reference's precision."""
    eps = config["rms_norm_eps"]

    def one_row(args):
        row, f = args
        masks = {}
        for i in range(_n_layers(params)):
            p, name = params[f"layer_{i}"], f"layer_{i}"
            if "ExpertLayer_0" not in p:
                row = layer(row, p, config, precision)
                continue
            mid = row + mixer(rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                              p, config, precision)
            h = rms_norm(mid, p["RMSNorm_1"]["scale"], eps)
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
    ``qwen3_next.train_steps`` does (the same walk, this family's loss,
    the balancing controller's step after the optimizer's): each batch in
    blocks of ``block_rows`` rows, the summed loss's gradients
    accumulated.  ``forced``: None, or for every step ``{layer name: (B x
    S, k) int}``, the experts to take in place of the routers' own choice.
    Returns host numbers — the loss of each step, the norm of each leaf
    of the first mean gradient, the norm of each leaf's change after the
    last step — and ``chosen``, for every step what
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
        if config.get("balancing"):
            # The controller's step, after the optimizer's: by the experts
            # this step computed with (the forced ones, or its own).
            params = rebalanced(
                params, f if f is not None else chosen[-1],
                config["balancing"]["rate"])
        if t < len(batches):
            m, v = jax.device_get((m, v))
    del m, v
    start = make_params()
    delta = jax.device_get(jax.jit(lambda a, b: _leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta, "chosen": chosen}
