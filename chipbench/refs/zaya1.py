"""Plain reference of the ``zaya`` decoder (ZAYA1-8B: every layer a
compressed-convolutional-attention branch and a top-1 sparse-expert branch
whose router hands a state to the next layer's): forward, loss, gradients
and AdamW in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, no kernel, no sorting, no batching tricks.  It imports nothing
of the program and reads the weight tree ``chipbench/weights_zaya.py``
makes, by name; the sizes and scalars come from the configuration's
published keys.

The model, as equations (``config.json`` keys in quotes; what the file
does not fix is from the CCA paper, arXiv:2510.04476, and the ZAYA1
report, arXiv:2511.17127, as far as they could be stated without a
network — every such item is listed under the configuration's
``assumed``).  ``h = RMSNorm(x)``, eps ``rms_norm_eps``, per layer ``l``:

*Attention branch, ``x <- x + CCA(h)``:*

1. ``q~ = h W_q`` (to ``num_attention_heads x head_dim``), ``k~ = h W_k``
   (to ``num_key_value_heads x head_dim``); ``v = [h W_v1 ; shift(h)
   W_v2]``, each half ``num_key_value_heads x head_dim / 2`` wide
   (``shift(h)_t = h_(t-1)``, zero at t = 0; with two KV heads head 0
   reads this token, head 1 the one before).
2. ``u = [q~ ; k~]``.  ``c1`` = causal depthwise convolution over time,
   ``cca_time0`` taps a channel, with bias; ``c2`` = causal convolution of
   ``c1``, ``cca_time1`` taps, grouped by head (one ``head_dim x
   head_dim`` matrix a tap a head), with bias.  The last tap weighs this
   token; at 2 taps each sees ``t`` and ``t-1`` only.
3. q-k mean: ``q = c2[:q] + (q~ + rep(k~)) / 2`` (each KV head repeated
   over its query heads), ``k = c2[q:] + (mean_group(q~) + k~) / 2``.
4. Per head ``q <- sqrt(D) q / |q|``, ``k <- tau_head sqrt(D) k / |k|``
   (``|x| = sqrt(sum x^2 + 1e-12)``; ``tau``: one learned float32 a KV
   head); rotary embedding on the first ``partial_rotary_factor x
   head_dim`` of each head's dimensions (dimension ``i`` of the first half
   of them paired with ``i + half``), base ``rope_parameters.hybrid.
   rope_theta``, positions 0..S-1; causal softmax attention, grouped-query,
   at scale ``1/sqrt(D)`` in the latent; ``CCA(h) = o W_o``.  No bias on
   the projections.

*Expert branch, ``x <- x + MoE(h, r_(l-1))``:*

5. ``r_l = h W_r + gamma_l * r_(l-1)`` (to ``router_hidden_size``;
   ``r_(-1)`` = 0: the report's exponential depth averaging); ``p =
   softmax(gelu(gelu(RMSNorm(r_l) W_1) W_2) W_3)`` over ALL
   ``num_experts`` published, exact (erf) GELU, float32 whatever
   ``precision`` says; ``e* = argmax(p + b)`` (``b``: the balancing bias,
   in the choice only, no gradient, ties to the lower index); ``MoE =
   p[e*] W_down,e* (silu(W_gate,e* h) * (W_up,e* h))`` where ``e*`` is
   HELD (``experts_held_first`` and the file's ``num_experts`` of them)
   and nothing where it is not: every held expert is applied to every
   token and its result taken times the token's weight for it, zero for
   most — a dense masked sum, where the program sorts the pairs and
   multiplies row groups.  The weight is ``p[e*]`` itself, not
   renormalised.  ``r_l`` goes on to layer ``l+1`` beside ``x``.
6. ``x = E[token]``; ``logits = RMSNorm(x) E^T`` (tied); mean
   cross-entropy.

Departures from the source, in the program alike: the report's learned
residual scaling has no key in ``config.json`` and is not built (plain
pre-norm sums); ``rope_parameters.hybrid_sliding`` is unused
(``sliding_window`` null).  The report's controller that holds the
experts' loads even through ``b`` is stood in for by a proportional one
(:func:`rebalanced`, ``config["balancing"]``), its form assumed.
Departures of this file from the equations, none changing the
mathematics, all to make one 8192-token row fit beside the float32
parameters, moments and gradients: every layer rematerialised, attention
and the head in row blocks, the experts one after another in a
rematerialised scan, AdamW's moments on the host between updates.

``precision`` is ``gpt2_dense``'s: ``float32`` is the reference proper,
``bfloat16`` and ``fp8_e4m3`` round every matrix-product operand but the
router's (the configuration states it float32).

``forced``, as ``nemotron_h`` has it and for its reason: every function
below takes the expert another computation chose (integer indices, one a
token, a layer) in place of its own choice; the weight is still the
reference's own probability of that expert.  What the reference would
have chosen is read beside it (:func:`chosen_experts`).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs.gpt2_dense import _adamw, _leaf_norms, _mm
from chipbench.refs.granite_hybrid import (
    ATTENTION_ROWS,
    HEAD_ROWS,
    _row_blocks,
    rms_norm,
)

HIGHEST = jax.lax.Precision.HIGHEST


def shift(x, by=1):
    """``x`` (S, ...) ``by`` tokens later, zeros before the first."""
    return jnp.pad(x, ((by, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def causal_taps(x, K, tap):
    """``sum_j tap(j, x_(t - (K-1-j)))``: the last of the ``K`` taps
    weighs this token."""
    return sum(tap(j, shift(x, K - 1 - j)) for j in range(K))


def rotate(x, config):
    """Rotary positions 0..S-1 on the first part of each head of ``x``
    (S, H, D)."""
    rope = config["rope_parameters"]["hybrid"]
    rot = int(config["head_dim"] * rope["partial_rotary_factor"])
    half = rot // 2
    freq = float(rope["rope_theta"]) ** (-2.0 * np.arange(half) / rot)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def cca(h, m, config, precision):
    """The attention branch of one row ``h`` (S, d): equations 1-4."""
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    D, S = config["head_dim"], h.shape[0]
    group = Hq // Hkv
    q0 = _mm("sd,de->se", h, m["query"]["kernel"], precision)
    k0 = _mm("sd,de->se", h, m["key"]["kernel"], precision)
    v = jnp.concatenate([
        _mm("sd,de->se", h, m["value_now"]["kernel"], precision),
        _mm("sd,de->se", shift(h), m["value_before"]["kernel"], precision),
    ], axis=-1).reshape(S, Hkv, D)

    def tap0(j, x):
        return x * m["conv0_kernel"][j]

    def tap1(j, x):
        return _mm("sgd,gde->sge", x, m["conv1_kernel"][j], precision)

    u = jnp.concatenate([q0, k0], axis=-1)
    c1 = m["conv0_bias"] + causal_taps(u, config["cca_time0"], tap0)
    c2 = m["conv1_bias"].reshape(Hq + Hkv, D) + causal_taps(
        c1.reshape(S, Hq + Hkv, D), config["cca_time1"], tap1)
    qh, kh = q0.reshape(S, Hkv, group, D), k0.reshape(S, Hkv, D)
    q = c2[:, :Hq] + ((qh + kh[:, :, None]) / 2).reshape(S, Hq, D)
    k = c2[:, Hq:] + (jnp.mean(qh, axis=2) + kh) / 2

    def unit(x):
        return math.sqrt(D) * x / jnp.sqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-12)

    q = rotate(unit(q), config)
    k = rotate(unit(k) * m["temp"][:, None], config)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    keys = jnp.arange(S)

    def block(qb, at):
        scores = _mm("qhk,shk->hqs", qb, k, precision) / math.sqrt(D)
        scores = jnp.where(keys[None, None, :] <= at[None, :, None],
                           scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    ctx = _row_blocks(block, (q, keys), ATTENTION_ROWS)
    return _mm("se,ed->sd", ctx.reshape(S, Hq * D), m["out"]["kernel"],
               precision)


def router_probabilities(h, state, e, config):
    """``(p, r_l)``: the softmax over all published experts (S, E) and
    the state handed on (S, r), of one row ``h`` (S, d) and the state
    ``r_(l-1)``.  Float32 at ``highest`` whatever the run's precision."""
    mm = functools.partial(jnp.einsum, "sd,de->se", precision=HIGHEST)
    state = mm(h, e["router_down"]) + e["router_gamma"] * state
    x = rms_norm(state, e["router_norm"], config["rms_norm_eps"])
    x = jax.nn.gelu(mm(x, e["router_w1"]), approximate=False)
    x = jax.nn.gelu(mm(x, e["router_w2"]), approximate=False)
    return jax.nn.softmax(mm(x, e["router_w3"]), axis=-1), state


def router(h, state, e, config, forced=None):
    """``(chosen, weight, r_l)``: the boolean (S, E) mask of the chosen
    expert — of ``forced`` (S, 1) where given — its float32 weight there
    and zero elsewhere, and the state handed on."""
    p, state = router_probabilities(h, state, e, config)
    best = (jnp.argmax(p + e["router_bias"], axis=-1)  # the first of equals
            if forced is None else forced[:, 0])
    chosen = jax.nn.one_hot(best, p.shape[-1], dtype=bool)
    return chosen, jnp.where(chosen, p, 0.0), state


def swiglu_mlp(h, w_gate, w_up, w_down, precision):
    """``(silu(h w_gate) * (h w_up)) w_down``, ``w_gate``, ``w_up`` (d, f)
    and ``w_down`` (f, d)."""
    hidden = jax.nn.silu(_mm("sd,df->sf", h, w_gate, precision)) * _mm(
        "sd,df->sf", h, w_up, precision)
    return _mm("sf,fd->sd", hidden, w_down, precision)


def experts(h, state, e, config, precision, forced=None):
    """The expert branch's part of this share, of one row ``h`` (S, d),
    and the router state handed on."""
    _, weight, state = router(h, state, e, config, forced)
    first, count = config["experts_held_first"], config["num_experts"]
    held = weight[:, first:first + count]              # (S, count)

    @jax.checkpoint
    def one(total, expert):
        w_gate, w_up, w_down, w = expert    # gate and up held output-major
        return total + w[:, None] * swiglu_mlp(
            h, w_gate.T, w_up.T, w_down, precision), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (e["experts_gate"], e["experts_up"], e["experts_down"], held.T))
    return total, state


def layer(x, state, p, config, precision, forced=None):
    """One layer on one row: ``(x, r_(l-1)) -> (x, r_l)``."""
    eps = config["rms_norm_eps"]
    x = x + cca(rms_norm(x, p["RMSNorm_0"]["scale"], eps), p["CCAMixer_0"],
                config, precision)
    out, state = experts(rms_norm(x, p["RMSNorm_1"]["scale"], eps), state,
                         p["ExpertLayer_0"], config, precision, forced)
    return x + out, state


def _n_layers(params):
    return sum(1 for k in params if k.startswith("layer_"))


def zero_state(x, config):
    return jnp.zeros(x.shape[:-1] + (config["router_hidden_size"],),
                     jnp.float32)


def layers(params, x, config, precision="float32", forced=None,
           router_state=None):
    """The residual stream (B, S, d) and the router state (B, S, r; None:
    zeros, the first stage) through every ``layer_<i>`` of ``params`` in
    order, a row at a time: ``(x, r)`` after the last.  ``forced``:
    ``{layer name: (B, S, 1) int}`` or None."""
    fn = jax.checkpoint(
        lambda row, r, p, f: layer(row, r, p, config, precision, f))
    if router_state is None:
        router_state = zero_state(x, config)

    def one_row(args):
        row, r, f = args
        for i in range(_n_layers(params)):
            row, r = fn(row, r, params[f"layer_{i}"],
                        (f or {}).get(f"layer_{i}"))
        return row, r

    return jax.lax.map(one_row, (x, router_state, forced))


def chosen_experts(params, tokens, config, precision="float32",
                   forced=None):
    """``{layer name: (B, S, E) bool}``: which expert every layer's router
    chooses for every token, of itself (no gradient is asked of it).  With
    ``forced`` the layers before it have computed with the forced experts:
    each router is then asked about the input — the normed stream AND the
    state handed on — the other computation's router saw, to this
    reference's precision."""
    def one_row(args):
        row, r, f = args
        masks = {}
        for i in range(_n_layers(params)):
            p, name = params[f"layer_{i}"], f"layer_{i}"
            eps = config["rms_norm_eps"]
            mid = row + cca(rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                            p["CCAMixer_0"], config, precision)
            masks[name] = router(
                rms_norm(mid, p["RMSNorm_1"]["scale"], eps), r,
                p["ExpertLayer_0"], config)[0]
            row, r = layer(row, r, p, config, precision, (f or {}).get(name))
        return masks

    x = embed(params, tokens)
    return jax.lax.map(one_row, (x, zero_state(x, config), forced))


def embed(params, tokens):
    return params["embed"]["embedding"][tokens]


def logits(params, x, config, precision="float32"):
    """(B, S, V) logits of the residual stream ``x`` after the last
    layer: the tied table."""
    h = rms_norm(x, params["final_norm"]["scale"], config["rms_norm_eps"])
    return _mm("bsd,vd->bsv", h, params["embed"]["embedding"], precision)


def loss_sum(params, tokens, labels, config, precision="float32",
             forced=None):
    """Sum over tokens of the softmax cross-entropy against ``labels``."""
    x, _ = layers(params, embed(params, tokens), config, precision, forced)

    def head_block(xb, yb):
        z = logits(params, xb[None], config, precision)[0]
        picked = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    return jnp.sum(_row_blocks(
        head_block, (x.reshape(-1, x.shape[-1]), labels.reshape(-1)),
        HEAD_ROWS))


# --------------------------------------------------------------- training

def rebalanced(params, taken, rate):
    """``params`` after one step of the balancing controller: every
    layer's bias ``b <- b + rate * (1/E - share)``, ``share`` the part of
    the step's (token, choice) pairs each expert took.  ``taken``:
    ``{layer name: indices (.., k) or boolean masks (.., E)}``."""
    out = dict(params)
    for name, took in taken.items():
        e = params[name]["ExpertLayer_0"]
        n = e["router_bias"].shape[0]
        took = np.asarray(took)
        counts = (took.reshape(-1, n).sum(0) if took.dtype == bool
                  else np.bincount(took.reshape(-1), minlength=n))
        bias = e["router_bias"] + jnp.asarray(
            rate * (1.0 / n - counts / counts.sum()), jnp.float32)
        out[name] = dict(params[name],
                         ExpertLayer_0=dict(e, router_bias=bias))
    return out


def train_steps(make_params, batches, config, precision="float32",
                block_rows=1, place=lambda x: x, forced=None):
    """Follow ``len(batches)`` AdamW steps from seeded weights, as
    ``nemotron_h.train_steps`` does (the same walk, this family's loss):
    each batch in blocks of ``block_rows`` rows, the summed loss's
    gradients accumulated.  ``forced``: None, or for every step ``{layer
    name: (B x S, 1) int}``, the expert to take in place of the routers'
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
