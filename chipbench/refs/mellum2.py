"""Plain reference of the ``mellum`` decoder (sliding-window attention
layers to one full-attention layer with YaRN-scaled rotary positions,
every layer followed by a sparse-expert FFN; an untied head): forward,
loss, gradients and AdamW in straightforward ``jax.numpy``, float32,
``highest`` matmul precision, no kernel, no cache, no sorting.  It imports
nothing of the program and reads the weight tree
``chipbench/weights_mellum2.py`` makes, by name; the sizes and scalars
come from the configuration's published keys.

Written from the published model (``config.json`` keys in quotes):

* ``x = E[token]`` (rounded to the configuration's compute precision
  where it states one: :func:`embed`); every layer ``x += attn(norm(x))``, ``x +=
  experts(norm(x))``; ``norm(x) = x rsqrt(mean x^2 + rms_norm_eps) w``;
  ``logits = norm(x) W_head^T``, ``W_head`` its own matrix
  (``tie_word_embeddings`` false);
* attention (``num_attention_heads`` query and ``num_key_value_heads``
  key/value heads of ``head_dim``, no bias): ``q <- norm_q(q)``, ``k <-
  norm_k(k)`` (the RMSNorm over a head; assumed, the configuration file
  says why); rotary positions 0..S-1 on the whole head, dimension ``i``
  paired with ``i + head_dim / 2``, by ``rope_parameters[layer_types[i]]``:
  ``default`` at ``inv_freq_i = rope_theta^(-2 i / head_dim)``; ``yarn``
  at :func:`yarn_frequencies`' blend with ``cos`` and ``sin`` both times
  ``attention_factor``; softmax at ``1/sqrt(head_dim)`` over the keys
  ``k_pos`` with ``0 <= q_pos - k_pos < reach``, ``reach`` the
  ``sliding_window`` in a ``sliding_attention`` layer and the row's
  length in a ``full_attention`` one: the band and the triangle are ONE
  comparison of positions;
* experts: ``p = softmax(h W_r)`` over all ``num_experts`` published, in
  float32 whatever ``precision`` says; the ``num_experts_per_tok`` largest
  chosen one after another, the lowest index on a tie; ``w = p / sum of
  the chosen p`` on the chosen and 0 elsewhere (``norm_topk_prob``);
  ``f(h) = sum_e w_e W_down,e (silu(W_gate,e h) * W_up,e h)`` over the
  experts HELD (``experts_held_first`` and the file's ``num_experts`` of
  them), every held expert applied to every token and its result taken
  times the token's weight for it, zero for most; no shared expert.  The
  router, one gated MLP and the loop over the held experts are
  ``refs/qwen3_next.py``'s, the same published form (that file's
  ``experts`` with its shared expert left out).

Departures, for room (one 16,384-token row beside the float32 parameters,
moments and gradients; a whole score matrix of 32 x 16,384^2 floats is 34
GB): every layer rematerialised, attention in blocks of
:data:`ATTENTION_ROWS` queries against all the keys (each block's masked
softmax is exact: a query's keys are all there), the head in row blocks,
the experts one after another in a rematerialised scan, AdamW's moments
on the host between updates.  None changes a number but by the order of
float32 sums.

``precision`` is ``gpt2_dense``'s: ``float32`` is the reference proper,
``bfloat16`` and ``fp8_e4m3`` round every matrix-product operand (the
projections, attention, the experts) but the router's (the configuration
states it float32).

``forced``: as ``nemotron_h`` (that file says why): every function below
takes the experts another computation chose in place of its own choice;
the weights are still the reference's own probabilities of those experts.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs.gpt2_dense import _adamw, _leaf_norms, _mm
from chipbench.refs.granite_hybrid import HEAD_ROWS, _row_blocks
from chipbench.refs.qwen3_next import experts as _held_experts
from chipbench.refs.qwen3_next import router

#: Queries a block of attention: 32 heads x 512 x 16,384 float32 scores
#: are 1.07 GB.
ATTENTION_ROWS = 512


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


# -------------------------------------------------------------- positions

def yarn_frequencies(rope, d):
    """The ``d / 2`` inverse frequencies of a ``yarn`` entry of
    ``rope_parameters``.  With ``b = rope_theta``, ``L =
    original_max_position_embeddings``, ``s = factor`` and ``c(r) = d
    ln(L / (2 pi r)) / (2 ln b)`` the dimension that turns ``r`` times
    over ``L`` positions: ``low = max(floor(c(beta_fast)), 0)``, ``high =
    min(ceil(c(beta_slow)), d - 1)``, ``ramp_i = clip((i - low) / (high -
    low), 0, 1)``, ``inv_freq_i = b^(-2 i / d) ((1 - ramp_i) + ramp_i /
    s)``: dimensions before ``low`` keep their frequency, those after
    ``high`` turn ``s`` times slower."""
    b, s = float(rope["rope_theta"]), float(rope["factor"])
    L = float(rope["original_max_position_embeddings"])

    def c(r):
        return d * math.log(L / (2.0 * math.pi * r)) / (2.0 * math.log(b))

    low = max(math.floor(c(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rope["beta_slow"]))), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return b ** (-2.0 * i / d) * ((1.0 - ramp) + ramp / s)


def rotate(x, rope):
    """Rotary positions 0..S-1 on each whole head of ``x`` (S, H, D) by
    one entry of ``rope_parameters``."""
    D = x.shape[-1]
    half = D // 2
    if rope["rope_type"] == "yarn":
        freq, factor = yarn_frequencies(rope, D), rope["attention_factor"]
    else:
        freq = float(rope["rope_theta"]) ** (-2.0 * np.arange(half) / D)
        factor = 1.0
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)
    cos = factor * jnp.cos(angle)[:, None, :]
    sin = factor * jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -------------------------------------------------------------- attention

def attention(h, att, kind, config, precision):
    """Grouped-query attention of one row ``h`` (S, d) in a layer of
    ``kind`` (an entry of ``layer_types``)."""
    D, eps = config["head_dim"], config["rms_norm_eps"]
    rope = config["rope_parameters"][kind]
    q = _mm("sd,dhk->shk", h, att["query"]["kernel"], precision)
    k = _mm("sd,dhk->shk", h, att["key"]["kernel"], precision)
    v = _mm("sd,dhk->shk", h, att["value"]["kernel"], precision)
    q = rotate(rms_norm(q, att["q_norm"]["scale"], eps), rope)
    k = rotate(rms_norm(k, att["k_norm"]["scale"], eps), rope)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    keys = jnp.arange(h.shape[0])
    reach = (config["sliding_window"] if kind == "sliding_attention"
             else h.shape[0])
    scale = 1.0 / math.sqrt(D)

    def block(qb, at):
        scores = _mm("qhk,shk->hqs", qb, k, precision) * scale
        behind = at[None, :, None] - keys[None, None, :]     # q_pos - k_pos
        scores = jnp.where((behind >= 0) & (behind < reach), scores,
                           -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    ctx = _row_blocks(block, (q, keys), ATTENTION_ROWS)
    return _mm("qhk,hkd->qd", ctx, att["out"]["kernel"], precision)


def experts(h, e, config, precision, forced=None):
    """The expert layer's part of this share, of one row ``h`` (S, d)."""
    return _held_experts(h, e, config, precision, forced, shared=False)


# ----------------------------------------------------------------- layers

def _kinds(params, config):
    n = sum(1 for k in params if k.startswith("layer_"))
    return config["layer_types"][:n]


def layer(x, p, kind, config, precision, forced=None):
    """One layer on one row ``x`` (S, d)."""
    eps = config["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["RMSNorm_0"]["scale"], eps),
                      p["MultiHeadAttention_0"], kind, config, precision)
    return x + experts(rms_norm(x, p["RMSNorm_1"]["scale"], eps),
                       p["ExpertLayer_0"], config, precision, forced)


def layers(params, x, config, precision="float32", forced=None):
    """The residual stream (B, S, d) through every ``layer_<i>`` of
    ``params`` in order, a row at a time.  ``forced``: ``{layer name:
    (B, S, k) int}`` or None."""
    def one_row(args):
        row, f = args
        for i, kind in enumerate(_kinds(params, config)):
            row = jax.checkpoint(
                lambda row, p, f, kind=kind: layer(
                    row, p, kind, config, precision, f))(
                row, params[f"layer_{i}"], (f or {}).get(f"layer_{i}"))
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
        for i, kind in enumerate(_kinds(params, config)):
            p, name = params[f"layer_{i}"], f"layer_{i}"
            mid = row + attention(
                rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                p["MultiHeadAttention_0"], kind, config, precision)
            h = rms_norm(mid, p["RMSNorm_1"]["scale"], eps)
            masks[name] = router(h, p["ExpertLayer_0"], config)[0]
            row = mid + experts(h, p["ExpertLayer_0"], config, precision,
                                (f or {}).get(name))
        return masks

    return jax.lax.map(one_row, (embed(params, tokens, config), forced))


def embed(params, tokens, config=None):
    """The table's rows of ``tokens``.  Where ``config`` states a compute
    precision the rows enter the stream rounded to it, as the program's
    lookup hands them on (``nn.Embed`` at the compute precision), in the
    reference proper and in the controls alike.  The table stays float32
    and its gradient is not rounded.  (Asked for in review as the likely
    cause of the routers' share not separating sound runs from the
    control; it was not: the share read 2.8e-3 to 3.8e-3 either way, the
    program's bfloat16 residual stream is what moves it.  PERF.md
    section 2, PR 39.)"""
    rows = params["embed"]["embedding"][tokens]
    compute = ((config or {}).get("precision") or {}).get("compute")
    if compute is None:
        return rows
    return rows + jax.lax.stop_gradient(
        rows.astype(compute).astype(rows.dtype) - rows)


def logits(params, x, config, precision="float32"):
    """(B, S, V) logits of the residual stream ``x`` after the last
    layer."""
    h = rms_norm(x, params["final_norm"]["scale"], config["rms_norm_eps"])
    return _mm("bsd,vd->bsv", h, params["lm_head"], precision)


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

def train_steps(make_params, batches, config, precision="float32",
                block_rows=1, place=lambda x: x, forced=None):
    """Follow ``len(batches)`` AdamW steps from seeded weights, as
    ``qwen3_next.train_steps`` does (the same walk, this family's loss):
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
