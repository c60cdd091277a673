"""Plain reference of an ``sdar_moe`` decoder TRAINED BY BLOCK DIFFUSION
(identical layers of a GQA attention row and a sparse-expert FFN; an
untied head; the step sees every document twice, clean and noised, under
one block-causal mask, and its loss is a weighted masked-token loss):
forward, loss, gradients and AdamW in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, no sorting.
It imports nothing of the program and reads the weight tree
``chipbench/weights_sdar_moe.py`` makes, by name; the sizes and scalars
come from the configuration's published keys, the objective's from its
``block_length`` and the batch.

Written from the published model (``config.json`` keys in quotes) and the
objective of the SDAR report (arXiv:2510.06303), which is BD3-LM's
(arXiv:2503.09573):

* ``x = E[token]`` (rounded to the configuration's compute precision
  where it states one, as ``refs/mellum2.embed``); every layer ``x +=
  attn(norm(x))``, ``x += experts(norm(x))``; ``norm(x) = x rsqrt(mean
  x^2 + rms_norm_eps) w``; ``logits = norm(x) W_head^T``, ``W_head`` its
  own matrix (``tie_word_embeddings`` false);
* attention (``num_attention_heads`` query and ``num_key_value_heads``
  key/value heads of ``head_dim``, no bias): ``q <- norm_q(q)``, ``k <-
  norm_k(k)`` (the RMSNorm over a head, a learned weight: the family's
  QK-norm); rotary positions on the whole head at ``rope_theta``,
  dimension ``i`` paired with ``i + head_dim / 2``, no scaling, AT THE
  TOKEN'S POSITION IN ITS DOCUMENT: the step's rows are ``[x0 ; xt]``,
  ``2 L`` of them, and row ``r`` stands at ``r mod L``; softmax at
  ``1/sqrt(head_dim)`` under the mask ``M[q, k]``, built here by
  comparison from ``blk(r) = (r mod L) // block_length`` and ``noisy(r) =
  r >= L``: clean q, clean k ``blk(k) <= blk(q)``; noisy q, clean k
  ``blk(k) < blk(q)``; noisy q, noisy k ``blk(k) == blk(q)``; clean q,
  noisy k never;
* experts: ``refs/qwen3_next.py``'s router and gated MLPs, the same
  published form (softmax over all ``num_experts`` published in float32,
  the ``num_experts_per_tok`` largest, renormalised over the chosen:
  ``norm_topk_prob``), over the experts HELD, no shared expert;
* the loss reads the head on the NOISY rows only, row ``L + i``
  predicting ``x0_i`` (no shift): ``sum_i w_i (-log p(x0_i | row i of
  xt))`` with ``w_i = 1 / t_blk(i)`` where token ``i`` was masked and 0
  elsewhere (the linear schedule), over ``L`` a document.

Departures, for room (16,384 rows beside the float32 parameters, moments
and gradients; a whole score matrix of 32 x 16,384^2 floats is 34 GB):
every layer rematerialised, attention in blocks of
:data:`ATTENTION_ROWS` queries against all the keys (each block's masked
softmax is exact: a query's keys are all there), the head in row blocks,
the experts one after another in a rematerialised scan, AdamW's moments
on the host between updates.  None changes a number but by the order of
float32 sums.

``precision`` is ``gpt2_dense``'s: ``float32`` is the reference proper,
``bfloat16`` and ``fp8_e4m3`` round every matrix-product operand but the
router's.  ``forced``: as ``nemotron_h`` (that file says why).

``broken``: None, or one of :data:`BROKEN` — the reference with one
statement of the objective wrong, which the cell's comparison has to
tell from the program (``chipbench/tools/control_train_bd_moe.py``): a
noisy row that sees its OWN clean block (``<=`` for ``<``), a clean row
that sees noisy keys (of its own block), positions ``0 .. 2L-1`` in
place of ``0 .. L-1`` twice, the loss without ``1 / t``, the loss shifted
by one (row ``i`` predicting ``x0_(i+1)``, the autoregressive habit).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs.gpt2_dense import _adamw, _leaf_norms, _mm
from chipbench.refs.granite_hybrid import HEAD_ROWS, _row_blocks
from chipbench.refs.mellum2 import embed, rms_norm
from chipbench.refs.qwen3_next import experts as _held_experts
from chipbench.refs.qwen3_next import router

#: Queries a block of attention: 32 heads x 512 x 16,384 float32 scores
#: are 1.07 GB.
ATTENTION_ROWS = 512
BROKEN = ("own_clean_block", "clean_sees_noisy", "positions_2L",
          "no_weight", "shifted")


def mask(q_rows, k_rows, L, block, broken=None):
    """``M[q, k]`` for rows ``q_rows`` against ``k_rows`` of the ``2 L``
    rows ``[clean ; noisy]``, by comparison."""
    qn, kn = (q_rows >= L)[:, None], (k_rows >= L)[None, :]
    qb = ((q_rows % L) // block)[:, None]
    kb = ((k_rows % L) // block)[None, :]
    noisy_clean = kb <= qb if broken == "own_clean_block" else kb < qb
    clean_noisy = (kb == qb) & (broken == "clean_sees_noisy")
    return jnp.where(
        qn, jnp.where(kn, kb == qb, noisy_clean),
        jnp.where(kn, clean_noisy, kb <= qb))


def rotate(x, at, theta):
    """Rotary positions ``at`` (S,) on each whole head of ``x`` (S, H,
    D), dimension ``i`` paired with ``i + D / 2``."""
    D = x.shape[-1]
    half = D // 2
    freq = float(theta) ** (-2.0 * np.arange(half) / D)
    angle = at.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, att, config, precision, broken=None):
    """Grouped-query attention of one document's ``2 L`` rows ``h`` (2L,
    d) under the block-diffusion mask."""
    eps = config["rms_norm_eps"]
    L, block = h.shape[0] // 2, config["block_length"]
    rows = jnp.arange(2 * L)
    at = rows if broken == "positions_2L" else rows % L
    q = _mm("sd,dhk->shk", h, att["query"]["kernel"], precision)
    k = _mm("sd,dhk->shk", h, att["key"]["kernel"], precision)
    v = _mm("sd,dhk->shk", h, att["value"]["kernel"], precision)
    q = rotate(rms_norm(q, att["q_norm"]["scale"], eps), at,
               config["rope_theta"])
    k = rotate(rms_norm(k, att["k_norm"]["scale"], eps), at,
               config["rope_theta"])
    ctx = masked_softmax(q, k, v, block, precision, broken)
    return _mm("qhk,hkd->qd", ctx, att["out"]["kernel"], precision)


def masked_softmax(q, k, v, block, precision="float32", broken=None):
    """Softmax attention of one document's ``2 L`` rows under ``M``:
    ``q`` (2L, H, D) against ``k``, ``v`` (2L, H_kv, D), scores over
    ``sqrt(D)``, in blocks of :data:`ATTENTION_ROWS` queries against all
    the keys."""
    L, rows = q.shape[0] // 2, jnp.arange(q.shape[0])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def block_of(qb, q_rows):
        scores = _mm("qhk,shk->hqs", qb, k, precision) * scale
        scores = jnp.where(mask(q_rows, rows, L, block, broken)[None],
                           scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v,
                   precision)

    return _row_blocks(block_of, (q, rows), ATTENTION_ROWS)


def experts(h, e, config, precision, forced=None):
    """The expert layer's part of this share, of one row ``h`` (S, d)."""
    return _held_experts(h, e, config, precision, forced, shared=False)


def _n_layers(params):
    return sum(1 for k in params if k.startswith("layer_"))


def layer(x, p, config, precision, forced=None, broken=None):
    """One layer on one document's rows ``x`` (2L, d)."""
    eps = config["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["RMSNorm_0"]["scale"], eps),
                      p["MultiHeadAttention_0"], config, precision, broken)
    return x + experts(rms_norm(x, p["RMSNorm_1"]["scale"], eps),
                       p["ExpertLayer_0"], config, precision, forced)


def layers(params, x, config, precision="float32", forced=None,
           broken=None):
    """The residual stream (B, 2L, d) through every ``layer_<i>`` of
    ``params`` in order, a document at a time.  ``forced``: ``{layer
    name: (B, 2L, k) int}`` or None."""
    def one_row(args):
        row, f = args
        for i in range(_n_layers(params)):
            row = jax.checkpoint(
                lambda row, p, f: layer(row, p, config, precision, f,
                                        broken))(
                row, params[f"layer_{i}"], (f or {}).get(f"layer_{i}"))
        return row

    return jax.lax.map(one_row, (x, forced))


def chosen_experts(params, tokens, config, precision="float32",
                   forced=None):
    """``{layer name: (B, 2L, E) bool}``: which experts every layer's
    router chooses for every row, of itself, as
    ``refs/mellum2.chosen_experts`` reads them (with ``forced`` the
    layers before have computed with the forced experts)."""
    eps = config["rms_norm_eps"]

    def one_row(args):
        row, f = args
        masks = {}
        for i in range(_n_layers(params)):
            p, name = params[f"layer_{i}"], f"layer_{i}"
            mid = row + attention(
                rms_norm(row, p["RMSNorm_0"]["scale"], eps),
                p["MultiHeadAttention_0"], config, precision)
            h = rms_norm(mid, p["RMSNorm_1"]["scale"], eps)
            masks[name] = router(h, p["ExpertLayer_0"], config)[0]
            row = mid + experts(h, p["ExpertLayer_0"], config, precision,
                                (f or {}).get(name))
        return masks

    return jax.lax.map(one_row, (embed(params, tokens, config), forced))


def first_attention(params, tokens, config, precision="float32",
                    broken=None):
    """(B, 2L, d): what the first layer's attention row adds to the
    stream, every row of it — what the cell's comparison holds the timed
    step's own to, row by row (the runner's docstring says why)."""
    p = params["layer_0"]
    return jax.lax.map(
        lambda row: attention(
            rms_norm(row, p["RMSNorm_0"]["scale"], config["rms_norm_eps"]),
            p["MultiHeadAttention_0"], config, precision, broken),
        embed(params, tokens, config))


def rows_of(x0, xt):
    """The step's rows: every document's clean copy, then its noised
    one."""
    return jnp.concatenate([x0, xt], axis=1)


def logits(params, x, config, precision="float32"):
    """(B, S, V) logits of the residual stream ``x`` after the last
    layer."""
    h = rms_norm(x, params["final_norm"]["scale"], config["rms_norm_eps"])
    return _mm("bsd,vd->bsv", h, params["lm_head"], precision)


def noisy_logits(params, x0, xt, config, precision="float32", forced=None,
                 broken=None):
    """(B, L, V): the head on the noisy rows."""
    x = layers(params, embed(params, rows_of(x0, xt), config), config,
               precision, forced, broken)
    return logits(params, x[:, x0.shape[1]:], config, precision)


def loss_sum(params, x0, xt, weights, config, precision="float32",
             forced=None, broken=None):
    """Sum over the noisy rows of ``w_i`` times the softmax
    cross-entropy of row ``i`` against ``x0_i``."""
    L = x0.shape[1]
    x = layers(params, embed(params, rows_of(x0, xt), config), config,
               precision, forced, broken)[:, L:]
    labels = x0
    if broken == "no_weight":
        weights = (weights > 0).astype(weights.dtype)
    if broken == "shifted":
        # row i predicts x0_(i+1); the last row of a document predicts
        # nothing
        labels = jnp.roll(x0, -1, axis=1)
        weights = jnp.asarray(weights).at[:, -1].set(0.0)

    def head_block(xb, yb, wb):
        z = logits(params, xb[None], config, precision)[0]
        picked = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
        return wb * (jax.nn.logsumexp(z, axis=-1) - picked)

    return jnp.sum(_row_blocks(
        head_block, (x.reshape(-1, x.shape[-1]), labels.reshape(-1),
                     weights.reshape(-1)), HEAD_ROWS))


# --------------------------------------------------------------- training

def train_steps(make_params, batches, config, precision="float32",
                block_rows=1, place=lambda x: x, forced=None, broken=None):
    """Follow ``len(batches)`` AdamW steps from seeded weights, as
    ``mellum2.train_steps`` does (the same walk, this objective's loss):
    each batch ``(x0, xt, weights)`` in blocks of ``block_rows``
    documents, the summed loss's gradients accumulated, the sum over
    ``x0.size`` (``L`` a document).  ``forced``: None, or for every step
    ``{layer name: (B x 2L, k) int}``.  Returns host numbers — the loss
    of each step, the norm of each leaf of the first mean gradient, the
    norm of each leaf's change after the last step — and, for every step
    under the step's parameters, ``chosen`` (what :func:`chosen_experts`
    gives) and ``attention`` (what :func:`first_attention` gives)."""
    opt = config["optimizer"]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, params, x0, xt, w, f):
        total, grads = jax.value_and_grad(loss_sum)(
            params, x0, xt, w, config, precision, f, broken)
        return jax.tree.map(jnp.add, acc, grads), total

    own_choice = jax.jit(
        lambda p, t, f: chosen_experts(p, t, config, precision, f))
    first = jax.jit(
        lambda p, t: first_attention(p, t, config, precision, broken))

    params = make_params()
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    where = jax.tree.map(lambda x: x.sharding, params)
    m = v = None
    losses, grad_norms, chosen, attended = [], None, [], []
    for t, (x0, xt, w) in enumerate(batches, start=1):
        rows = (x0.shape[0], 2 * x0.shape[1])
        f = None if forced is None else {
            name: np.asarray(c).reshape(rows + (-1,))
            for name, c in forced[t - 1].items()}
        tokens = place(np.concatenate([x0, xt], axis=1))
        chosen.append(jax.device_get(own_choice(
            params, tokens, jax.tree.map(place, f))))
        attended.append(jax.device_get(first(params, tokens)))
        acc, total = zeros(), 0.0
        for r in range(0, x0.shape[0], block_rows):
            docs = slice(r, r + block_rows)
            acc, part = accumulate(
                acc, params, place(x0[docs]), place(xt[docs]),
                place(w[docs]), jax.tree.map(lambda c: place(c[docs]), f))
            total += float(part)
        n = float(x0.size)
        losses.append(total / n)
        grads = jax.tree.map(lambda g: g / n, acc)
        del acc
        if t == 1:
            grad_norms = jax.device_get(jax.jit(_leaf_norms)(grads))
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
            "delta_norms": delta, "chosen": chosen, "attention": attended}
