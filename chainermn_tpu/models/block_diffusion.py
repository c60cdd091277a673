"""The loss of a block-diffusion training step (BD3-LM's, as SDAR trains
with it): what a training script calls with a batch that
``datasets.block_diffusion.noise_batch`` noised.

The model runs ONCE over ``2 L`` rows, the clean copy of every document
followed by its noised one, at positions ``0 .. L-1`` twice (both copies
of a token carry its position in the document); a table that states
``block_diffusion`` (``block_table.table_from_config`` of an ``sdar_moe``
config) makes every attention row see them through the block-diffusion
mask.  The head reads the NOISY rows only, each predicting the token at
its own position (no shift), and the loss is

    (1 / L) sum over masked i of (1 / t_blk(i)) (-log p(x0_i | row i of xt))

a document, averaged over the batch's documents: the weights carry ``1 /
t`` on the masked rows and 0 on the others, and the head runs over all
``L`` noisy rows so that every step has the same shapes.
"""

import jax.numpy as jnp

from chainermn_tpu.ops.fused_ce import fused_cross_entropy


def block_diffusion_loss(hidden_fn, head, x0, xt, weights, *, chunk=None):
    """``(loss, counters)`` of one batch.  ``hidden_fn(tokens,
    positions)`` is the model: ``(rows, 2 L)`` tokens and the ``(2 L,)``
    positions to the final-norm hidden states ``(rows, 2 L, d)`` —
    ``lambda t, p: model.apply({"params": params}, t, position_offset=p,
    return_hidden=True)``.  ``head`` is the output matrix ``(vocab, d)``
    (the untied ``lm_head``, or the embedding table).  ``x0``, ``xt``
    ``(rows, L)`` int and ``weights`` ``(rows, L)`` float32 as
    ``noise_batch`` gives them.  ``counters``: the rows whose token was
    masked and the sum of the weights, of this batch."""
    L = x0.shape[1]
    hidden = hidden_fn(jnp.concatenate([x0, xt], axis=1),
                       jnp.tile(jnp.arange(L), 2))
    loss = fused_cross_entropy(
        hidden[:, L:], head, x0, chunk=chunk, weights=weights,
        normaliser=float(x0.size))
    return loss, {"masked_rows": jnp.sum(weights > 0),
                  "weight_sum": jnp.sum(weights)}
