"""The noising of block-diffusion training (BD3-LM, arXiv:2503.09573, as
SDAR adapts an autoregressive model with it, arXiv:2510.06303).

A document ``x0`` of ``L`` tokens is cut in blocks of ``block`` tokens.
Every block draws a noise level ``t`` uniform on ``[eps, 1]`` and each of
its tokens is replaced by the mask id with probability ``t`` (the linear
schedule, ``alpha_t = 1 - t``).  A masked row's loss term is weighted ``1
/ t`` (the schedule's ``-alpha_t' / (1 - alpha_t)``), an unmasked row's
0: the weights say both which rows were masked and at which level, and
the head runs over every row, so a step's shapes do not depend on the
draw.  ``models.block_diffusion.block_diffusion_loss`` takes ``(x0, xt,
weights)`` from here.
"""

import numpy as np

#: The lowest noise level drawn (BD3-LM's ``sampling_eps``): ``1 / t``
#: stays bounded.
SAMPLING_EPS = 1e-3


def noise_batch(x0, block: int, mask_id: int, rng, eps: float = SAMPLING_EPS):
    """``(xt, weights)`` of ``x0`` (rows, L) int: ``xt`` with the masked
    tokens replaced by ``mask_id``, ``weights`` float32 ``1 / t`` of the
    token's block where it was masked and 0 elsewhere.

    ``rng``: a ``numpy.random.Generator``; the draw is made on the host
    in numpy, two calls in a fixed order (the levels, then one uniform a
    token), so the same generator state gives the same batch."""
    rows, L = x0.shape
    if L % block:
        raise ValueError(f"a row of {L} tokens is no whole number of "
                         f"blocks of {block}")
    t = rng.uniform(eps, 1.0, size=(rows, L // block))
    t = np.repeat(t, block, axis=1)
    masked = rng.random(size=(rows, L)) < t
    return (np.where(masked, mask_id, x0).astype(np.asarray(x0).dtype),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))
