"""Block-diffusion training batches for ``kind: train_bd_moe`` traffic,
beside ``traffic.py`` (whose sampler and seed streams it uses): fresh
documents every step from the mix's unigram over the slice's NON-MASK
rows, and their noising — a level ``t`` uniform on ``[eps, 1]`` a block
of ``block_length`` tokens, each token of the block replaced by the mask
id with probability ``t``, the weight ``1 / t`` on the replaced ones and 0
elsewhere.  The benchmark's own few lines of numpy, the same two draws in
the same order as the library's ``datasets.block_diffusion.noise_batch``
makes from a numpy generator (a tier-1 test holds them equal).  Every
seed gets the same sizes.

Nothing here imports the program.
"""

import numpy as np

from chipbench.traffic import seed_rng, token_sampler


def noise(x0, block, mask_id, rng, eps):
    """``(xt, weights)`` of ``x0`` (rows, L)."""
    rows, L = x0.shape
    t = np.repeat(rng.uniform(eps, 1.0, size=(rows, L // block)), block,
                  axis=1)
    masked = rng.random(size=(rows, L)) < t
    return (np.where(masked, mask_id, x0).astype(x0.dtype),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))


def train_batches(mix, config, seed):
    """``batch(step) -> (x0, xt, weights)``: int32 ``(global_batch,
    seq_len)`` twice and float32 weights.  The mask id is the LAST row of
    the configuration's vocabulary slice; documents draw from the rows
    before it."""
    B, L = int(mix["global_batch"]), int(mix["seq_len"])
    mask_id = int(config["vocab_size"]) - 1
    block, eps = int(config["block_length"]), float(mix["sampling_eps"])
    sample = token_sampler(mask_id, mix.get("token_dist", {}))

    def batch(step):
        x0 = sample(seed_rng(seed, 1000 + step), (B, L))
        return (x0,) + noise(x0, block, mask_id,
                             seed_rng(seed, 500000 + step), eps)

    return batch
