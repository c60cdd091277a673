"""One general traffic generator; a mix is a data file under
``chipbench/traffic/``, picked by its ``kind``.

``kind: train``: a global batch of fresh rows every step, token ids drawn
from the mix's unigram by ``--seed``.  Every seed gets the same sizes
(``global_batch`` x ``seq_len``) and only other ids, so a run's work does
not depend on the seed.

Nothing here imports the program.
"""

import numpy as np


def seed_rng(seed, stream):
    """A generator for one purpose (``stream``) of one run's seed."""
    return np.random.default_rng((int(seed), int(stream)))


def token_sampler(vocab, dist):
    """``sample(rng, shape) -> int32 ids`` from the mix's unigram."""
    name = dist.get("name", "uniform")
    if name == "uniform":
        return lambda rng, shape: rng.integers(
            0, vocab, size=shape).astype(np.int32)
    if name != "zipf":
        raise ValueError(f"unknown token_dist {name!r}")
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(dist["s"])
    cdf = np.cumsum(w / w.sum())

    def sample(rng, shape):
        ids = np.searchsorted(cdf, rng.random(size=shape))
        return np.minimum(ids, vocab - 1).astype(np.int32)

    return sample


def train_batches(mix, vocab, seed):
    """``batch(step) -> (tokens, labels)`` int32 ``(global_batch,
    seq_len)``: fresh rows every step, labels the next token."""
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    sample = token_sampler(vocab, mix.get("token_dist", {}))

    def batch(step):
        rows = sample(seed_rng(seed, 1000 + step), (B, S + 1))
        return rows[:, :-1].copy(), rows[:, 1:].copy()

    return batch
