import numpy as np
import pytest

from chipbench import traffic


def test_train_batches_differ_row_by_row_and_step_by_step():
    batch = traffic.train_batches(
        {"global_batch": 4, "seq_len": 64,
         "token_dist": {"name": "zipf", "s": 1.0}}, 1000, 2**31 + 9)
    t0, l0 = batch(0)
    t1, _ = batch(1)
    assert t0.shape == (4, 64) and t0.dtype == np.int32
    assert (t0[:, 1:] == l0[:, :-1]).all()
    assert len({r.tobytes() for r in t0}) == 4 and not (t0 == t1).all()
    assert (batch(0)[0] == t0).all()


def test_other_seeds_give_other_ids_and_wide_seeds_work():
    mix = {"global_batch": 2, "seq_len": 32,
           "token_dist": {"name": "zipf", "s": 1.0}}
    a = traffic.train_batches(mix, 1000, 2**31 + 9)(0)[0]
    b = traffic.train_batches(mix, 1000, 2**31 + 10)(0)[0]
    assert a.shape == b.shape and not (a == b).all()


def test_zipf_ids_are_in_range_and_skewed():
    sample = traffic.token_sampler(50257, {"name": "zipf", "s": 1.0})
    ids = sample(traffic.seed_rng(3, 0), (4096,))
    assert ids.min() >= 0 and ids.max() < 50257
    assert (ids < 100).mean() > 0.3          # Zipf(1): the head is heavy
    uniform = traffic.token_sampler(50257, {})(traffic.seed_rng(3, 0), (4096,))
    assert (uniform < 100).mean() < 0.05
    with pytest.raises(ValueError):
        traffic.token_sampler(10, {"name": "no-such"})
