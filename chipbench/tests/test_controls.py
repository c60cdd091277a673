"""The comparison that decides ``correct`` has been shown to fail.

The controls of "How correct is decided", at a size a test run can hold
(on the chip, at the cells' own sizes, ``chipbench/tools/control_*.py``
read them; PERF.md has the readings):

* the reference computed in the next lower precision (fp8 for bfloat16
  compute) comes out as not correct against the tiny limits, which the
  sound program passes (``test_runners_tiny.py``);
* the rest of a run with the timed path broken underneath (a step that
  returns its state unchanged; a part of the batch left out) comes out
  with ``correct`` false.
"""

import time

import pytest

from chipbench import harness
from chipbench.runners import train
from chipbench.tests import tiny
from chipbench.tests.test_runners_tiny import TRAIN_LIMITS


def _train_run(seed):
    import jax

    cell = {"name": "t", "config": "c", "traffic": "train", "chips": 1}
    return harness.Run(
        tiny.manifest(cell), cell, tiny.TRAIN_CONFIG, dict(tiny.TRAIN_MIX),
        dict(TRAIN_LIMITS), seed, 0.0, False, time.perf_counter(),
        list(jax.devices()[:1]))


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_the_training_comparison(seed):
    run = _train_run(seed)
    job = train.TrainJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    ref = train.reference_readings(run, like)
    control = train.reference_readings(run, like, "fp8_e4m3")
    train.compare(run, control, ref)
    assert run.correct is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert "first_grad_norm_worst_leaf_gap" in failed


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss = self.step_fn(*copy, batch)
        return loss

    monkeypatch.setattr(train.TrainJob, "step", frozen_step)
    line, run = tiny.tiny_run(seed=5, seconds=0.3,
                              limits=TRAIN_LIMITS)
    assert line["correct"] is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert "param_change_norm_worst_leaf_gap" in failed
    assert "first_grad_norm_worst_leaf_gap" in failed


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    real_feed = train.TrainJob.feed

    def half_feed(self, index):
        tokens, labels = self.batches(index)
        half = tokens.shape[0] // 2
        tokens[half:], labels[half:] = tokens[:half], labels[:half]
        return self.comm.global_batch((tokens, labels))

    monkeypatch.setattr(train.TrainJob, "feed", half_feed)
    line, run = tiny.tiny_run(seed=6, seconds=0.3,
                              limits=TRAIN_LIMITS)
    assert line["correct"] is False
    assert real_feed is not half_feed
