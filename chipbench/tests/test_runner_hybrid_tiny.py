"""The ``train_hybrid`` runner end to end at a tiny size on the CPU, and
the controls of "How correct is decided" failing for it as they fail for
the ``train`` runner (``test_controls.py``)."""

import pytest

from chipbench.runners import train, train_hybrid
from chipbench.tests import tiny_hybrid

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def hybrid_line():
    return tiny_hybrid.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_hybrid_run_is_correct_and_shaped(hybrid_line):
    line, run = hybrid_line
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    names = [c[0] for c in run.checks]
    assert "first_grad_norm_worst_leaf_gap" in names
    assert "param_change_norm_worst_leaf_gap" in names


def test_hybrid_job_is_built_from_the_published_keys():
    run = tiny_hybrid.make_run(1)
    job = train_hybrid.HybridJob(run.config, run.mix, run.devices)
    job.reset(1)
    kinds = ["Mamba2Mixer_0" if "Mamba2Mixer_0" in job.params[f"layer_{i}"]
             else "MultiHeadAttention_0" for i in range(4)]
    assert kinds == ["Mamba2Mixer_0", "Mamba2Mixer_0",
                     "MultiHeadAttention_0", "Mamba2Mixer_0"]
    assert "layer_4" not in job.params        # n_layer cuts layer_types


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_the_hybrid_comparison(seed):
    run = tiny_hybrid.make_run(seed)
    job = train_hybrid.HybridJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    ref = train_hybrid.reference_readings(run, like)
    control = train_hybrid.reference_readings(run, like, "fp8_e4m3")
    train.compare(run, control, ref)
    assert run.correct is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert "first_grad_norm_worst_leaf_gap" in failed


def test_a_hybrid_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss = self.step_fn(*copy, batch)
        return loss

    monkeypatch.setattr(train_hybrid.HybridJob, "step", frozen_step)
    line, run = tiny_hybrid.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert "param_change_norm_worst_leaf_gap" in failed
    assert "first_grad_norm_worst_leaf_gap" in failed


def test_a_part_of_the_hybrid_batch_left_out_is_not_correct(monkeypatch):
    def half_feed(self, index):
        tokens, labels = self.batches(index)
        half = tokens.shape[0] // 2
        tokens[half:], labels[half:] = tokens[:half], labels[:half]
        return self.comm.global_batch((tokens, labels))

    monkeypatch.setattr(train_hybrid.HybridJob, "feed", half_feed)
    line, _ = tiny_hybrid.tiny_run(seed=6, seconds=0.3)
    assert line["correct"] is False
