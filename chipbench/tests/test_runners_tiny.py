"""The training runner end to end at a tiny size on the CPU, through the
test-only entry (the rest of a run, without the look for a chip)."""

import pytest

from chipbench.tests import tiny

TRAIN_LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 4e-3,
                "delta_norm_gap": 5e-3}
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def train_line():
    return tiny.tiny_run(seed=2**31 + 7, seconds=0.6,
                         limits=TRAIN_LIMITS)


def test_train_run_is_correct_and_shaped(train_line):
    line, run = train_line
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["count"] == 1
    names = [c[0] for c in run.checks]
    assert "first_grad_norm_worst_leaf_gap" in names
    assert "param_change_norm_worst_leaf_gap" in names


def test_train_same_seed_same_first_losses(train_line):
    _, run = train_line
    _, again = tiny.tiny_run(seed=2**31 + 7, seconds=0.2,
                             limits=TRAIN_LIMITS)
    first = [c for c in run.checks if c[0] == "first_steps_loss_rel_gap"]
    second = [c for c in again.checks
              if c[0] == "first_steps_loss_rel_gap"]
    assert first[0][1] == second[0][1]
