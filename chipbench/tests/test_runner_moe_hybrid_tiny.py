"""The ``train_moe_hybrid`` runner end to end at a tiny size on the CPU,
its controls failing as the other runners' fail, and what it adds to
``correct``: no pair past the row buffer's bound, the routers' agreement
with the reference's."""

import numpy as np
import pytest

from chipbench.runners import train, train_moe_hybrid
from chipbench.tests import tiny_moe_hybrid

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def moe_line():
    return tiny_moe_hybrid.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_moe_hybrid_run_is_correct_and_shaped(moe_line):
    line, run = moe_line
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    checks = {c[0]: c for c in run.checks}
    for name in ("first_grad_norm_worst_leaf_gap",
                 "param_change_norm_worst_leaf_gap",
                 "first_steps_loss_rel_gap", "moe_pairs_past_bound",
                 "router_pairs_differing_share", "window_nonfinite_losses"):
        assert name in checks, name
    assert checks["moe_pairs_past_bound"][1:3] == (0, 0)
    assert 0.0 <= checks["router_pairs_differing_share"][1] < 0.012


def test_moe_hybrid_job_is_built_from_the_published_keys():
    run = tiny_moe_hybrid.make_run(1)
    job = train_moe_hybrid.MoeHybridJob(run.config, run.mix, run.devices)
    job.reset(1)
    kinds = [sorted(k for k in job.params[f"layer_{i}"]
                    if k != "RMSNorm_0") for i in range(5)]
    assert kinds == [["Mamba2Mixer_0"], ["ExpertLayer_0"],
                     ["Mamba2Mixer_0"], ["MultiHeadAttention_0"],
                     ["ExpertLayer_0"]]
    assert "layer_5" not in job.params       # n_layer cuts the pattern
    e = job.params["layer_1"]["ExpertLayer_0"]
    assert e["router"].shape == (64, 8)      # the published width
    assert e["experts_up"].shape == (2, 48, 64)     # the experts held
    assert job.params["lm_head"].shape == (211, 64)
    spec = job.table.layers[1].experts
    assert spec.experts_held == (2, 2) and spec.top_k == 3
    job.step(job.feed(0))
    (chosen,) = job.routed          # the step hands its choice back
    assert sorted(chosen) == ["layer_1", "layer_4"]
    assert chosen["layer_1"].shape == (2 * 128, 3)


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_the_moe_hybrid_comparison(seed):
    run = tiny_moe_hybrid.make_run(seed)
    job = train_moe_hybrid.MoeHybridJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    control, ref = train_moe_hybrid.control_readings(run, like, "fp8_e4m3")
    train_moe_hybrid.compare(run, control, ref)
    assert run.correct is False
    failed = [c[0] for c in run.checks if not c[3]]
    # By the arithmetic (the reference took the control's experts, so no
    # moved pair is in this gap) and by the routers' agreement, each alone.
    assert "first_grad_norm_worst_leaf_gap" in failed
    assert "router_pairs_differing_share" in failed


def test_the_reference_takes_the_choice_it_is_given():
    """With the float32 reference's own choice forced back on it the
    readings are its own to the bit, and with one pair moved they are
    not: the forced experts are the ones computed with."""
    run = tiny_moe_hybrid.make_run(2**31 + 4)
    job = train_moe_hybrid.MoeHybridJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    own = train_moe_hybrid.reference_readings(run, like)
    chosen = train_moe_hybrid.chosen_from_masks(own["chosen"], 3)
    same = train_moe_hybrid.reference_readings(run, like, forced=chosen)
    assert same["losses"] == pytest.approx(own["losses"], rel=1e-6)
    assert train_moe_hybrid.differing_pairs_share(
        chosen, same["chosen"]) == 0.0
    first = chosen[0]["layer_1"]
    first[:, 0] = (first[:, 0] + 1 + np.arange(len(first)) % 2) % 8
    rows = [len(set(r)) == 3 for r in first]    # keep three distinct
    first[~np.array(rows)] = [0, 1, 2]
    moved = train_moe_hybrid.reference_readings(run, like, forced=chosen)
    assert moved["losses"][0] != pytest.approx(own["losses"][0], rel=1e-6)


def test_a_moe_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss, chosen = self.step_fn(*copy, batch)
        self.routed.append(chosen)
        return loss

    monkeypatch.setattr(train_moe_hybrid.MoeHybridJob, "step", frozen_step)
    line, run = tiny_moe_hybrid.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert "param_change_norm_worst_leaf_gap" in failed


def test_a_backward_product_in_fp8_is_not_correct(monkeypatch):
    """A lower precision in the timed step's backward pass alone — the
    grouped matmul's weight gradient from operands rounded to fp8 — moves
    no loss and no choice of experts, and fails by the gradient's limit:
    the reference took the step's experts, so that gap is arithmetic."""
    from jax import lax

    from chainermn_tpu.ops import grouped_matmul

    sound = grouped_matmul._dw_call

    def fp8_dw(rows, cols, *args, **kwargs):
        return sound(lax.reduce_precision(rows, 4, 3),
                     lax.reduce_precision(cols, 4, 3), *args, **kwargs)

    monkeypatch.setattr(grouped_matmul, "_dw_call", fp8_dw)
    line, run = tiny_moe_hybrid.tiny_run(seed=2**31 + 9, seconds=0.3)
    assert line["correct"] is False
    checks = {c[0]: c for c in run.checks}
    assert not checks["first_grad_norm_worst_leaf_gap"][3]
    assert checks["first_steps_loss_rel_gap"][3]
    assert checks["router_pairs_differing_share"][3]


def test_a_row_buffer_too_small_is_not_correct(monkeypatch):
    """The broken timed path this cell can have and the others cannot: a
    bound the held pairs do not fit under (2 x 512 tokens x 3 choices on 2
    of 8 experts: some 770 pairs, over 256 an expert, in a buffer of three
    tiles of 256).  Nothing is dropped in silence: the counter says how
    many, and every loss is NaN."""
    from chainermn_tpu.parallel import moe_dropless

    monkeypatch.setattr(moe_dropless, "BOUND_OVER_EXPECTED", 0.01)
    line, run = tiny_moe_hybrid.tiny_run(
        seed=6, seconds=0.3, mix={"seq_len": 512})
    assert line["correct"] is False
    checks = {c[0]: c for c in run.checks}
    assert checks["moe_pairs_past_bound"][1] > 0
    assert not checks["moe_pairs_past_bound"][3]
    assert not checks["window_nonfinite_losses"][3]
    assert line["failed"] == line["attempted"]


def test_routers_that_disagree_are_counted():
    ref = {"layer_1": np.zeros((2, 4, 8), bool)}
    ref["layer_1"][..., :3] = True
    same = {"layer_1": np.tile([0, 1, 2], (8, 1))}
    assert train_moe_hybrid.differing_pairs_share([same], [ref]) == 0.0
    other = {"layer_1": np.tile([0, 1, 7], (8, 1))}
    assert train_moe_hybrid.differing_pairs_share(
        [same, other], [ref, ref]) == pytest.approx(1 / 6)
    assert train_moe_hybrid.chosen_from_masks([ref], 3)[0][
        "layer_1"].tolist() == same["layer_1"].tolist()
