"""The ``train_cca_moe`` runner end to end at a tiny size on the CPU, its
control failing as the other runners' fail, and three broken timed paths
of its own."""

import pytest

from chipbench.runners import train_cca_moe, train_moe_hybrid
from chipbench.tests import tiny_cca_moe

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def cca_line():
    return tiny_cca_moe.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_cca_moe_run_is_correct_and_shaped(cca_line):
    line, run = cca_line
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


def test_cca_moe_job_is_built_from_the_published_keys():
    run = tiny_cca_moe.make_run(1)
    job = train_cca_moe.CcaMoeJob(run.config, run.mix, run.devices)
    job.reset(1)
    assert "layer_3" not in job.params       # n_layer cuts layer_types
    for i in range(3):
        assert sorted(job.params[f"layer_{i}"]) == [
            "CCAMixer_0", "ExpertLayer_0", "RMSNorm_0", "RMSNorm_1"]
    e = job.params["layer_1"]["ExpertLayer_0"]
    assert e["router_w3"].shape == (16, 8)   # the published width
    assert e["experts_gate"].shape == (4, 48, 64)   # the experts held
    assert "lm_head" not in job.params       # the tied table
    spec = job.table.layers[1].experts
    assert spec.experts_held == (2, 4) and spec.top_k == 1
    assert (spec.router, spec.expert, spec.d_shared) == (
        "mlp_softmax", "swiglu", 0)
    assert job.table.positions == "rotary"
    job.step(job.feed(0))
    (chosen,) = job.routed          # the step hands its choice back
    assert sorted(chosen) == ["layer_0", "layer_1", "layer_2"]
    assert chosen["layer_1"].shape == (2 * 128, 1)


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_the_cca_moe_comparison(seed):
    run = tiny_cca_moe.make_run(seed)
    job = train_cca_moe.CcaMoeJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    control, ref = train_cca_moe.control_readings(run, like, "fp8_e4m3")
    train_moe_hybrid.compare(run, control, ref)
    assert run.correct is False
    failed = [c[0] for c in run.checks if not c[3]]
    # By the arithmetic (the reference took the control's experts, so no
    # moved pair is in this gap) and by the routers' agreement, each alone.
    assert "first_grad_norm_worst_leaf_gap" in failed
    assert "router_pairs_differing_share" in failed


def test_a_cca_moe_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss, chosen = self.step_fn(*copy, batch)
        self.routed.append(chosen)
        return loss

    monkeypatch.setattr(train_cca_moe.CcaMoeJob, "step", frozen_step)
    line, run = tiny_cca_moe.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert "param_change_norm_worst_leaf_gap" in failed


def test_a_gated_products_weight_gradient_in_fp8_is_not_correct(
        monkeypatch):
    """A lower precision in the timed step's backward pass alone — the
    grouped matmuls' weight gradients (gate, up and down) from operands
    rounded to fp8 — moves no loss and no choice of experts, and fails by
    the gradient's limit."""
    from jax import lax

    from chainermn_tpu.ops import grouped_matmul

    sound = grouped_matmul._dw_call

    def fp8_dw(rows, cols, *args, **kwargs):
        return sound(lax.reduce_precision(rows, 4, 3),
                     lax.reduce_precision(cols, 4, 3), *args, **kwargs)

    monkeypatch.setattr(grouped_matmul, "_dw_call", fp8_dw)
    line, run = tiny_cca_moe.tiny_run(seed=2**31 + 9, seconds=0.3)
    assert line["correct"] is False
    checks = {c[0]: c for c in run.checks}
    assert not checks["first_grad_norm_worst_leaf_gap"][3]
    assert checks["first_steps_loss_rel_gap"][3]
    assert checks["router_pairs_differing_share"][3]


def test_a_router_that_drops_the_carried_state_is_not_correct(monkeypatch):
    """The broken timed path this cell can have and the others cannot: a
    layer that starts its router's state from zeros instead of what the
    layer before handed on.  Its choices are then not the reference's
    (which is asked about the state it carried itself), and the weights
    of the experts it took are others."""
    import jax.numpy as jnp

    from chainermn_tpu.parallel import moe_dropless

    sound = moe_dropless.route_mlp_softmax

    def dropped(h, state, *args, **kwargs):
        return sound(h, jnp.zeros_like(state), *args, **kwargs)

    monkeypatch.setattr(moe_dropless, "route_mlp_softmax", dropped)
    line, run = tiny_cca_moe.tiny_run(seed=2**31 + 11, seconds=0.3)
    assert line["correct"] is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert "router_pairs_differing_share" in failed
    assert "first_grad_norm_worst_leaf_gap" in failed


def test_a_loop_that_drops_the_balancing_controller_is_not_correct(
        monkeypatch):
    """The controller's step is the loop's, beside the optimizer's: a
    timed loop without it leaves every balancing bias where AdamW's decay
    puts it: the parameters' change says so, and from the second step on
    its routers choose by other biases than the reference's."""
    monkeypatch.setattr(
        train_cca_moe.CcaMoeJob, "step",
        train_moe_hybrid.MoeHybridJob.step)
    line, run = tiny_cca_moe.tiny_run(seed=2**31 + 13, seconds=0.3)
    assert line["correct"] is False
    failed = [c[0] for c in run.checks if not c[3]]
    assert failed == ["param_change_norm_worst_leaf_gap",
                      "router_pairs_differing_share"]
