"""The ``train_gdn_moe`` runner end to end at a tiny size on the CPU, its
controls failing as the other runners' fail, and broken timed paths of
this family's own — a scan that drops the decay, a router that skips the
renormalisation, a shared expert without its gate — each failing a
limit."""

import numpy as np
import pytest

from chipbench.runners import train_gdn_moe
from chipbench.tests import tiny_gdn_moe

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def failed_checks(run):
    return [c[0] for c in run.checks if not c[3]]


@pytest.fixture(scope="module")
def gdn_line():
    return tiny_gdn_moe.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_gdn_moe_run_is_correct_and_shaped(gdn_line):
    line, run = gdn_line
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    checks = {c[0]: c for c in run.checks}
    for name in ("first_grad_norm_worst_leaf_gap",
                 "param_change_norm_worst_leaf_gap",
                 "first_steps_loss_rel_gap", "moe_pairs_past_bound",
                 "router_pairs_differing_share", "window_nonfinite_losses",
                 "window_loss_last_minus_first"):
        assert name in checks, name
    assert checks["moe_pairs_past_bound"][1:3] == (0, 0)
    assert 0.0 <= checks["router_pairs_differing_share"][1] < 0.012


def test_gdn_moe_job_is_built_from_the_published_keys():
    run = tiny_gdn_moe.make_run(1)
    job = train_gdn_moe.GdnMoeJob(run.config, run.mix, run.devices)
    job.reset(1)
    kinds = [sorted(k for k in job.params[f"layer_{i}"]
                    if not k.startswith("ZeroCentredRMSNorm"))
             for i in range(4)]
    assert kinds == [["ExpertLayer_0", "GatedDeltaNetMixer_0"]] * 3 + [
        ["ExpertLayer_0", "MultiHeadAttention_0"]]
    assert "layer_4" not in job.params       # n_layer cuts the pattern
    e = job.params["layer_1"]["ExpertLayer_0"]
    assert e["router"].shape == (64, 16)     # the published width
    assert e["experts_up"].shape == (4, 48, 64)     # the experts held
    assert job.params["lm_head"].shape == (211, 64)
    spec = job.table.layers[1].experts
    assert spec.experts_held == (4, 4) and spec.top_k == 3
    assert spec.router == "softmax" and spec.shared_gate
    job.step(job.feed(0))
    (chosen,) = job.routed          # the step hands its choice back
    assert sorted(chosen) == [f"layer_{i}" for i in range(4)]
    assert chosen["layer_1"].shape == (2 * 128, 3)
    load = train_gdn_moe.routing_load(run.config, chosen)
    assert all(s["pairs"] == 768 and s["buffer_tiles"] == 7
               for s in load.values())
    assert 0.0 < train_gdn_moe.tile_fill([load]) <= 1.0


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_and_the_bfloat16_one_passes(seed):
    run = tiny_gdn_moe.make_run(seed)
    job = train_gdn_moe.GdnMoeJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    control, ref = train_gdn_moe.control_readings(run, like, "fp8_e4m3")
    train_gdn_moe.compare(run, control, ref)
    assert run.correct is False
    # By the arithmetic (the reference took the control's experts, so no
    # moved pair is in this gap) and by the routers' agreement, each alone.
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
    assert "router_pairs_differing_share" in failed_checks(run)
    run = tiny_gdn_moe.make_run(seed)
    rounded, ref = train_gdn_moe.control_readings(run, like, "bfloat16")
    train_gdn_moe.compare(run, rounded, ref)
    assert run.correct is True


def test_the_reference_takes_the_choice_it_is_given():
    """With the float32 reference's own choice forced back on it the
    readings are its own, and with pairs moved they are not: the forced
    experts are the ones computed with."""
    run = tiny_gdn_moe.make_run(2**31 + 4)
    job = train_gdn_moe.GdnMoeJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    own = train_gdn_moe.reference_readings(run, like)
    chosen = train_gdn_moe.chosen_from_masks(own["chosen"], 3)
    same = train_gdn_moe.reference_readings(run, like, forced=chosen)
    assert same["losses"] == pytest.approx(own["losses"], rel=1e-6)
    first = chosen[0]["layer_1"]
    first[:, 0] = (first[:, 0] + 1 + np.arange(len(first)) % 2) % 16
    rows = [len(set(r)) == 3 for r in first]    # keep three distinct
    first[~np.array(rows)] = [0, 1, 2]
    moved = train_gdn_moe.reference_readings(run, like, forced=chosen)
    assert moved["losses"][0] != pytest.approx(own["losses"][0], rel=1e-6)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss, chosen = self.step_fn(*copy, batch)
        self.routed.append(chosen)
        return loss

    monkeypatch.setattr(train_gdn_moe.GdnMoeJob, "step", frozen_step)
    line, run = tiny_gdn_moe.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    assert "param_change_norm_worst_leaf_gap" in failed_checks(run)


def test_a_scan_that_drops_the_decay_is_not_correct(monkeypatch):
    """The timed path's delta rule with ``g`` = 0 (a state that never
    forgets): the loss, the first gradient or the parameters' change
    leaves its limit."""
    from chainermn_tpu.ops import gated_delta

    sound = gated_delta._chunked
    monkeypatch.setattr(
        gated_delta, "_chunked",
        lambda q, k, v, g, beta, C: sound(q, k, v, 0.0 * g, beta, C))
    line, run = tiny_gdn_moe.tiny_run(seed=2**31 + 9, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)


def test_a_router_that_skips_the_renormalisation_is_not_correct(monkeypatch):
    """Weights ``p[chosen]`` themselves, not over their sum: the same
    experts, so the routers agree, and another layer."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chainermn_tpu.parallel import moe_dropless

    def raw(h, w_router, *, top_k, scaling=1.0):
        p = jax.nn.softmax(jnp.dot(
            h.astype(jnp.float32), w_router,
            precision=lax.Precision.HIGHEST), axis=-1)
        _, chosen = lax.top_k(p, top_k)
        chosen = chosen.astype(jnp.int32)
        return chosen, jnp.take_along_axis(p, chosen, axis=-1) * scaling

    monkeypatch.setattr(moe_dropless, "route_softmax", raw)
    line, run = tiny_gdn_moe.tiny_run(seed=2**31 + 10, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
    checks = {c[0]: c for c in run.checks}
    assert checks["moe_pairs_past_bound"][3]


def test_a_shared_expert_without_its_gate_is_not_correct(monkeypatch):
    import flax.linen as nn

    monkeypatch.setattr(nn, "sigmoid", lambda x: 0.0 * x + 1.0)
    line, run = tiny_gdn_moe.tiny_run(seed=2**31 + 11, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
