"""The ``train_kda_mla_moe`` runner end to end at a tiny size on the CPU,
its controls failing as the other runners' fail, and broken timed paths of
this family's own — a scan that drops the decay's channel dependence, a
router without the group step, values read at the scores' width from
padded lanes, a shared expert left out — each failing a limit."""

import numpy as np
import pytest

from chipbench.runners import train_kda_mla_moe
from chipbench.tests import tiny_kda_mla_moe

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def failed_checks(run):
    return [c[0] for c in run.checks if not c[3]]


@pytest.fixture(scope="module")
def line_and_run():
    return tiny_kda_mla_moe.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_run_is_correct_and_shaped(line_and_run):
    line, run = line_and_run
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
    assert 0.0 <= checks["router_pairs_differing_share"][1] < 0.025


def test_job_is_built_from_the_published_keys():
    run = tiny_kda_mla_moe.make_run(1)
    job = train_kda_mla_moe.KdaMlaMoeJob(run.config, run.mix, run.devices)
    job.reset(1)
    kinds = [sorted(k for k in job.params[f"layer_{i}"]
                    if not k.startswith("RMSNorm")) for i in range(3)]
    assert kinds == [["GatedFeedForward_0", "KDAMixer_0"],
                     ["ExpertLayer_0", "KDAMixer_0"],
                     ["ExpertLayer_0", "MLAMixer_0"]]
    assert "layer_3" not in job.params       # n_layer cuts the pattern
    e = job.params["layer_1"]["ExpertLayer_0"]
    assert e["router"].shape == (64, 16)     # the published width
    assert e["router_bias"].shape == (16,)
    assert e["experts_up"].shape == (4, 48, 64)     # the experts held
    assert job.params["lm_head"].shape == (211, 64)
    spec = job.table.layers[1].experts
    assert spec.experts_held == (2, 4) and spec.top_k == 3
    assert (spec.router, spec.n_group, spec.topk_group) == ("sigmoid", 4, 2)
    assert job.table.layers[2].mla.d_qk == 24
    before = np.asarray(e["router_bias"])
    job.step(job.feed(0))
    (chosen,) = job.routed          # the step hands its choice back
    assert sorted(chosen) == ["layer_1", "layer_2"]
    assert chosen["layer_1"].shape == (2 * 128, 3)
    # the balancing controller stepped beside the optimizer: a move AdamW's
    # decay of a 0.01-scale bias at this rate cannot make
    after = np.asarray(job.params["layer_1"]["ExpertLayer_0"]["router_bias"])
    assert np.max(np.abs(after - before)) > 1e-4
    load = train_kda_mla_moe.routing_load(run.config, chosen)
    assert all(s["pairs"] == 768 and 0.0 <= s["held_group_token_share"] <= 1
               for s in load.values())


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_and_the_bfloat16_one_passes(seed):
    run = tiny_kda_mla_moe.make_run(seed)
    job = train_kda_mla_moe.KdaMlaMoeJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    control, ref = train_kda_mla_moe.control_readings(run, like, "fp8_e4m3")
    train_kda_mla_moe.compare(run, control, ref)
    assert run.correct is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
    assert "router_pairs_differing_share" in failed_checks(run)
    run = tiny_kda_mla_moe.make_run(seed)
    rounded, ref = train_kda_mla_moe.control_readings(run, like, "bfloat16")
    train_kda_mla_moe.compare(run, rounded, ref)
    assert run.correct is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss, chosen = self.step_fn(*copy, batch)
        self.routed.append(chosen)
        return loss

    monkeypatch.setattr(train_kda_mla_moe.KdaMlaMoeJob, "step", frozen_step)
    line, run = tiny_kda_mla_moe.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    assert "param_change_norm_worst_leaf_gap" in failed_checks(run)


def test_a_scan_that_drops_the_channel_dependence_is_not_correct(
        monkeypatch):
    """The timed path's rule with every channel of a head decaying by the
    head's MEAN log-decay (the scalar rule's shape): the first gradient
    leaves its limit."""
    import jax.numpy as jnp

    from chainermn_tpu.ops import kda

    sound = kda._chunked
    monkeypatch.setattr(
        kda, "_chunked", lambda q, k, v, g, beta, C: sound(
            q, k, v, jnp.broadcast_to(
                jnp.mean(g, axis=-1, keepdims=True), g.shape), beta, C))
    line, run = tiny_kda_mla_moe.tiny_run(seed=2**31 + 9, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)


def test_a_router_without_the_group_step_is_not_correct(monkeypatch):
    """The eight best of all the experts, whatever their groups: other
    experts, so the routers' share leaves its limit."""
    from chainermn_tpu.parallel import moe_dropless

    monkeypatch.setattr(moe_dropless, "keep_groups",
                        lambda biased, n_group, topk_group: biased)
    line, run = tiny_kda_mla_moe.tiny_run(seed=2**31 + 10, seconds=0.3)
    assert line["correct"] is False
    assert "router_pairs_differing_share" in failed_checks(run)


def test_values_read_at_the_scores_width_are_not_correct(monkeypatch):
    """The latent row's values laid out at the scores' width (zero lanes
    after each head's 16) and read back as if they were 16 apart: every
    head but the first reads another's lanes."""
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    sound = fa.flash_attention

    def padded(q, k, v, **kw):
        if v.shape[-1] != q.shape[-1]:
            B, S, H, Dv = v.shape
            wide = jnp.pad(v, [(0, 0)] * 3 + [(0, q.shape[-1] - Dv)])
            v = wide.reshape(B, S, -1)[..., :H * Dv].reshape(B, S, H, Dv)
        return sound(q, k, v, **kw)

    monkeypatch.setattr(fa, "flash_attention", padded)
    line, run = tiny_kda_mla_moe.tiny_run(seed=2**31 + 11, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)


def test_a_shared_expert_left_out_is_not_correct(monkeypatch):
    from chainermn_tpu.models import transformer

    sound = transformer.GatedFeedForward.__call__

    def hollow(self, x):
        out = sound(self, x)
        return 0.0 * out if self.name == "shared" else out

    monkeypatch.setattr(transformer.GatedFeedForward, "__call__", hollow)
    line, run = tiny_kda_mla_moe.tiny_run(seed=2**31 + 12, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
