"""The ``train_swa_moe`` runner end to end at a tiny size on the CPU, its
controls failing as the other runners' fail, and broken timed paths of
this family's own — a sliding row that lost its window, a full row whose
YaRN blend or attention factor was dropped, a router that skips the
renormalisation — each failing a limit."""

import pytest

from chipbench.runners import train_swa_moe
from chipbench.tests import tiny_swa_moe

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def failed_checks(run):
    return [c[0] for c in run.checks if not c[3]]


@pytest.fixture(scope="module")
def swa_line():
    return tiny_swa_moe.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_swa_moe_run_is_correct_and_shaped(swa_line):
    line, run = swa_line
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


def test_swa_moe_job_is_built_from_the_published_keys():
    run = tiny_swa_moe.make_run(1)
    job = train_swa_moe.SwaMoeJob(run.config, run.mix, run.devices)
    job.reset(1)
    assert all(sorted(job.params[f"layer_{i}"]) == [
        "ExpertLayer_0", "MultiHeadAttention_0", "RMSNorm_0", "RMSNorm_1"]
        for i in range(4))
    assert "layer_4" not in job.params       # n_layer cuts the pattern
    e = job.params["layer_1"]["ExpertLayer_0"]
    assert e["router"].shape == (64, 16)     # the published width
    assert e["experts_up"].shape == (4, 48, 64)     # the experts held
    assert "shared" not in e and "router_bias" not in e
    assert job.params["lm_head"].shape == (211, 64)
    # the rows' windows and rotary positions are the table's, from the
    # published keys: the runner states none
    rows = job.table.layers
    assert [r.window for r in rows] == [48, 48, 48, None]
    assert [r.yarn is not None for r in rows] == [False] * 3 + [True]
    assert rows[3].yarn.factor == 4 and rows[3].yarn.scale == pytest.approx(
        1.1386294361119891)
    assert all(r.qk_norm and r.rotary_dim == 16 for r in rows)
    spec = rows[1].experts
    assert spec.experts_held == (4, 4) and spec.top_k == 3
    assert spec.router == "softmax" and not spec.d_shared
    job.step(job.feed(0))
    (chosen,) = job.routed          # the step hands its choice back
    assert sorted(chosen) == [f"layer_{i}" for i in range(4)]
    assert chosen["layer_1"].shape == (2 * 128, 3)
    load = train_swa_moe.routing_load(run.config, chosen)
    assert all(s["pairs"] == 768 and s["buffer_tiles"] == 7
               for s in load.values())


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_and_the_bfloat16_one_passes(seed):
    run = tiny_swa_moe.make_run(seed)
    job = train_swa_moe.SwaMoeJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    control, ref = train_swa_moe.control_readings(run, like, "fp8_e4m3")
    train_swa_moe.compare(run, control, ref)
    assert run.correct is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
    run = tiny_swa_moe.make_run(seed)
    rounded, ref = train_swa_moe.control_readings(run, like, "bfloat16")
    train_swa_moe.compare(run, rounded, ref)
    assert run.correct is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss, chosen = self.step_fn(*copy, batch)
        self.routed.append(chosen)
        return loss

    monkeypatch.setattr(train_swa_moe.SwaMoeJob, "step", frozen_step)
    line, run = tiny_swa_moe.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    assert "param_change_norm_worst_leaf_gap" in failed_checks(run)


def test_a_sliding_row_that_lost_its_window_is_not_correct(monkeypatch):
    """The adapter drops what the row hands it: every row attends the
    whole triangle."""
    import importlib

    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "row_window", lambda own, handed: None)
    line, run = tiny_swa_moe.tiny_run(seed=2**31 + 9, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)


@pytest.mark.parametrize("broken", ["blend", "factor"])
def test_a_full_row_with_plain_rotary_positions_is_not_correct(
        monkeypatch, broken):
    """YaRN's blended frequencies replaced by the plain ones, or its
    attention factor by 1: the full row's logits move."""
    from chainermn_tpu.models import block_table, transformer

    def wrong(rotary_dim, theta, yarn=None):
        freq, scale = block_table.rotary_frequencies(rotary_dim, theta, yarn)
        if yarn is None:
            return freq, scale
        if broken == "factor":
            return freq, 1.0
        return block_table.rotary_frequencies(rotary_dim, theta)[0], scale

    monkeypatch.setattr(transformer, "rotary_frequencies", wrong)
    line, run = tiny_swa_moe.tiny_run(seed=2**31 + 10, seconds=0.3)
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
    line, run = tiny_swa_moe.tiny_run(seed=2**31 + 11, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
    checks = {c[0]: c for c in run.checks}
    assert checks["moe_pairs_past_bound"][3]
