"""The ``train_gswa_moe`` runner end to end at a tiny size on the CPU, its
controls failing as the other runners' fail, and broken TIMED paths of
this family's own, each failing a limit: a sliding row that lost its
window, a row without its gate, a full row that turns its whole head or
lost its attention factor — which what the step's own attention rows
added to the stream tells from a sound step — a router that skips the
renormalisation, a step that returns its state unchanged."""

import pytest

from chipbench.runners import train_gswa_moe
from chipbench.tests import tiny_gswa_moe

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
ROWS = ("attention_layer_1_worst_row_gap", "attention_layer_4_worst_row_gap")


def failed_checks(run):
    return [c[0] for c in run.checks if not c[3]]


@pytest.fixture(scope="module")
def gswa_line():
    return tiny_gswa_moe.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_gswa_moe_run_is_correct_and_shaped(gswa_line):
    line, run = gswa_line
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
                 "window_loss_last_minus_first", *ROWS):
        assert name in checks, name
    assert checks["moe_pairs_past_bound"][1:3] == (0, 0)
    assert 0.0 <= checks["router_pairs_differing_share"][1] < 0.012
    assert all(0.0 < checks[name][1] < 0.02 for name in ROWS)


def test_gswa_moe_job_is_built_from_the_published_keys():
    run = tiny_gswa_moe.make_run(1)
    job = train_gswa_moe.GswaMoeJob(run.config, run.mix, run.devices)
    job.reset(1)
    assert sorted(job.params["layer_0"]) == [
        "GatedFeedForward_0", "MultiHeadAttention_0", "RMSNorm_0",
        "RMSNorm_1"]
    assert all(sorted(job.params[f"layer_{i}"]) == [
        "ExpertLayer_0", "MultiHeadAttention_0", "RMSNorm_0", "RMSNorm_1"]
        for i in range(1, 5))
    assert "layer_5" not in job.params       # n_layer cuts the pattern
    e = job.params["layer_1"]["ExpertLayer_0"]
    assert e["router"].shape == (64, 16)     # the published width
    assert e["experts_up"].shape == (4, 48, 64)     # the experts held
    assert e["shared"]["wi"]["kernel"].shape == (64, 96)
    att = [job.params[f"layer_{i}"]["MultiHeadAttention_0"]
           for i in range(5)]
    assert [a["query"]["kernel"].shape[1] for a in att] == [4, 6, 6, 6, 4]
    assert [a["gate"]["kernel"].shape for a in att] == [
        (64, 4), (64, 6), (64, 6), (64, 6), (64, 4)]
    assert all("q_norm" not in a for a in att)
    assert job.params["lm_head"].shape == (211, 64)
    # the rows' shapes, windows, gates and rotary positions are the
    # table's, from the published keys: the runner states none
    rows = job.table.layers
    assert [r.window for r in rows] == [None, 48, 48, 48, None]
    assert [r.n_heads for r in rows] == [4, 6, 6, 6, 4]
    assert [r.rotary_dim for r in rows] == [8, 16, 16, 16, 8]
    assert [r.yarn is not None for r in rows] == [True] + [False] * 3 + [
        True]
    assert all(r.head_gate and not r.qk_norm for r in rows)
    assert rows[0].ffn == "swiglu" and rows[0].d_ff == 128
    spec = rows[1].experts
    assert spec.experts_held == (4, 4) and spec.top_k == 3
    assert spec.router == "sigmoid" and spec.d_shared == 48
    assert spec.scaling == 2.5
    job.step(job.feed(0))
    (chosen,) = job.routed          # the step hands its choice back
    (added,) = job.attention        # and its compared attention rows
    assert sorted(chosen) == [f"layer_{i}" for i in range(1, 5)]
    assert chosen["layer_1"].shape == (2 * 128, 3)
    assert sorted(added) == ["layer_1", "layer_4"]
    assert added["layer_1"].shape == (2, 128, 64)
    load = train_gswa_moe.routing_load(run.config, chosen)
    assert all(s["pairs"] == 768 for s in load.values())


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_fp8_reference_fails_and_the_bfloat16_one_passes(seed):
    run = tiny_gswa_moe.make_run(seed)
    job = train_gswa_moe.GswaMoeJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    control, ref = train_gswa_moe.control_readings(run, like, "fp8_e4m3")
    train_gswa_moe.compare_all(run, control, ref)
    assert run.correct is False
    assert set(ROWS) <= set(failed_checks(run))
    run = tiny_gswa_moe.make_run(seed)
    rounded, ref = train_gswa_moe.control_readings(run, like, "bfloat16")
    train_gswa_moe.compare_all(run, rounded, ref)
    assert run.correct is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss, (chosen, added) = self.step_fn(*copy, batch)
        self.routed.append(chosen)
        if len(self.attention) < int(self.mix["reference_steps"]):
            self.attention.append(added)
        return loss

    monkeypatch.setattr(train_gswa_moe.GswaMoeJob, "step", frozen_step)
    line, run = tiny_gswa_moe.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    assert "param_change_norm_worst_leaf_gap" in failed_checks(run)


def test_a_sliding_row_that_lost_its_window_is_not_correct(monkeypatch):
    """The adapter drops what the row hands it: every row attends the
    whole triangle.  The sliding row's check fails, the full row's
    holds."""
    import importlib

    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "row_window", lambda own, handed: None)
    line, run = tiny_gswa_moe.tiny_run(seed=2**31 + 9, seconds=0.3)
    assert line["correct"] is False
    assert ROWS[0] in failed_checks(run)


def test_a_row_without_its_gate_is_not_correct(monkeypatch):
    """``sigmoid`` in the rows' gate replaced by 1: the heads' outputs
    reach ``W_o`` ungated, in both row kinds."""
    import jax.numpy as jnp

    from chainermn_tpu.models import transformer

    class Ungated:
        def __getattr__(self, name):
            return getattr(transformer_nn, name)

        @staticmethod
        def sigmoid(x):
            return jnp.ones_like(x)

    transformer_nn = transformer.nn
    monkeypatch.setattr(transformer, "nn", Ungated())
    line, run = tiny_gswa_moe.tiny_run(seed=2**31 + 12, seconds=0.3)
    assert line["correct"] is False
    assert set(ROWS) <= set(failed_checks(run))


@pytest.mark.parametrize("broken", ["whole_head", "factor"])
def test_a_full_row_that_rotates_otherwise_is_not_correct(
        monkeypatch, broken):
    """The full row turning its whole head (the blend over all of it) in
    place of half, or its attention factor replaced by 1: the full row's
    check fails."""
    from chainermn_tpu.models import block_table, transformer

    real = transformer.rotate_partial

    def wrong_width(x, positions, rotary_dim, theta, yarn=None, *rest):
        if yarn is not None:
            rotary_dim = x.shape[-1]
        return real(x, positions, rotary_dim, theta, yarn, *rest)

    def wrong_factor(rotary_dim, theta, yarn=None):
        freq, scale = block_table.rotary_frequencies(rotary_dim, theta, yarn)
        return freq, 1.0

    if broken == "whole_head":
        monkeypatch.setattr(transformer, "rotate_partial", wrong_width)
    else:
        monkeypatch.setattr(transformer, "rotary_frequencies", wrong_factor)
    line, run = tiny_gswa_moe.tiny_run(seed=2**31 + 10, seconds=0.3)
    assert line["correct"] is False
    assert ROWS[1] in failed_checks(run)
    checks = {c[0]: c for c in run.checks}
    assert checks[ROWS[0]][3]           # the sliding row is plain: sound


def test_a_router_that_skips_the_renormalisation_is_not_correct(monkeypatch):
    """Weights ``scaling x s[chosen]`` themselves, not over their sum: the
    same experts, so the routers agree, and another layer."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chainermn_tpu.parallel import moe_dropless

    def raw(h, w_router, bias, *, top_k, scaling=1.0, n_group=0,
            topk_group=0):
        s = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), w_router,
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(s + lax.stop_gradient(bias), top_k)
        chosen = chosen.astype(jnp.int32)
        return chosen, jnp.take_along_axis(s, chosen, axis=-1) * scaling

    monkeypatch.setattr(moe_dropless, "route", raw)
    line, run = tiny_gswa_moe.tiny_run(seed=2**31 + 11, seconds=0.3)
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
    checks = {c[0]: c for c in run.checks}
    assert checks["moe_pairs_past_bound"][3]
