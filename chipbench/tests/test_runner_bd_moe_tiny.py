"""The ``train_bd_moe`` runner end to end at a tiny size on the CPU, its
controls failing as the other runners' fail, and broken TIMED paths of
this objective's own, each failing a limit: the adapter dropping the
block a row hands it, a row handing the wrong block length, a clean row
that sees noisy keys (which the step's own first attention alone tells
from a sound step), positions 0 .. 2L-1, the weights dropped from the
loss."""

import pytest

from chipbench.runners import train_bd_moe
from chipbench.tests import tiny_bd_moe

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def failed_checks(run):
    return [c[0] for c in run.checks if not c[3]]


@pytest.fixture(scope="module")
def bd_line():
    return tiny_bd_moe.tiny_run(seed=2**31 + 7, seconds=0.6)


def test_bd_moe_run_is_correct_and_shaped(bd_line):
    line, run = bd_line
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    checks = {c[0]: c for c in run.checks}
    for name in ("first_grad_norm_worst_leaf_gap",
                 "param_change_norm_worst_leaf_gap",
                 "first_steps_loss_rel_gap", "moe_pairs_past_bound",
                 "window_nonfinite_losses",
                 "blockdiff_attention_worst_row_gap"):
        assert name in checks, name
    # the routers' share is printed, not held to a limit
    assert "router_pairs_differing_share" not in checks
    assert checks["moe_pairs_past_bound"][1:3] == (0, 0)
    assert 0.0 < checks["blockdiff_attention_worst_row_gap"][1] < 0.03


def test_bd_moe_job_is_built_from_the_published_keys():
    run = tiny_bd_moe.make_run(1)
    job = train_bd_moe.BdMoeJob(run.config, run.mix, run.devices)
    job.reset(1)
    assert all(sorted(job.params[f"layer_{i}"]) == [
        "ExpertLayer_0", "MultiHeadAttention_0", "RMSNorm_0", "RMSNorm_1"]
        for i in range(3))
    assert "layer_3" not in job.params       # n_layer cuts the stack
    e = job.params["layer_1"]["ExpertLayer_0"]
    assert e["router"].shape == (64, 16)     # the published width
    assert e["experts_up"].shape == (4, 48, 64)     # the experts held
    assert job.params["lm_head"].shape == (211, 64)
    # the mask is the table's, from the file's block: the runner states none
    assert job.table.block_diffusion == 4
    assert all(r.window is None and r.qk_norm and r.rotary_dim == 16
               for r in job.table.layers)
    x0, xt, w = job.batches(0)
    assert x0.shape == xt.shape == w.shape == (2, 128)
    assert x0.max() < 210 and set(xt[xt != x0]) == {210}   # the mask id
    job.step(job.feed(0))
    (chosen,) = job.routed          # the step hands its choice back,
    (counters,) = job.counters      # its counters beside it,
    (attention,) = job.attention    # and its first attention row's output
    assert attention.shape == (2, 2 * 128, 64)
    assert sorted(chosen) == [f"layer_{i}" for i in range(3)]
    assert chosen["layer_1"].shape == (2 * 2 * 128, 3)    # [x0 ; xt]
    assert int(counters["masked_rows"]) == int((xt != x0).sum())
    assert float(counters["weight_sum"]) == pytest.approx(float(w.sum()))
    load = train_bd_moe.routing_load(run.config, chosen)
    assert all(s["pairs"] == 1536 for s in load.values())


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2])
def test_fp8_reference_fails_and_the_bfloat16_one_passes(seed):
    run = tiny_bd_moe.make_run(seed)
    job = train_bd_moe.BdMoeJob(run.config, run.mix, run.devices)
    like = {"replicated": job.replicated, "rows": job.rows}
    control, ref = train_bd_moe.control_readings(run, like, "fp8_e4m3")
    train_bd_moe.compare_all(run, control, ref)
    assert run.correct is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)
    assert "blockdiff_attention_worst_row_gap" in failed_checks(run)
    run = tiny_bd_moe.make_run(seed)
    rounded, ref = train_bd_moe.control_readings(run, like, "bfloat16")
    train_bd_moe.compare_all(run, rounded, ref)
    assert run.correct is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def frozen_step(self, batch):
        copy = jax.tree.map(jnp.copy, (self.params, self.state))
        _, _, loss, (chosen, counters, attention) = self.step_fn(
            *copy, batch)
        self.routed.append(chosen)
        self.counters.append(counters)
        self.attention = (self.attention + [attention])[
            :self.mix["reference_steps"]]
        return loss

    monkeypatch.setattr(train_bd_moe.BdMoeJob, "step", frozen_step)
    line, run = tiny_bd_moe.tiny_run(seed=5, seconds=0.3)
    assert line["correct"] is False
    assert "param_change_norm_worst_leaf_gap" in failed_checks(run)


def test_an_adapter_that_drops_the_rows_block_is_not_correct(monkeypatch):
    """The adapter drops what the row hands it: every row attends the
    causal triangle of the 2 L rows, so a noisy row sees its own clean
    token."""
    import importlib

    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "row_mask", lambda handed: {})
    line, run = tiny_bd_moe.tiny_run(seed=2**31 + 9, seconds=0.3)
    assert line["correct"] is False
    failed = failed_checks(run)
    assert "first_grad_norm_worst_leaf_gap" in failed
    assert "blockdiff_attention_worst_row_gap" in failed


def test_a_row_that_hands_the_wrong_block_length_is_not_correct(
        monkeypatch):
    """Blocks of 8 where the table says 4: a clean row sees the clean
    tokens after its block, a noisy row its neighbours' noise."""
    import importlib

    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    real = fa.row_mask
    monkeypatch.setattr(fa, "row_mask", lambda handed: {
        k: 2 * v if k == "block_diffusion" else v
        for k, v in real(handed).items()})
    line, run = tiny_bd_moe.tiny_run(seed=2**31 + 12, seconds=0.3)
    assert line["correct"] is False
    assert "blockdiff_attention_worst_row_gap" in failed_checks(run)


def test_a_clean_row_that_sees_noisy_keys_is_not_correct(monkeypatch):
    """The kernels' walk enters, for a clean q tile, the noisy tile on
    its diagonal too (where the in-tile mask reads ``blk(k) == blk(q)``):
    a clean row sees the noisy keys of its block.  No norm of the first
    steps moves past a sound run's (a branch is a hundredth of the
    stream): the step's own first attention, row by row, is the one
    number that tells."""
    import importlib

    import jax
    import numpy as np

    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    real = fa._blockdiff_tiles

    def leaky(L, B, block_q, block_k):
        live, full = real(L, B, block_q, block_k)
        n_q, n_k = L // block_q, L // block_k
        iq = np.arange(2 * n_q)[:, None]
        ik = np.arange(2 * n_k)[None, :]
        q0, k0 = (iq % n_q) * block_q // B, (ik % n_k) * block_k // B
        q1 = ((iq % n_q) * block_q + block_q - 1) // B
        k1 = ((ik % n_k) * block_k + block_k - 1) // B
        return live | ((iq < n_q) & (ik >= n_k) & (k0 <= q1) & (q0 <= k1)
                       ), full

    def forget():
        # the walk's tables are cached, and so are the kernels' traces
        # that hold them
        fa._blockdiff_walk.cache_clear()
        jax.clear_caches()

    monkeypatch.setattr(fa, "_blockdiff_tiles", leaky)
    forget()
    try:
        line, run = tiny_bd_moe.tiny_run(seed=2**31 + 13, seconds=0.3)
    finally:
        forget()
    assert line["correct"] is False
    assert failed_checks(run) == ["blockdiff_attention_worst_row_gap"]


def test_positions_over_both_copies_are_not_correct(monkeypatch):
    """Positions 0 .. 2L-1 in place of 0 .. L-1 twice."""
    import jax.numpy as jnp

    from chainermn_tpu.models import block_diffusion

    real = jnp.tile
    monkeypatch.setattr(
        block_diffusion.jnp, "tile",
        lambda a, reps: jnp.arange(a.shape[0] * reps) if reps == 2
        else real(a, reps))
    try:
        line, run = tiny_bd_moe.tiny_run(seed=2**31 + 10, seconds=0.3)
    finally:
        monkeypatch.undo()
    assert line["correct"] is False
    assert "first_grad_norm_worst_leaf_gap" in failed_checks(run)


def test_a_loss_without_its_weights_is_not_correct(monkeypatch):
    """``1 / t`` dropped: every masked row counts once."""
    from chainermn_tpu.models import block_diffusion

    real = block_diffusion.fused_cross_entropy

    def unweighted(hidden, head, labels, *, weights, **kw):
        return real(hidden, head, labels,
                    weights=(weights > 0).astype(weights.dtype), **kw)

    monkeypatch.setattr(block_diffusion, "fused_cross_entropy", unweighted)
    line, run = tiny_bd_moe.tiny_run(seed=2**31 + 11, seconds=0.3)
    assert line["correct"] is False
    assert "first_steps_loss_rel_gap" in failed_checks(run)
