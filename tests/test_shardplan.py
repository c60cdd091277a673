"""Declarative sharding-plan registry tests (docs/sharding.md).

Three contracts pin the subsystem:

* **Compatibility** — registry plan ``tp`` resolves leaf-for-leaf to the
  exact specs the retired hand-wired ``transformer_param_spec`` emitted,
  and the plan-driven gspmd train step tracks the spec-tree step.
* **Coverage** — every model in :mod:`chainermn_tpu.models` resolves
  every registry plan with zero unmatched leaves (lint rule R006's
  clean case).
* **TP decode** — an :class:`InferenceEngine` built with ``plan="tp"``
  on a model-axis mesh streams bit-identical tokens to the single-device
  oracle engine, greedy AND sampled.
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.parallel.sharding import (
    make_gspmd_train_step,
    transformer_param_spec,
)
from chainermn_tpu.sharding import (
    PlanRule,
    ShardingPlan,
    get_plan,
    list_plans,
    plans_for_mesh,
    register_plan,
    tree_path_str,
    validate,
)
from chainermn_tpu.tools.shardplan import MODEL_BUILDERS, model_params

from conftest import subprocess_env


@pytest.fixture(scope="module")
def dp_tp_mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    return Mesh(np.array(devs[:8]).reshape(4, 2), ("data", "model"))


@pytest.fixture(scope="module")
def model_mesh():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 devices")
    return Mesh(np.array(devs[:2]), ("model",))


def tiny_lm(**over):
    cfg = dict(vocab=64, d_model=32, n_heads=4, d_ff=64, n_layers=2,
               max_len=16, dtype=jnp.float32)
    cfg.update(over)
    return TransformerLM(**cfg)


def shape_params(model, *args, **kwargs):
    """Shape-only param tree (no compute) — plans resolve on paths and
    shapes, so eval_shape is all a resolution test needs."""
    out = jax.eval_shape(
        lambda k: model.init(k, *args, **kwargs), jax.random.PRNGKey(0)
    )
    return out["params"]


def flat_specs(tree):
    return {
        tree_path_str(path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_builtins():
    names = [p.name for p in list_plans()]
    assert names == ["dp", "dp_tp", "fsdp", "sp", "tp", "zero"]
    with pytest.raises(KeyError, match="registered plans"):
        get_plan("nope")
    with pytest.raises(ValueError, match="already registered"):
        register_plan(get_plan("dp"))


def test_plans_for_mesh_filters_axes(dp_tp_mesh):
    both = {p.name for p in plans_for_mesh(dp_tp_mesh)}
    assert both == {"dp", "dp_tp", "fsdp", "tp", "zero"}
    devs = jax.devices()
    data_only = Mesh(np.array(devs[:4]), ("data",))
    assert {p.name for p in plans_for_mesh(data_only)} == {
        "dp", "fsdp", "zero"
    }


# ---------------------------------------------------------------------------
# Compatibility: plan "tp" == transformer_param_spec, leaf for leaf
# ---------------------------------------------------------------------------


def test_tp_plan_matches_legacy_transformer_spec():
    lm = tiny_lm()
    params = shape_params(lm, jnp.ones((1, 8), jnp.int32))
    legacy = flat_specs(transformer_param_spec(params))
    plan = flat_specs(get_plan("tp").resolve(params))
    assert plan == legacy
    # and the interesting rows really shard
    assert any(s == P(None, "model", None) for s in plan.values())
    assert any(s == P("model", None) for s in plan.values())


def test_tp_plan_matches_legacy_vit_spec():
    from chainermn_tpu.models.vit import ViT

    m = ViT(num_classes=10, patch=4, d_model=32, n_heads=4, d_ff=64,
            n_layers=2)
    params = shape_params(m, jnp.ones((1, 16, 16, 3), jnp.float32),
                          train=False)
    legacy = flat_specs(transformer_param_spec(params))
    assert flat_specs(get_plan("tp").resolve(params)) == legacy


# ---------------------------------------------------------------------------
# Coverage: every model x every registry plan, zero unmatched leaves
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model_name", sorted(MODEL_BUILDERS))
def test_every_model_resolves_every_plan(model_name):
    from chainermn_tpu.analysis import analyze_plan

    params = model_params(model_name)
    for plan in list_plans():
        v = validate(plan, params)
        assert v.ok, f"{model_name} x {plan.name}: {v.render()}"
        assert v.unmatched == []
        report = analyze_plan(plan, params)
        assert not report.findings, report.render()
        assert "R006" in report.rules_run


def test_resolve_raises_on_unmatched_leaf():
    plan = ShardingPlan(
        name="partial",
        rules=(PlanRule("dense", r"dense/kernel$", P("data", None)),),
        axes=("data",),
    )
    params = {"dense": {"kernel": jnp.zeros((8, 8))},
              "other": {"kernel": jnp.zeros((8, 8))}}
    with pytest.raises(ValueError, match="has no rule matching leaf"):
        plan.resolve(params)
    v = validate(plan, params)
    assert not v.ok and v.unmatched == ["other/kernel"]


def test_scalars_replicate_without_a_rule():
    plan = get_plan("tp")
    out = plan.resolve({"w": jnp.zeros((4, 2, 8)), "step": jnp.zeros(())})
    assert out["step"] == P()


# ---------------------------------------------------------------------------
# Moments: one rule table drives optimizer state too
# ---------------------------------------------------------------------------


def test_moment_resolution_reuses_param_rules():
    params = {"attn": {"query": {"kernel": jnp.zeros((8, 4, 2)),
                                 "bias": jnp.zeros((4, 2))}}}
    opt_state = optax.adam(1e-3).init(params)
    specs = flat_specs(get_plan("tp").resolve_moments(opt_state))
    mu_q = [s for p, s in specs.items()
            if "mu" in p and p.endswith("query/kernel")]
    assert mu_q == [P(None, "model", None)]
    counts = [s for p, s in specs.items() if p.endswith("count")]
    assert counts and all(s == P() for s in counts)


def test_zero_plan_shards_moments_not_params():
    params = {"dense": {"kernel": jnp.zeros((8, 8))}}
    plan = get_plan("zero")
    assert plan.resolve(params)["dense"]["kernel"] == P()
    specs = flat_specs(plan.resolve_moments(optax.adam(1e-3).init(params)))
    mu = [s for p, s in specs.items()
          if "mu" in p and p.endswith("kernel")]
    assert mu == [P(None, "data")]


def test_opt_shard_miss_is_a_hard_error(dp_tp_mesh):
    """The spec-tree path's old shape-first-match fallback is gone: an
    optimizer leaf whose path embeds no parameter path must raise and
    NAME the leaf, never silently pick a same-shaped layout."""
    spec = {"w": P(None, "model")}
    _, shard_fn = make_gspmd_train_step(
        lambda p, b: jnp.sum(p["w"]), optax.sgd(0.1), dp_tp_mesh, spec,
        data_axis="data",
    )
    params = {"w": jnp.zeros((8, 8))}
    with pytest.raises(ValueError, match="mystery"):
        shard_fn(params, {"mystery": jnp.zeros((4, 4))})


# ---------------------------------------------------------------------------
# Plan-driven gspmd train step
# ---------------------------------------------------------------------------


def lm_loss_fn(lm):
    def loss(params, batch):
        logits = lm.apply(params, batch)
        targets = jnp.roll(batch, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    return loss


def test_plan_step_matches_spec_tree_step(dp_tp_mesh):
    lm = tiny_lm()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    params = lm.init(jax.random.PRNGKey(1), tokens)
    loss_fn = lm_loss_fn(lm)
    optimizer = optax.adam(1e-2)

    # Host copies per path: both steps donate their buffers.
    host = jax.tree.map(np.asarray, params)

    spec = {"params": transformer_param_spec(params["params"])}
    old_step, old_shard = make_gspmd_train_step(
        loss_fn, optimizer, dp_tp_mesh, spec, data_axis="data"
    )
    # Plan accepted by registry NAME, resolved lazily at shard_fn time.
    new_step, new_shard = make_gspmd_train_step(
        loss_fn, optimizer, dp_tp_mesh, "dp_tp", data_axis="data"
    )

    op, oo = old_shard(jax.tree.map(jnp.array, host),
                       optimizer.init(jax.tree.map(jnp.array, host)))
    np_, no = new_shard(jax.tree.map(jnp.array, host),
                        optimizer.init(jax.tree.map(jnp.array, host)))
    for _ in range(3):
        op, oo, old_loss = old_step(op, oo, tokens)
        np_, no, new_loss = new_step(np_, no, tokens)
    np.testing.assert_allclose(float(new_loss), float(old_loss),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(np_), jax.tree.leaves(op)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_plan_step_before_shard_fn_raises(dp_tp_mesh):
    step, _ = make_gspmd_train_step(
        lambda p, b: jnp.sum(p["w"]), optax.sgd(0.1), dp_tp_mesh, "dp",
        data_axis="data",
    )
    with pytest.raises(RuntimeError, match="before shard_fn"):
        step({"w": jnp.zeros((4,))}, None, jnp.zeros((8,)))


def test_plan_step_rejects_axisless_mesh():
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:2]), ("data",))
    with pytest.raises(ValueError, match="the mesh lacks"):
        make_gspmd_train_step(
            lambda p, b: jnp.sum(p["w"]), optax.sgd(0.1), mesh, "tp",
            data_axis="data",
        )


# ---------------------------------------------------------------------------
# Tensor-parallel decode: plan-sharded engine == single-device oracle
# ---------------------------------------------------------------------------


def make_engine_pair(model_mesh):
    from chainermn_tpu.serving import EngineConfig, InferenceEngine

    lm = TransformerLM(vocab=64, d_model=32, n_heads=4, d_ff=64,
                       n_layers=2, max_len=32, dtype=jnp.float32,
                       n_kv_heads=2)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = lm.init(jax.random.PRNGKey(0), tokens)["params"]
    cfg = EngineConfig(block_size=4, n_blocks=32, max_len=32, max_batch=4)
    oracle = InferenceEngine(lm, jax.tree.map(jnp.array, params), cfg)
    tp = InferenceEngine(lm, params, cfg, plan="tp", mesh=model_mesh)
    return oracle, tp


def test_tp_decode_bit_exact_greedy(model_mesh):
    oracle, tp = make_engine_pair(model_mesh)
    # the KV pages really shard over the model axis
    k_pages = jax.tree_util.tree_flatten_with_path(tp._cache)[0]
    paged = [l for path, l in k_pages if "pages" in str(path)]
    assert paged and all(
        "model" in str(l.sharding.spec) for l in paged
    )
    prompt = [5, 9, 3, 17, 2]
    assert tp.generate(prompt, 12) == oracle.generate(prompt, 12)


def test_tp_decode_bit_exact_sampling(model_mesh):
    from chainermn_tpu.serving import SamplingParams

    oracle, tp = make_engine_pair(model_mesh)
    sp = SamplingParams(temperature=0.8, top_k=5, seed=123)
    prompt = [5, 9, 3, 17, 2]
    assert (tp.generate(prompt, 12, sampling=sp)
            == oracle.generate(prompt, 12, sampling=sp))


def test_engine_plan_requires_mesh():
    from chainermn_tpu.serving import EngineConfig, InferenceEngine

    lm = tiny_lm(max_len=32)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = EngineConfig(block_size=4, n_blocks=16, max_len=32, max_batch=2)
    with pytest.raises(ValueError, match="mesh"):
        InferenceEngine(lm, params, cfg, plan="tp")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.shardplan", *argv],
        capture_output=True, text=True, env=subprocess_env(),
        timeout=600,
    )


def test_cli_list_show_lint():
    r = _run_cli("--list", "--format", "json")
    assert r.returncode == 0, r.stderr
    names = [p["name"] for p in json.loads(r.stdout)["plans"]]
    assert names == ["dp", "dp_tp", "fsdp", "sp", "tp", "zero"]

    r = _run_cli("--show", "mlp", "dp")
    assert r.returncode == 0, r.stderr
    assert "replicate" in r.stdout

    r = _run_cli("--lint", "mlp")
    assert r.returncode == 0, r.stderr + r.stdout
