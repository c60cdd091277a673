"""What a rematerialised layer keeps: the flash kernel's output and row
statistics, named inside the ``custom_vjp``'s forward rule
(``flash_attention.FLASH_RESIDUALS``), and the one policy of
``TransformerLM(remat=True)`` that saves them beside the expert layers'
names — one ``flash-fwd`` call a layer-step, the gradients the
unrematerialised ones to the bit."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chainermn_tpu.models.block_table import table_from_config  # noqa: E402
from chainermn_tpu.models.transformer import (  # noqa: E402
    TransformerLM,
    remat_kept,
    remat_names,
    remat_policy,
)
from chainermn_tpu.ops import make_flash_attention_fn  # noqa: E402
from chainermn_tpu.ops.grouped_matmul import SAVED_PRODUCTS  # noqa: E402
from chainermn_tpu.parallel.moe_dropless import ROUTER_CHOICE  # noqa: E402
from chipbench import weights, weights_hybrid, weights_zaya  # noqa: E402

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
gd = importlib.import_module("chainermn_tpu.ops.gated_delta")


def kernel_calls(jaxpr, name):
    """``pallas_call`` equations called ``name`` in ``jaxpr``, the
    jaxprs its equations carry looked into."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found += eqn.params["name"] == name
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += kernel_calls(sub, name)
    return found


def test_the_policy_is_the_five_names():
    from chainermn_tpu.ops.kda import KDA_RESIDUALS

    assert remat_names() == (ROUTER_CHOICE, SAVED_PRODUCTS,
                             fa.FLASH_RESIDUALS, gd.GDN_RESIDUALS,
                             KDA_RESIDUALS)


# ---------------------------------------------------------- the kernel's rule

@pytest.mark.parametrize("kv_rows", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("segmented", [False, True],
                         ids=["_flash_bh", "_flash_bh_seg"])
def test_a_checkpoint_that_saves_the_name_runs_the_forward_once(
        segmented, kv_rows):
    """Under ``jax.checkpoint`` with the model's policy the gradient's
    jaxpr holds ONE ``flash-fwd`` call; with no policy two (the backward
    pass runs the kernel again for ``o`` and ``lse``: what every remat
    model did before the name).  ``dq, dk, dv`` are the unrematerialised
    ones to the bit either way."""
    S, D = 128, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (4, S, D))
    k = jax.random.normal(keys[1], (kv_rows, S, D))
    v = jax.random.normal(keys[2], (kv_rows, S, D))
    w = jax.random.normal(keys[3], (4, S, D))
    statics = (D ** -0.5, True, 64, 64, True)
    if segmented:
        ids = (jnp.arange(S) >= 48).astype(jnp.int32)[None, :, None]
        q_seg = jnp.broadcast_to(ids, (4, S, 1))
        kv_seg = jnp.broadcast_to(ids, (kv_rows, S, 1))

        def attend(q, k, v):
            return fa._flash_bh_seg(q, k, v, q_seg, kv_seg, *statics)
    else:
        def attend(q, k, v):
            return fa._flash_bh(q, k, v, *statics)

    def loss(q, k, v):
        # the projections' stand-in: something to recompute before the call
        return jnp.sum(attend(jnp.tanh(q), jnp.tanh(k), v) * w)

    def grad_of(fn):
        return jax.grad(fn, argnums=(0, 1, 2))

    plain = jax.jit(grad_of(loss))(q, k, v)
    for policy, fwd_calls in ((remat_policy(), 1), (None, 2)):
        grad = grad_of(jax.checkpoint(loss, policy=policy))
        jaxpr = jax.make_jaxpr(grad)(q, k, v).jaxpr
        assert kernel_calls(jaxpr, "flash-fwd") == fwd_calls
        # (the backward is one pass, under flash-bwd-dkv's name, where
        # the KV row fits its footprint: here and in every cell)
        assert kernel_calls(jaxpr, "flash-bwd-dq") == 0
        assert kernel_calls(jaxpr, "flash-bwd-dkv") == 1
        for got, want in zip(jax.jit(grad)(q, k, v), plain):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_checkpoint_that_saves_the_name_runs_the_delta_rule_once():
    """As for flash: under ``jax.checkpoint`` with the model's policy the
    gradient's jaxpr holds ONE ``gdn-fwd`` call (the one that keeps the
    tiles' states), with no policy two; the gradients are the
    unrematerialised ones to the bit either way."""
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    q = jax.random.normal(keys[0], (1, 32, 1, 8)) / 4
    k = jax.random.normal(keys[1], (1, 32, 1, 8)) / 3
    v = jax.random.normal(keys[2], (1, 32, 2, 8))
    g = -jnp.exp(jax.random.normal(keys[3], (1, 32, 2)) - 2)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 32, 2)))
    w = jax.random.normal(keys[5], v.shape)

    def loss(q, k, v, g, beta):
        return jnp.sum(gd.gated_delta_rule(
            jnp.tanh(q), jnp.tanh(k), v, g, beta, chunk=8) * w)

    def grad_of(fn):
        return jax.grad(fn, argnums=range(5))

    args = (q, k, v, g, beta)
    plain = jax.jit(grad_of(loss))(*args)
    for policy, fwd_calls in ((remat_policy(), 1), (None, 2)):
        grad = grad_of(jax.checkpoint(loss, policy=policy))
        jaxpr = jax.make_jaxpr(grad)(*args).jaxpr
        assert kernel_calls(jaxpr, "gdn-fwd") == fwd_calls
        assert kernel_calls(jaxpr, "gdn-bwd") == 1
        for got, want in zip(jax.jit(grad)(*args), plain):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_residual_is_kept_with_the_tokens_on_the_lanes():
    """``lse`` leaves the kernel a (BH, S, 1) column, one number a
    128-lane row on the chip; what the forward rule hands the backward
    rule is (BH, S)."""
    q = jnp.ones((2, 64, 16))
    _, res = fa._flash_vjp_fwd(q, q, q, 0.25, True, 64, 64, True)
    assert res[3].shape == (2, 64, 16) and res[4].shape == (2, 64)
    seg = jnp.zeros((2, 64, 1), jnp.int32)
    _, res = fa._flash_seg_vjp_fwd(q, q, q, seg, seg, 0.25, True, 64, 64,
                                   True)
    assert res[4].shape == (2, 64) and res[4].dtype == jnp.float32


# ------------------------------------------------------------ the model's

def _granite_like():
    """mamba2 + attention rows, dense FFNs, no experts: the table whose
    policy was ``None``."""
    c = {
        "model_type": "granitemoehybrid", "attention_bias": False,
        "attention_multiplier": 0.125, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
        "layer_types": ["mamba", "attention", "mamba", "attention"],
        "logits_scaling": 8, "mamba_chunk_size": 8, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 8, "mamba_d_state": 16,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
        "mamba_proj_bias": False, "normalization_function": "rmsnorm",
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 4, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
        "vocab_size": 96, "n_layer": 4,
    }
    return (c, table_from_config(c), make_flash_attention_fn(
        causal=True, scale=c["attention_multiplier"]))


def _zaya_like():
    """Three cca + top-1 expert layers, experts 4-7 of 8 held."""
    c = {
        "model_type": "zaya", "attention_bias": False, "cca_time0": 2,
        "cca_time1": 2, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 32, "layer_types": ["hybrid"] * 6,
        "lm_head_bias": False, "max_position_embeddings": 1024,
        "moe_intermediate_size": 24, "num_attention_heads": 4,
        "num_experts": 4, "num_experts_published": 8,
        "experts_held_first": 4, "num_experts_per_tok": 1,
        "num_hidden_layers": 6, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 16, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 96, "n_layer": 3,
    }
    table = table_from_config(dict(c, num_experts=8), n_layers=3,
                              experts_held=(4, 4))
    return c, table, make_flash_attention_fn(causal=True)


#: name -> (the table's builder, its seeded weights, its flash layers)
TABLES = {"granite_like": (_granite_like, weights_hybrid, 2),
          "zaya_like": (_zaya_like, weights_zaya, 3)}
BATCH, SEQ = 2, 64


@pytest.fixture(scope="module", params=sorted(TABLES))
def sides(request):
    """Loss and gradients of one tiny table with and without ``remat``,
    float32, flash attention in interpret mode, and the jaxpr of the
    rematerialised gradient.  Both run op by op: jitted, XLA:CPU fuses
    the two programs differently around the layers and a few embedding
    gradients differ in their last bit, whatever is kept."""
    build, seeded, flash_layers = TABLES[request.param]
    c, table, attention_fn = build()
    params = seeded.make(c, 2**31 + 5)
    toks = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0,
                              c["vocab_size"])
    x, y = toks[:, :-1], toks[:, 1:]

    def value_and_grad(remat):
        lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                           table=table, dtype=jnp.float32, remat=remat,
                           attention_fn=attention_fn)

        def loss(p):
            z = lm.apply({"params": p}, x)
            picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
            return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)

        return jax.value_and_grad(loss)

    kept = value_and_grad(True)
    return {"flash_layers": flash_layers,
            "jaxpr": jax.make_jaxpr(kept)(params).jaxpr,
            "kept": kept(params),
            "plain": value_and_grad(False)(params)}


def test_a_remat_model_calls_the_forward_kernel_once_a_layer(sides):
    n = sides["flash_layers"]
    assert kernel_calls(sides["jaxpr"], "flash-fwd") == n
    assert kernel_calls(sides["jaxpr"], "flash-bwd-dq") == 0
    assert kernel_calls(sides["jaxpr"], "flash-bwd-dkv") == n


def test_a_remat_model_is_the_plain_one_to_the_bit(sides):
    (loss, grads), (want_loss, want) = sides["kept"], sides["plain"]
    assert float(loss) == float(want_loss)
    got, want = weights.flatten(grads), weights.flatten(want)
    assert sorted(got) == sorted(want)
    for path, leaf in want.items():
        np.testing.assert_array_equal(
            np.asarray(got[path]), np.asarray(leaf),
            err_msg=weights.leaf_name(path))


# ------------------------------------------------------------- the record

def test_kept_bytes_come_from_the_shapes():
    """Granite-like: two flash layers of 4 heads of 8 over 128 tokens in
    float32, ``o`` and 4 bytes a token and head; no expert name kept.
    Zaya-like: three flash layers in the latent (4 heads of 16), three
    expert layers whose buffer is every pair's tile and one a held expert
    (256 x 5 rows), gate, up and down products kept."""
    _, table, _ = _granite_like()
    kept = remat_kept(table, 32, BATCH * SEQ, 4)
    assert kept == {
        "layers": 4, "flash_layers": 2, "expert_layers": 0,
        f"{ROUTER_CHOICE}_bytes": 0, f"{SAVED_PRODUCTS}_bytes": 0,
        f"{fa.FLASH_RESIDUALS}_bytes": 2 * 128 * 4 * (8 * 4 + 4),
        f"{gd.GDN_RESIDUALS}_bytes": 0, "kda-residuals_bytes": 0}
    assert remat_kept(table, 32, BATCH * SEQ, 4, flash=False)[
        f"{fa.FLASH_RESIDUALS}_bytes"] == 0
    _, table, _ = _zaya_like()
    kept = remat_kept(table, 32, BATCH * SEQ, 2)
    assert kept == {
        "layers": 3, "flash_layers": 3, "expert_layers": 3,
        f"{ROUTER_CHOICE}_bytes": 3 * 128 * 4,
        f"{SAVED_PRODUCTS}_bytes": 3 * (256 * 5) * 2 * (24 + 24 + 32),
        f"{fa.FLASH_RESIDUALS}_bytes": 3 * 128 * 4 * (16 * 2 + 4),
        f"{gd.GDN_RESIDUALS}_bytes": 0, "kda-residuals_bytes": 0}
    # a Gated DeltaNet row: ``o`` and a float32 state a value head and
    # tile (two rows of 64 tokens, chunk 16: one tile of four chunks a row)
    from chainermn_tpu.models.block_table import BlockTable, GDNSpec, LayerSpec

    table = BlockTable((LayerSpec(mixer="gdn", gdn=GDNSpec(2, 4, 8, 16,
                                                           chunk=16)),))
    assert remat_kept(table, 32, BATCH * SEQ, 2, seq=SEQ)[
        f"{gd.GDN_RESIDUALS}_bytes"] == 4 * 16 * (128 * 2 + 2 * 8 * 4)


@pytest.mark.parametrize("name", sorted(TABLES))
def test_the_model_says_what_it_keeps_when_someone_listens(tmp_path, name):
    from chainermn_tpu.observability import reporter, step_log

    c, table, attention_fn = TABLES[name][0]()
    x = jnp.zeros((BATCH, SEQ), jnp.int32)
    rep, path = reporter.Reporter(), str(tmp_path / "steps.jsonl")

    def trace(remat):
        lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                           table=table, remat=remat,
                           attention_fn=attention_fn)
        jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), x))

    with reporter.scope(rep), step_log.recording(path):
        trace(False)
        assert "remat/calls" not in rep.summary()["counters"]
        trace(True)
    want = remat_kept(table, c["hidden_size"], BATCH * SEQ, 2)
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    assert rep.summary()["counters"]["remat/calls"] == 1
    for field, value in want.items():
        assert gauges[f"remat/{field}"] == value
    row, = [r for r in map(json.loads, open(path))
            if r["event"] == "remat_geometry"]
    assert {k: row[k] for k in want} == want
    assert {k[:-len("_bytes")] for k in row if k.endswith("_bytes")} == set(
        remat_names())
