"""The ``laguna`` rows of the block table — sliding-window rows and
full-attention rows of DIFFERENT query-head counts in one table, a
sigmoid gate a head on every attention output, YaRN on half a head, a
leading dense FFN, a sigmoid top-k router over one expert-parallel rank's
share of the experts beside a shared expert — against the plain reference
the benchmark compares with on the chip (``chipbench/refs/laguna.py``:
attention as an explicit masked softmax, the rotation written out, dense
masked sums over the held experts, none of the program's code)."""

import dataclasses
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

from chainermn_tpu.models.block_table import (  # noqa: E402
    LayerSpec,
    MLASpec,
    YarnSpec,
    rotary_frequencies,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    MultiHeadAttention,
    TransformerLM,
    causal_mask,
    rotate_partial,
)
from chainermn_tpu.observability import device_trace  # noqa: E402
from chainermn_tpu.ops import make_flash_attention_fn  # noqa: E402
from chipbench import flops_laguna, weights, weights_laguna  # noqa: E402
from chipbench.refs import laguna as reference  # noqa: E402

sys.path.insert(0, os.path.join(ROOT, "tests"))
import _accepted_tables  # noqa: E402

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

D_MODEL, VOCAB = 32, 96
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG_FILE = os.path.join(ROOT, "chipbench/configs/laguna-s-2.1-train.json")
YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 128,
        "original_max_position_embeddings": 16, "beta_fast": 2,
        "beta_slow": 0.5, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
ATT, EXPERTS = reference.ATT, reference.EXPERTS


def config(held=(0, 8), n_layer=5, **over):
    """A ``laguna`` config at toy widths, keys as published (query heads
    a layer of 4 and 6 over 2: groups of 2 and 3), plus the benchmark's
    own: the layers kept and the experts held."""
    c = {
        "model_type": "laguna", "attention_bias": False, "head_dim": 16,
        "hidden_size": D_MODEL, "intermediate_size": 64,
        "layer_types": (["full_attention"]
                        + ["sliding_attention"] * 3) * 2,
        "num_attention_heads": 4,
        "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "mlp_only_layers": [0], "decoder_sparse_step": 1,
        "gating": "per-head", "gating_types": ["per_head"] * 8,
        "max_position_embeddings": 1024, "moe_intermediate_size": 24,
        "shared_expert_intermediate_size": 24, "norm_topk_prob": True,
        "moe_routed_scaling_factor": 2.5,
        "moe_apply_router_weight_on_input": False,
        "moe_router_logit_softcapping": 0,
        "num_experts": held[1], "num_experts_published": 8,
        "experts_held_first": held[0], "num_experts_per_tok": 3,
        "num_hidden_layers": 8, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": dict(YARN),
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 1000,
                                  "partial_rotary_factor": 1}},
        "sliding_window": 12, "tie_word_embeddings": False,
        "vocab_size": VOCAB, "n_layer": n_layer,
    }
    c.update(over)
    return c


def table_of(c):
    published = dict(c, num_experts=c["num_experts_published"])
    return table_from_config(
        published, n_layers=c["n_layer"],
        experts_held=(c["experts_held_first"], c["num_experts"]))


def tokens(seed, batch, length):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0,
                              VOCAB)


def model(c, **kw):
    return TransformerLM(vocab=VOCAB, d_model=D_MODEL, table=table_of(c),
                         **kw)


def ref_logits(params, x, c):
    return reference.logits(params, reference.layers(
        params, reference.embed(params, x), c), c)


# ------------------------------------------------- the table from the keys

def test_the_published_keys_give_rows_of_two_shapes():
    c = config()
    table = table_from_config(dict(c, num_experts=8))
    assert len(table.layers) == 8 and table.positions == "rotary"
    assert table.final_norm == "rmsnorm" and not table.tied_head
    assert [r.window for r in table.layers] == [None, 12, 12, 12] * 2
    assert [r.n_heads for r in table.layers] == [4, 6, 6, 6] * 2
    assert [r.rotary_dim for r in table.layers] == [8, 16, 16, 16] * 2
    assert [r.ffn for r in table.layers] == ["swiglu"] + ["experts"] * 7
    full, sliding = table.layers[4], table.layers[1]
    assert sliding.yarn is None and sliding.rope_theta == 1000.0
    assert full.yarn == YarnSpec(
        factor=128.0, original_max_position=16, beta_fast=2.0,
        beta_slow=0.5, attention_factor=1.4852030263919618)
    assert dataclasses.replace(
        full, yarn=None, window=12, n_heads=6, rotary_dim=16,
        rope_theta=1000.0) == sliding
    assert all(r.head_gate and not r.out_gate and not r.qk_norm
               and r.n_kv_heads == 2 and r.d_head == 16
               for r in table.layers)
    dense = table.layers[0]
    assert dense.d_ff == 64 and dense.experts is None
    z = sliding.experts
    assert (z.n_experts, z.top_k, z.d_expert, z.d_shared, z.router,
            z.scaling, z.shared_gate, z.n_group) == (
                8, 3, 24, 24, "sigmoid", 2.5, False, 0)
    # the lists a layer may be absent: the heads are then
    # num_attention_heads, the FFNs mlp_only_layers'
    short = {k: v for k, v in c.items() if k not in (
        "num_attention_heads_per_layer", "mlp_layer_types",
        "gating_types")}
    plain = table_from_config(dict(short, num_experts=8))
    assert [r.n_heads for r in plain.layers] == [4] * 8
    assert [r.ffn for r in plain.layers] == [r.ffn for r in table.layers]


def test_the_catalog_rows_keys_build_the_whole_table_and_the_cells_count():
    """The published widths: all 48 layers from the catalog row's own
    keys, and through the configuration file the cell's five with the
    parameter count of the file's own reckoning."""
    with open(CONFIG_FILE) as f:
        c = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1"][0]
        assert c["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if c.get(k) != v} == {
            "num_experts", "vocab_size"}
        whole = table_from_config(row["config"])
        assert len(whole.layers) == 48
        full = [r for r in whole.layers if r.window is None]
        sliding = [r for r in whole.layers if r.window == 512]
        assert (len(full), len(sliding)) == (12, 36)
        assert {r.n_heads for r in full} == {48}
        assert {r.n_heads for r in sliding} == {72}
        assert {(r.rotary_dim, r.rope_theta) for r in full} == {
            (64, 500000.0)}
        assert {(r.rotary_dim, r.rope_theta, r.yarn) for r in sliding} == {
            (128, 10000.0, None)}
        assert [r.ffn for r in whole.layers] == ["swiglu"] + [
            "experts"] * 47
        assert all(r.experts.d_shared == 1024 and r.experts.held is None
                   for r in whole.layers[1:])
    table = table_from_config(
        dict(c, num_experts=c["num_experts_published"]), n_layers=5,
        experts_held=(0, 8))
    assert [r.window for r in table.layers] == [None, 512, 512, 512, None]
    assert [r.n_heads for r in table.layers] == [48, 72, 72, 72, 48]
    assert table.layers[0].yarn == YarnSpec(
        factor=128.0, original_max_position=8192, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.4852030263919618)
    assert table.layers[0].d_ff == 12288
    for row in table.layers[1:]:
        z = row.experts
        assert (row.n_kv_heads, row.d_head, row.head_gate) == (8, 128, True)
        assert (z.n_experts, z.top_k, z.d_expert, z.d_shared, z.router,
                z.scaling, z.experts_held) == (
                    256, 10, 1024, 1024, "sigmoid", 2.5, (0, 8))
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table)
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    r = c["reckoning"]
    assert count == r["total"] == r["issue_total"] + 4 * 256
    assert count == weights_laguna.n_params(c)
    assert r["issue_total"] == 811_017_216
    rest = (9 * r["routed_expert"] + r["router"]
            + r["router_correction_bias"] + r["layer_norms"])
    assert r["layer_0"] == (r["attention_full_row"] + r["dense_ffn"]
                            + r["layer_norms"])
    assert r["sliding_sparse_layer"] == r["attention_sliding_row"] + rest
    assert r["layer_4"] == r["attention_full_row"] + rest
    assert r["total"] == (
        r["layer_0"] + 3 * r["sliding_sparse_layer"] + r["layer_4"]
        + r["table_and_head"] + r["final_norm"])
    assert r["state_bytes"] == 16 * r["total"]
    step = r["compiled_step"]
    assert (step["argument_bytes"] + step["temporary_bytes"]
            + step["code_bytes"]) <= 15.0e9      # the rule behind seq_len
    assert flops_laguna.train_flops_per_step(
        c, {"global_batch": 1, "seq_len": 8192}) == pytest.approx(
            30.0e12, rel=0.01)


def _ropes(**full):
    return {"full_attention": dict(YARN, **full),
            "sliding_attention": {"rope_theta": 1e4}}


@pytest.mark.parametrize("key,value,needle", [
    ("gating", "per-channel", "gating"),
    ("gating", None, "gating"),
    ("gating_types", ["per_head"] * 7 + ["per_channel"], "gating_types"),
    ("gating_types", ["per_head"] * 7, "num_hidden_layers"),
    ("layer_types", ["full_attention"] * 7, "num_hidden_layers"),
    ("num_attention_heads_per_layer", [4] * 9, "num_hidden_layers"),
    ("mlp_layer_types", ["sparse"] * 7, "num_hidden_layers"),
    ("layer_types", ["chunked_attention"] * 8, "layer_types"),
    ("mlp_layer_types", ["dense"] + ["mixture"] * 7, "mlp_layer_types"),
    ("mlp_only_layers", [0, 1], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("sliding_window", None, "sliding_window"),
    ("rope_parameters", _ropes(rope_type="llama3"), "rope_type"),
    ("rope_parameters", {"full_attention": dict(YARN)}, "rope_parameters"),
    ("rope_parameters", _ropes(truncate=False), "truncated"),
    ("rope_parameters", _ropes(partial_rotary_factor=0.05),
     "partial_rotary_factor"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("moe_apply_router_weight_on_input", True,
     "moe_apply_router_weight_on_input"),
    ("moe_router_logit_softcapping", 30.0, "softcapping"),
    ("attention_bias", True, "attention_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("tie_word_embeddings", True, "tied"),
])
def test_table_from_config_refuses_by_key(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_from_config(dict(config(), **{key: value}))


def test_every_accepted_configurations_table_is_unchanged():
    """``==`` on the dataclasses, through their ``repr``
    (``tests/_accepted_tables.py``): the digests are the parent
    commit's."""
    with open(os.path.join(ROOT, "tests/golden/accepted_tables.json")) as f:
        assert _accepted_tables.digests(ROOT) == json.load(f)


def test_a_row_states_its_gate():
    plain = LayerSpec(head_gate=True)
    assert plain.head_gate and plain.mla is None
    assert LayerSpec(head_gate=True, mla=MLASpec(16, 8, 8, 8)).head_gate
    with pytest.raises(ValueError, match="not both"):
        LayerSpec(head_gate=True, out_gate=True)
    with pytest.raises(ValueError, match="attention row's"):
        LayerSpec(mixer="none", ffn="gelu", head_gate=True)


def test_the_caches_take_no_gate_a_head():
    layer = MultiHeadAttention(D_MODEL, 4, jnp.float32, decode=True,
                               cache_len=8, head_gate=True)
    with pytest.raises(ValueError, match="gate"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, D_MODEL)),
                   jnp.zeros((1, 1, D_MODEL)))


# ------------------------------------------------------- the gate, by hand

@pytest.mark.parametrize("flash", [False, True])
def test_the_gate_a_head_is_the_hand_written_one(flash):
    """``out = W_o (attn_h * sigmoid(x W_g)_h)``: the gated row against
    the same row without its gate, the gate applied by hand between the
    attention and the output projection."""
    heads, kv, d_head, S = 6, 2, 8, 24
    fn = make_flash_attention_fn(causal=True, block_q=8, block_k=8) if (
        flash) else None
    kw = dict(n_kv_heads=kv, d_head=d_head)
    gated = MultiHeadAttention(D_MODEL, heads, jnp.float32, fn,
                               head_gate=True, **kw)
    plain = MultiHeadAttention(D_MODEL, heads, jnp.float32, fn, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, S, D_MODEL))
    mask = causal_mask(S)
    params = gated.init(jax.random.PRNGKey(1), x, x, mask)["params"]
    assert params["gate"]["kernel"].shape == (D_MODEL, heads)
    assert "bias" not in params["gate"]
    with jax.default_matmul_precision("highest"):
        got = gated.apply({"params": params}, x, x, mask)
        # the row without its gate, its output projection the identity
        eye = jnp.eye(heads * d_head).reshape(heads, d_head, -1)
        bare = {k: v for k, v in params.items() if k != "gate"}
        heads_out = MultiHeadAttention(
            heads * d_head, heads, jnp.float32, fn, **kw).apply(
            {"params": dict(bare, out={"kernel": eye})}, x, x, mask)
        g = jax.nn.sigmoid(x @ params["gate"]["kernel"])      # (B, S, H)
        want = jnp.einsum(
            "bshd,hdm->bsm",
            heads_out.reshape(2, S, heads, d_head) * g[..., None],
            params["out"]["kernel"])
        ungated = plain.apply({"params": bare}, x, x, mask)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(got - ungated))) > 1e-2


# ---------------------------------------------------- YaRN on half a head

def test_yarn_frequencies_over_half_the_published_head_by_hand():
    """The full row of the catalog's keys: the blend is over the 64
    ROTATED dimensions (``head_dim x partial_rotary_factor``), not the
    head's 128."""
    rope = {"rope_theta": 500000, "factor": 128, "beta_fast": 32,
            "beta_slow": 1, "original_max_position_embeddings": 8192}
    yarn = YarnSpec(128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    got, scale = rotary_frequencies(64, 500000.0, yarn)
    assert scale == 1.4852030263919618
    np.testing.assert_allclose(got, reference.yarn_frequencies(rope, 64),
                               rtol=1e-12)
    # by hand: c(t) = 64 ln(8192 / (2 pi t)) / (2 ln 500000)
    c = lambda t: 64 * np.log(8192 / (2 * np.pi * t)) / (  # noqa: E731
        2 * np.log(500000))
    low, high = np.floor(c(32)), np.ceil(c(1))
    assert (low, high) == (9.0, 18.0)
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(got[18:], plain[18:] / 128, rtol=1e-12)
    assert np.all((got[10:18] < plain[10:18])
                  & (got[10:18] > plain[10:18] / 128))
    # and not the whole head's blend
    over_head = rotary_frequencies(128, 500000.0, yarn)[0]
    assert not np.allclose(over_head[:32], got)


def test_rotation_of_half_a_head_under_yarn_is_the_references():
    rope = dict(YARN)
    yarn = YarnSpec(128.0, 16, 2.0, 0.5, 1.4852030263919618)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 3, 16))
    got = rotate_partial(x, jnp.arange(40), 8, 10000.0, yarn)
    want = reference.rotate(x[0], rope)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
    # the second half of the head passes through, unscaled
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # the broken statements are others
    for broken in ("whole_head_rotation", "no_attention_factor"):
        assert float(jnp.max(jnp.abs(
            reference.rotate(x[0], rope, broken) - want))) > 1e-2


# ------------------------------------------- the program and the reference

def both_sides(held, flash):
    """Logits, loss and gradients of the program (float32, ``highest``;
    ``flash``: through the flash adapter in interpret mode at blocks of
    8, so that the band crosses tiles, else the dense masked path) and of
    the reference on one seeded tree."""
    c = config(held=held)
    params = weights_laguna.make(c, 2**31 + 11)
    toks = tokens(1, 2, 41)
    x, y = toks[:, :-1], toks[:, 1:]
    lm = model(c, dtype=jnp.float32, remat=True,
               attention_fn=make_flash_attention_fn(
                   causal=True, block_q=8, block_k=8) if flash else None)

    def program_loss(p):
        z = lm.apply({"params": p}, x)
        picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - picked)

    with jax.default_matmul_precision("highest"):
        got = (lm.apply({"params": params}, x),
               *jax.value_and_grad(program_loss)(params))
        want = (ref_logits(params, x, c),
                *jax.value_and_grad(reference.loss_sum)(params, x, y, c))
    return got, want


@pytest.fixture(scope="module")
def all_held_flash():
    return both_sides((0, 8), True)


@pytest.fixture(scope="module")
def some_held_dense():
    return both_sides((2, 4), False)


@pytest.fixture(params=["all_held_flash", "some_held_dense"])
def sides(request):
    return request.getfixturevalue(request.param)


def test_program_logits_and_loss_match_the_reference(sides):
    # Both sides are float32 at ``highest``: what is left is the order of
    # sums (the kernels' online softmax against a whole one, sorted row
    # groups against a dense masked sum).
    (logits, loss, _), (ref, ref_loss, _) = sides
    np.testing.assert_allclose(logits, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def _leaves():
    return [weights.leaf_name(p) for p in sorted(
        weights_laguna.shapes(config()))
        if p[-1] != "router_bias"]      # the choice's alone: no gradient


@pytest.mark.parametrize("leaf", _leaves())
def test_program_gradient_matches_the_reference(sides, leaf):
    # rtol 1e-3 with an absolute floor of 2e-5 of the leaf's largest
    # entry, as the other families' tests.
    (_, _, grads), (_, _, ref_grads) = sides
    got = weights.flatten(grads)[tuple(leaf.split("/"))]
    want = weights.flatten(ref_grads)[tuple(leaf.split("/"))]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-5 * scale)


def test_the_correction_bias_gets_no_gradient(sides):
    (_, _, grads), (_, _, ref_grads) = sides
    for tree in (grads, ref_grads):
        assert all(not np.any(np.asarray(v))
                   for p, v in weights.flatten(tree).items()
                   if p[-1] == "router_bias")


def test_the_seeded_tree_is_the_programs_tree():
    """Names and shapes of ``weights_laguna`` against the program's own
    ``init`` (the reference reads the tree by these names): a gate a
    head in every row, a dense FFN first, a shared expert beside the
    held ones, no QK-norm."""
    c = config(held=(2, 4))
    shapes = jax.eval_shape(
        lambda: model(c).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        weights_laguna.shapes(c))
    made = weights_laguna.shapes(c)
    assert made[("layer_0", ATT, "gate", "kernel")] == (D_MODEL, 4)
    assert made[("layer_1", ATT, "gate", "kernel")] == (D_MODEL, 6)
    assert made[("layer_1", ATT, "query", "kernel")] == (D_MODEL, 6, 16)
    assert ("layer_0", "GatedFeedForward_0", "wi", "kernel") in made
    assert ("layer_1", EXPERTS, "shared", "wi", "kernel") in made
    assert not any(part in ("q_norm", "k_norm", "shared_gate")
                   for p in made for part in p)
    tree = weights_laguna.make(c, 3)
    assert not np.any(np.asarray(tree["layer_1"][EXPERTS]["router_bias"]))


def test_the_programs_choices_are_the_references():
    c = config()
    params = weights_laguna.make(c, 2**31 + 11)
    x = tokens(1, 2, 41)[:, :-1]
    lm = model(c, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, seen = lm.apply({"params": params}, x, mutable=["intermediates"])
        want = reference.chosen_experts(params, x, c)
    assert sorted(want) == [f"layer_{i}" for i in range(1, 5)]
    for name, mask in want.items():
        chosen = seen["intermediates"][name]["ExpertLayer_0"]["chosen"][0]
        assert chosen.shape == (2 * 40, 3)
        got = np.zeros(mask.shape, bool).reshape(-1, 8)
        np.put_along_axis(got, np.asarray(chosen), True, axis=-1)
        np.testing.assert_array_equal(got.reshape(mask.shape), mask)


def test_what_the_attention_rows_add_is_the_references():
    """What the cell's comparison holds the timed step to: the output of
    ``layer_<i>/MultiHeadAttention_0``, handed back through flax's
    ``capture_intermediates``, against ``reference.attention_rows`` — and
    every broken statement of a row moves it."""
    c = config()
    params = weights_laguna.make(c, 2**31 + 13)
    x = tokens(4, 2, 41)[:, :-1]
    keep = ("layer_1", "layer_4")
    paths = {(name, ATT) for name in keep}
    lm = model(c, dtype=jnp.float32, remat=True,
               attention_fn=make_flash_attention_fn(
                   causal=True, block_q=8, block_k=8))
    with jax.default_matmul_precision("highest"):
        _, seen = lm.apply(
            {"params": params}, x, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.path in paths)
        want = reference.attention_rows(params, x, c, keep)
        broken = {b: reference.attention_rows(params, x, c, keep, broken=b)
                  for b in reference.BROKEN}
    worst = lambda a, b: float(np.max(  # noqa: E731
        np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)))
    for name in keep:
        got = seen["intermediates"][name][ATT]["__call__"][0]
        assert got.shape == (2, 40, D_MODEL)
        assert worst(got, want[name]) < 1e-4
    moved = {b: {name: worst(rows[name], want[name]) for name in keep}
             for b, rows in broken.items()}
    assert moved["no_window"]["layer_1"] > 0.1      # the sliding row
    assert moved["no_gate"]["layer_1"] > 0.1
    assert moved["no_gate"]["layer_4"] > 0.1
    assert moved["whole_head_rotation"]["layer_4"] > 0.01   # the full row
    assert moved["no_attention_factor"]["layer_4"] > 0.01


def test_remat_on_and_off_give_the_same_gradients():
    c = config(held=(2, 4))
    params = weights_laguna.make(c, 5)
    x = tokens(2, 1, 32)
    fn = make_flash_attention_fn(causal=True, block_q=8, block_k=8)

    def grads(remat):
        lm = model(c, dtype=jnp.float32, remat=remat, attention_fn=fn)
        return jax.grad(lambda p: jnp.sum(
            lm.apply({"params": p}, x) ** 2))(params)

    with jax.default_matmul_precision("highest"):
        on, off = grads(True), grads(False)
    for a, b in zip(jax.tree.leaves(on), jax.tree.leaves(off)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------------- the shares

def test_thirty_two_shares_add_up_to_the_uncut_layer():
    """What ties one rank's share to the model: at 64 experts the
    thirty-two ranks' layers, two experts each, with the shared expert
    (which every rank computes alike) counted ONCE, add up to the
    reference's layer with all sixty-four."""
    over = dict(n_layer=2, num_experts_published=64)
    whole = config(held=(0, 64), **over)
    params = weights_laguna.make(whole, 2**31 + 5)["layer_1"]
    e = params[EXPERTS]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, D_MODEL))
    stacks = ("experts_gate", "experts_up", "experts_down")
    mask = causal_mask(16)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.halves(
            row, params, "sliding_attention", whole, "float32")[1]
            for row in x])
        base, total = None, 0.0
        for first in range(0, 64, 2):
            c = config(held=(first, 2), **over)
            row = table_of(c).layers[1]
            share = dict(e, **{k: e[k][first:first + 2] for k in stacks})
            out = Block(D_MODEL, row, jnp.float32).apply(
                {"params": dict(params, **{EXPERTS: share})}, x, mask)
            if base is None:    # x + attention + the shared expert
                hollow = dict(e, **{k: jnp.zeros_like(share[k])
                                    for k in stacks})
                base = Block(D_MODEL, row, jnp.float32).apply(
                    {"params": dict(params, **{EXPERTS: hollow})}, x, mask)
            total = total + out - base
    np.testing.assert_allclose(total + base, want, rtol=2e-4, atol=2e-5)
    # the routed experts' part is not nothing, and the shared expert is
    # in ``base`` once
    assert float(jnp.max(jnp.abs(total))) > 1e-4
    no_shared = jnp.stack([reference.experts(
        row, e, whole, "float32", shared=False) for row in x])
    assert float(jnp.max(jnp.abs(no_shared))) > 1e-4


@pytest.mark.parametrize("layer,kind", [(0, "full_attention"),
                                        (1, "sliding_attention"),
                                        (4, "full_attention")])
def test_a_share_is_the_references_share(layer, kind):
    c = config(held=(3, 4))
    params = weights_laguna.make(c, 2**31 + 6)[f"layer_{layer}"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, D_MODEL))
    with jax.default_matmul_precision("highest"):
        got = Block(D_MODEL, table_of(c).layers[layer], jnp.float32).apply(
            {"params": params}, x, causal_mask(24))
        want = reference.halves(x[0], params, kind, c, "float32")[1]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_a_stage_cut_is_the_references_first_layers():
    """``n_layers`` cuts a pipeline stage: the first five rows of the
    eight-layer table, fed the same tree, give what the reference's
    first five layers give."""
    c8 = config(n_layer=8)
    params = weights_laguna.make(c8, 2**31 + 7)
    x = tokens(2, 2, 24)
    first = {k: v for k, v in params.items()
             if not k.startswith("layer_") or int(k.split("_")[1]) < 5}
    with jax.default_matmul_precision("highest"):
        got = model(config(n_layer=5), dtype=jnp.float32).apply(
            {"params": first}, x)
        want = ref_logits(first, x, c8)
        whole = model(c8, dtype=jnp.float32).apply({"params": params}, x)
        ref_whole = ref_logits(params, x, c8)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(whole, ref_whole, rtol=2e-4, atol=2e-5)


def test_a_placement_renames_experts_and_changes_no_function():
    c = config(held=(0, 2))
    params = weights_laguna.make(c, 11)
    toks = np.asarray(tokens(3, 2, 32))
    order = weights_laguna.placement(params, toks, c)
    assert sorted(order) == [f"layer_{i}" for i in range(1, 5)]
    assert all(sorted(o.tolist()) == list(range(8)) for o in order.values())
    placed = weights_laguna.with_placement(params, order)
    e, p = params["layer_1"][EXPERTS], placed["layer_1"][EXPERTS]
    np.testing.assert_array_equal(
        p["router"], np.asarray(e["router"])[:, order["layer_1"]])
    assert p["experts_up"] is e["experts_up"]
    assert placed["layer_0"] is params["layer_0"]


# ------------------------------------------------- the census a row shape

def test_the_flash_census_is_published_a_row_shape():
    """Two attention shapes in one step: the adapter publishes each under
    its own name (heads, key/value heads, window), beside the
    ``flash/<kernel>/*`` gauges, which are the last traced call's."""
    from chainermn_tpu.observability import Reporter
    from chainermn_tpu.observability import reporter as reporter_mod

    c = config(held=(0, 2), sliding_window=8)
    lm = model(c, dtype=jnp.float32, attention_fn=make_flash_attention_fn(
        causal=True, block_q=8, block_k=8))
    x = tokens(5, 1, 32)
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), x))["params"]
    rep = Reporter()
    with reporter_mod.scope(rep):
        jax.eval_shape(lambda p: lm.apply({"params": p}, x), params)
    summary = rep.summary()
    gauges = {n: g["value"] for n, g in summary["gauges"].items()}
    counters = summary["counters"]
    wide, narrow = fa.shape_key(6, 2, 8), fa.shape_key(4, 2, None)
    assert (wide, narrow) == ("h6-kv2-w8", "h4-kv2-w0")
    assert counters["flash/calls"] == 5
    assert counters[f"flash/shape/{wide}/calls"] == 3
    assert counters[f"flash/shape/{narrow}/calls"] == 2
    assert gauges[f"flash/shape/{wide}/group"] == 3
    assert gauges[f"flash/shape/{narrow}/group"] == 2
    assert gauges[f"flash/shape/{wide}/window"] == 8
    band = fa.tile_census(32, 32, 8, 8, True, 8)["fwd"]
    triangle = fa.tile_census(32, 32, 8, 8, True, None)["fwd"]
    for field in ("block_q", "block_k", "live", "visited", "copied", "cut"):
        assert gauges[f"flash/shape/{wide}/flash-fwd/{field}"] == (
            band[field])
        assert gauges[f"flash/shape/{narrow}/flash-fwd/{field}"] == (
            triangle[field])
    # layer 4, the last traced, is a full row: the unkeyed gauges are its
    assert gauges["flash/flash-fwd/live"] == triangle["live"] == 10
    assert band["live"] == 7


# --------------------------------------------------------------- the scopes

@pytest.fixture(scope="module")
def compiled_text():
    """A tiny ``laguna`` model through ``make_train_step`` under
    ``remat`` with the flash adapter, compiled on the CPU."""
    import optax

    import chainermn_tpu
    from chainermn_tpu.communicators import build_mesh
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    c = config(held=(2, 4), sliding_window=8)
    lm = model(c, remat=True, attention_fn=make_flash_attention_fn(
        causal=True, block_q=8, block_k=8))
    comm = chainermn_tpu.create_communicator("xla_ici", mesh=build_mesh(
        inter_size=1, intra_size=1, devices=jax.devices()[:1]))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    toks = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), toks)["params"])

    def loss_fn(p, batch):
        h = lm.apply({"params": p}, batch[0], return_hidden=True)
        return fused_cross_entropy(h, p["lm_head"], batch[1], chunk=32)

    return opt.make_train_step(loss_fn).lower(
        params, jax.eval_shape(opt.init, params), (toks, toks)).compile(
        ).as_text()


def test_the_rows_scopes_tell_the_kinds_apart_and_hold_the_gate(
        compiled_text):
    table = device_trace.scope_table(compiled_text)
    by_layer, gated = {}, {}
    for path in table.values():
        layer, on = device_trace.layer_of(path), device_trace.scopes_on(path)
        for name in ("attn-window", "attn-mixer"):
            if name in on:
                by_layer.setdefault(name, set()).add(layer)
                if "mixer-gate" in on:
                    gated.setdefault(name, set()).add(layer)
    assert by_layer == {"attn-window": {"1", "2", "3"},
                        "attn-mixer": {"0", "4"}}
    assert gated == by_layer        # the gate a head, in every row
    (census,) = table.tiles_within["attn-window"]["flash-fwd"]
    assert (census["live"], census["visited"]) == (7, 8)
    assert table.tiles_within["attn-mixer"]["flash-fwd"][0]["live"] == 10
