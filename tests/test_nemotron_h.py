"""The ``nemotron_h`` rows of the block table — one branch a layer, the
dropless expert layer told which experts it holds, the grouped matmul, the
grouped gated norm, the untied head — against the plain reference the
benchmark compares with on the chip (``chipbench/refs/nemotron_h.py``:
dense masked sums, the recurrence step by step, none of the program's
code)."""

import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chainermn_tpu.models.block_table import (  # noqa: E402
    ExpertsSpec,
    LayerSpec,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    ExpertLayer,
    TransformerLM,
)
from chainermn_tpu.ops import grouped_matmul as gmm  # noqa: E402
from chainermn_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402
from chainermn_tpu.parallel import moe_dropless  # noqa: E402
from chipbench import weights, weights_nemotron  # noqa: E402
from chipbench.refs import nemotron_h as reference  # noqa: E402

PATTERN = "MEM*E-ME"


def config(pattern=PATTERN, held=(0, 8), **over):
    """A ``nemotron_h`` config at toy widths, keys as published, plus the
    benchmark's own: the layers kept and the experts held."""
    c = {
        "model_type": "nemotron_h", "attention_bias": False,
        "chunk_size": 8, "conv_kernel": 4, "expand": 2, "head_dim": 16,
        "hidden_size": 32, "hybrid_override_pattern": pattern,
        "intermediate_size": 24, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 8, "mamba_hidden_act": "silu",
        "mamba_num_heads": 8, "mamba_proj_bias": False, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 48, "n_group": 1,
        "n_groups": 2, "n_routed_experts": held[1],
        "n_routed_experts_published": 8, "experts_held_first": held[0],
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts_per_tok": 3,
        "num_hidden_layers": len(pattern), "num_key_value_heads": 2,
        "routed_scaling_factor": 2.5, "ssm_state_size": 16,
        "tie_word_embeddings": False, "time_step_floor": 1e-4,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "vocab_size": 96,
        "n_layer": len(pattern),
    }
    c.update(over)
    return c


def table_of(c, **kw):
    published = dict(c, n_routed_experts=c["n_routed_experts_published"])
    return table_from_config(
        published, n_layers=c["n_layer"],
        experts_held=(c["experts_held_first"], c["n_routed_experts"]), **kw)


def tokens(seed, batch, length, vocab):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0,
                              vocab)


# ------------------------------------------------- the table from the keys

def test_the_published_keys_give_one_branch_a_layer():
    table = table_of(config())
    assert [(r.mixer, r.ffn) for r in table.layers] == [
        ("mamba2", "none"), ("none", "experts"), ("mamba2", "none"),
        ("attention", "none"), ("none", "experts"), ("none", "relu2"),
        ("mamba2", "none"), ("none", "experts")]
    assert not table.tied_head and table.positions == "none"
    assert table.final_norm == "rmsnorm" and table.norm_eps == 1e-05
    m, e, a = table.layers[0], table.layers[1], table.layers[3]
    assert (m.ssm.d_inner, m.ssm.n_groups, m.ssm.norm_groups,
            m.ssm.chunk) == (64, 2, 2, 8)       # heads x head width
    assert (a.n_heads, a.n_kv_heads, a.d_head, a.attn_scale) == (
        4, 2, 16, None)                         # 16 is not 32 / 4
    assert e.experts == ExpertsSpec(
        n_experts=8, top_k=3, d_expert=24, d_shared=48, held=(0, 8),
        scaling=2.5)
    assert all(r.norm == "rmsnorm" and r.residual_multiplier == 1.0
               for r in table.layers)


def test_the_cut_and_the_share_are_the_callers():
    c = config()
    published = dict(c, n_routed_experts=8)
    table = table_from_config(published, n_layers=5, experts_held=(2, 4))
    assert len(table.layers) == 5
    assert table.layers[1].experts.experts_held == (2, 4)
    assert table_from_config(published).layers[1].experts.experts_held == (
        0, 8)


@pytest.mark.parametrize("key,value,needle", [
    ("attention_bias", True, "attention_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("use_bias", True, "use_bias"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("use_conv_bias", False, "use_conv_bias"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("mamba_hidden_act", "gelu", "mamba_hidden_act"),
    ("n_group", 4, "router groups"),
    ("topk_group", 2, "router groups"),
    ("n_shared_experts", 2, "n_shared_experts"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("norm_eps", 1e-6, "norm_eps"),
    ("hybrid_override_pattern", "MEM*E-MX", "does not build"),
    ("num_hidden_layers", 7, "num_hidden_layers"),
    ("model_type", "mamba2", "nemotron_h"),
])
def test_table_from_config_refuses_by_key(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_from_config(config(**{key: value}))


@pytest.mark.parametrize("kw,needle", [
    (dict(mixer="none", ffn="none"), "a mixer, an ffn or both"),
    (dict(ffn="experts"), "ExpertsSpec"),
    (dict(ffn="gelu", experts=ExpertsSpec(8, 2, 16, 32)), "ExpertsSpec"),
])
def test_a_row_states_what_it_has(kw, needle):
    with pytest.raises(ValueError, match=needle):
        LayerSpec(**kw)


@pytest.mark.parametrize("held", [(-1, 2), (7, 2), (0, 0)])
def test_experts_held_lie_among_the_published(held):
    with pytest.raises(ValueError, match="held"):
        ExpertsSpec(n_experts=8, top_k=2, d_expert=16, d_shared=32,
                    held=held)


def test_a_granite_table_holds_no_experts():
    from tests.test_hybrid import config as granite

    with pytest.raises(ValueError, match="no experts to hold"):
        table_from_config(granite(), experts_held=(0, 2))


# ------------------------------------------------------- program vs reference

def both_sides(held):
    """Loss, logits and gradients of the program (float32, ``highest``)
    and of the reference on one seeded tree."""
    c = config(held=held)
    params = weights_nemotron.make(c, 2**31 + 11)
    toks = tokens(1, 2, 33, c["vocab_size"])
    x, y = toks[:, :-1], toks[:, 1:]
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table_of(c), dtype=jnp.float32, remat=True)

    def program_loss(p):
        z = lm.apply({"params": p}, x)
        picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - picked)

    with jax.default_matmul_precision("highest"):
        got = (lm.apply({"params": params}, x),
               *jax.value_and_grad(program_loss)(params))
        want = (
            reference.logits(params, reference.layers(
                params, reference.embed(params, x), c), c),
            *jax.value_and_grad(reference.loss_sum)(params, x, y, c))
    return got, want


@pytest.fixture(scope="module")
def all_held():
    return both_sides((0, 8))


@pytest.fixture(scope="module")
def two_held():
    return both_sides((2, 2))


@pytest.fixture(params=["all_held", "two_held"])
def sides(request):
    return request.getfixturevalue(request.param)


def test_program_logits_and_loss_match_the_reference(sides):
    (logits, loss, _), (ref_logits, ref_loss, _) = sides
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def _leaves():
    return [weights.leaf_name(p) for p in sorted(
        weights_nemotron.shapes(config()))]


@pytest.mark.parametrize("leaf", _leaves())
def test_program_gradient_matches_the_reference(sides, leaf):
    (_, _, grads), (_, _, ref_grads) = sides
    got = weights.flatten(grads)[tuple(leaf.split("/"))]
    want = weights.flatten(ref_grads)[tuple(leaf.split("/"))]
    if leaf.endswith("router_bias"):      # chooses, and gets no gradient
        assert not np.any(got) and not np.any(want)
        return
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-5 * scale)


def test_the_seeded_tree_is_the_programs_tree():
    """Names and shapes of ``weights_nemotron`` against the program's own
    ``init`` (the reference reads the tree by these names)."""
    c = config(held=(2, 2))
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table_of(c))
    shapes = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        weights_nemotron.shapes(c))


def test_return_hidden_and_the_untied_head_give_the_logits(all_held):
    c = config()
    params = weights_nemotron.make(c, 2**31 + 11)
    x = tokens(1, 2, 33, c["vocab_size"])[:, :-1]
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table_of(c), dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = lm.apply({"params": params}, x, return_hidden=True)
        z = jnp.einsum("bsd,vd->bsv", h, params["lm_head"])
    np.testing.assert_allclose(z, all_held[0][0], rtol=1e-5, atol=1e-6)


def test_the_programs_choices_are_the_references(all_held):
    c = config()
    params = weights_nemotron.make(c, 2**31 + 11)
    x = tokens(1, 2, 33, c["vocab_size"])[:, :-1]
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table_of(c), dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, seen = lm.apply({"params": params}, x, mutable=["intermediates"])
        want = reference.chosen_experts(params, x, c)
    assert sorted(want) == ["layer_1", "layer_4", "layer_7"]
    for name, mask in want.items():
        chosen = seen["intermediates"][name]["ExpertLayer_0"]["chosen"][0]
        got = np.zeros(mask.shape, bool).reshape(-1, 8)
        np.put_along_axis(got, np.asarray(chosen), True, axis=-1)
        np.testing.assert_array_equal(got.reshape(mask.shape), mask)


# --------------------------------------------- the share and the whole layer

def test_all_shares_add_up_to_the_uncut_layer():
    """What ties one rank's share to the model: the four ranks' layers,
    two experts each, the shared expert counted once, add up to the
    reference's layer with all eight experts."""
    whole = config("E")
    params = weights_nemotron.make(whole, 2**31 + 5)["layer_0"]
    e = params["ExpertLayer_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.layer(row, params, whole, "float32")
                          for row in x])
        shared = jnp.stack([reference.relu2_mlp(
            reference.rms_norm(row, params["RMSNorm_0"]["scale"], 1e-05),
            e["shared"]["wi"]["kernel"], e["shared"]["wo"]["kernel"],
            "float32")
            for row in x])
        total = x - 3 * shared       # four ranks add x and shared four times
        for first in (0, 2, 4, 6):
            row = table_of(config("E", held=(first, 2))).layers[0]
            share = dict(e, experts_up=e["experts_up"][first:first + 2],
                         experts_down=e["experts_down"][first:first + 2])
            total = total + Block(32, row, jnp.float32).apply(
                {"params": dict(params, ExpertLayer_0=share)}, x) - x
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_a_share_is_the_references_share():
    c = config("E", held=(3, 4))
    params = weights_nemotron.make(c, 2**31 + 6)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32))
    with jax.default_matmul_precision("highest"):
        got = Block(32, table_of(c).layers[0], jnp.float32).apply(
            {"params": params}, x)
        want = reference.layer(x[0], params, c, "float32")
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------------ the router

def hand_top_k(scores, bias, k):
    """The k largest of score + bias, the lowest index on a tie."""
    rows = []
    for s in np.asarray(scores + bias, np.float64):
        rows.append(sorted(range(len(s)), key=lambda e: (-s[e], e))[:k])
    return np.array(rows)


def test_router_against_a_hand_written_top_k_with_ties():
    """Scores that tie exactly (equal logits), the tie broken one way by
    the index and the other way by the correction bias; the weights are
    the bare scores, normalised and scaled, and never see the bias."""
    logits = np.array([
        [2.0, 2.0, 2.0, 2.0, -1.0, -1.0, 0.5, 0.5],    # four equal tops
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],      # all equal
        [1.0, -1.0, 1.0, -1.0, 1.0, -1.0, 1.0, -1.0],
        [0.3, 0.1, 0.2, 0.3, 0.1, 0.2, 0.3, 0.25]], np.float32)
    bias = np.array([0, 0, 0, 1e-3, 0, 0, 2e-3, 0], np.float32)
    h = jnp.eye(4, dtype=jnp.float32)
    for b in (np.zeros(8, np.float32), bias):
        chosen, weight = moe_dropless.route(
            h, jnp.asarray(logits), jnp.asarray(b), top_k=3, scaling=2.5)
        scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
        want = hand_top_k(scores.astype(np.float32), b, 3)
        np.testing.assert_array_equal(np.asarray(chosen), want)
        picked = np.take_along_axis(scores, want, axis=-1)
        np.testing.assert_allclose(
            weight, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    no_bias = np.asarray(moe_dropless.route(
        h, jnp.asarray(logits), jnp.zeros(8), top_k=3)[0])
    with_bias = np.asarray(moe_dropless.route(
        h, jnp.asarray(logits), jnp.asarray(bias), top_k=3)[0])
    assert no_bias[0].tolist() == [0, 1, 2]      # the index breaks the tie
    assert with_bias[0].tolist() == [3, 0, 1]    # the bias breaks it first
    assert with_bias[1].tolist() == [6, 3, 0]


# ------------------------------------------------------- the grouped matmul

def grouped_case(seed, sizes, tile, K, N, spare_tiles=2):
    """Row groups of ``sizes`` laid out as the dispatch lays them, with
    ``spare_tiles`` dead tiles behind them."""
    rng = np.random.default_rng(seed)
    tiles = [max(1, -(-n // tile)) for n in sizes]
    n_tiles = sum(tiles) + spare_tiles
    x = np.zeros((n_tiles * tile, K), np.float32)
    live = np.zeros(n_tiles * tile, bool)
    tile_group, at = [], 0
    for g, (n, t) in enumerate(zip(sizes, tiles)):
        x[at:at + n] = rng.normal(size=(n, K))
        live[at:at + n] = True
        tile_group += [g] * t
        at += t * tile
    tile_group += [len(sizes) - 1] * spare_tiles
    w = rng.normal(size=(len(sizes), K, N)).astype(np.float32)
    return (jnp.asarray(x), jnp.asarray(w),
            jnp.asarray(tile_group, jnp.int32),
            jnp.asarray([sum(tiles)], jnp.int32), live,
            np.repeat(tile_group, tile))


CASES = {
    "ragged": dict(sizes=[5, 0, 17, 8, 1], tile=8, K=32, N=24),
    "one_group": dict(sizes=[11], tile=8, K=16, N=16),
    "blocked_features": dict(sizes=[9, 3], tile=8, K=256, N=384),
    "no_spare": dict(sizes=[8, 8], tile=8, K=16, N=8, spare_tiles=0),
}


@pytest.mark.parametrize("transpose_w", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_matmul_and_its_backward_against_a_loop(case, transpose_w,
                                                        monkeypatch):
    if case == "blocked_features":      # 2 x 3 blocks a matrix, not one
        monkeypatch.setattr(gmm, "_BLOCK_BYTES", 128 * 128 * 4)
        jax.clear_caches()
    x, w, tile_group, n_live, live, row_group = grouped_case(
        7, **CASES[case])
    dy = np.random.default_rng(8).normal(
        size=(x.shape[0], w.shape[2])).astype(np.float32) * live[:, None]

    if transpose_w:                     # the stack held output-major
        w = jnp.swapaxes(w, 1, 2)

    def loop(x, w):
        if transpose_w:
            w = jnp.swapaxes(w, 1, 2)
        return jnp.stack([x[r] @ w[row_group[r]] if live[r]
                          else jnp.zeros(w.shape[2])
                          for r in range(x.shape[0])])

    def kernel(x, w):
        y = grouped_matmul(x, w, tile_group, n_live, transpose_w)
        return jnp.where(live[:, None], y, 0.0)     # dead rows: unwritten

    with jax.default_matmul_precision("highest"):
        want, pull_want = jax.vjp(loop, x, w)
        got, pull_got = jax.vjp(kernel, x, w)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        for g, r in zip(pull_got(jnp.asarray(dy)),
                        pull_want(jnp.asarray(dy))):
            np.testing.assert_allclose(
                np.where(np.isnan(g), 0.0, g) if g.shape == x.shape else g,
                r, rtol=1e-5, atol=1e-4)


def test_weight_blocks(monkeypatch):
    assert gmm.feature_block(2688, 1024) == 896      # 21 x 128
    assert gmm.feature_block(1856, 1024) == 1856     # no multiple of 128
    assert gmm.feature_block(2688, 512) == 384
    assert gmm.feature_block(96, 128) == 96
    # the cell's expert matrices stay whole: bfloat16 and the float32 sum
    assert gmm.weight_blocks(2688, 1856, 2) == (2688, 1856)
    assert gmm.weight_blocks(1856, 2688, 4) == (1856, 2688)
    assert gmm.weight_blocks(8192, 8192, 2) == (1024, 8192)
    monkeypatch.setattr(gmm, "_BLOCK_BYTES", 128 * 128 * 4)
    assert gmm.weight_blocks(256, 384, 4) == (128, 128)


# ------------------------------------------------- the dispatch and its bound

def test_dispatch_lays_every_held_pair_out_once():
    chosen = jnp.asarray(np.random.default_rng(0).integers(
        0, 16, size=(40, 3)), jnp.int32)
    plan = moe_dropless.dispatch(chosen, (4, 5), 120, tile_rows=8)
    valid = np.asarray(plan.valid)
    pairs = np.asarray(plan.pair)[valid]
    flat = np.asarray(chosen).reshape(-1)
    held = np.flatnonzero((flat >= 4) & (flat < 9))
    assert sorted(pairs.tolist()) == held.tolist()       # each pair once
    group = np.repeat(np.asarray(plan.tile_group), 8)[valid]
    np.testing.assert_array_equal(flat[pairs] - 4, group)  # in its group
    assert int(plan.past_bound) == 0
    assert np.all(np.diff(np.asarray(plan.tile_group)) >= 0)
    assert set(np.asarray(plan.tile_group).tolist()) == set(range(5))
    stats = moe_dropless.load_stats(chosen, 16, (4, 5), tile_rows=8)
    assert stats["held_pairs"] == len(held) == int(valid.sum())
    assert stats["live_tiles"] == int(plan.n_live[0])
    assert stats["pairs_past_bound"] == 0 and stats["pairs"] == 120


def test_the_bound_is_every_pair_or_four_times_the_share():
    assert moe_dropless.rows_bound(98304, 8, 128) == 24576     # the cell
    assert moe_dropless.rows_bound(768, 2, 8) == 768           # a quarter
    assert moe_dropless.rows_bound(768, 8, 8) == 768           # all held
    assert moe_dropless.rows_bound(100, 1, 16) == 25
    assert moe_dropless.buffer_tiles(24576, 8) == 104
    assert moe_dropless.buffer_tiles(25, 1, tile_rows=8) == 5


def test_a_pair_past_the_bound_is_loud_never_dropped(monkeypatch):
    """Every token chooses the two held experts of 16: 64 held pairs where
    the share expects 8.  The layer's buffer (4 x 8 rows, in tiles of 256)
    takes them all; laid out in tiles of 8 the same bound cannot, and says
    so in every number it returns."""
    spec = ExpertsSpec(n_experts=16, top_k=2, d_expert=8, d_shared=8,
                       held=(0, 2))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 16))
    layer = ExpertLayer(16, spec, jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = jnp.asarray([10.0, 10.0] + [0.0] * 14)

    def apply():
        y, seen = layer.apply({"params": params}, x,
                              mutable=["intermediates"])
        return y, moe_dropless.load_stats(
            seen["intermediates"]["chosen"][0], 16, (0, 2))

    whole, stats = apply()
    assert stats["held_pairs"] == 64 and stats["pairs_past_bound"] == 0
    assert stats["max_load_over_mean"] == 8.0
    assert np.all(np.isfinite(whole))
    chosen = jnp.tile(jnp.asarray([[0, 1]], jnp.int32), (32, 1))
    plan = moe_dropless.dispatch(chosen, (0, 2), 8, tile_rows=8)
    assert int(plan.past_bound) == 64 - 3 * 8     # three tiles: 1 + 2 spare
    stats = moe_dropless.load_stats(chosen, 16, (0, 2), tile_rows=8)
    assert stats["buffer_tiles"] == 4 + 2          # 4 x 8 expected rows
    assert stats["pairs_past_bound"] == 64 - 6 * 8
    monkeypatch.setattr(moe_dropless, "BOUND_OVER_EXPECTED", 1)
    cut, stats = apply()                           # 8 rows: one tile + 2
    assert stats["pairs_past_bound"] == 0          # tiles of 256 hold 64
    np.testing.assert_array_equal(cut, whole)
    y = moe_dropless.combine(jnp.ones((24, 4)), jnp.ones((32, 2)), plan, 32)
    assert np.all(np.isnan(y))


# ------------------------------------- what the other families' tables build

@pytest.mark.parametrize("family", ["gpt2", "granite"])
def test_the_older_parameter_trees_are_unchanged(family):
    """The program's ``init`` gives the names and shapes the benchmark's
    seeded trees have had since PR 23 / PR 26."""
    if family == "gpt2":
        from chipbench import weights as w

        c = {"n_embd": 32, "n_head": 4, "n_inner": 64, "n_layer": 2,
             "vocab_size": 96, "n_positions": 64}
        lm = TransformerLM(vocab=96, d_model=32, n_heads=4, d_ff=64,
                           n_layers=2, max_len=64)
    else:
        from chipbench import weights_hybrid as w
        from tests.test_hybrid import config as granite

        c = granite()
        lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                           table=table_from_config(c))
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        w.shapes(c))


# ------------------------------------------- what the layer tells telemetry

def test_the_layer_publishes_its_geometry_when_someone_listens():
    from chainermn_tpu.observability import reporter, spans

    for name in ("moe-layer", "moe-route", "moe-dispatch", "moe-experts",
                 "moe-shared"):
        assert spans.is_scope(name)
    c = config("E", held=(2, 2))
    x = jnp.zeros((2, 8, 32))
    layer = Block(32, table_of(c).layers[0], jnp.float32)
    rep = reporter.Reporter()
    with reporter.scope(rep):
        layer.init(jax.random.PRNGKey(0), x)
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    assert gauges["moe/experts"] == 8 and gauges["moe/experts_held"] == 2
    assert gauges["moe/top_k"] == 3 and gauges["moe/tokens"] == 16
    assert gauges["moe/pair_rows"] == 48
    assert gauges["moe/tile_rows"] == moe_dropless.TILE_ROWS
    assert gauges["moe/buffer_rows"] == 3 * moe_dropless.TILE_ROWS  # 48 rows
    assert gauges["moe/pallas_tile_aligned"] == 1
    assert rep.summary()["counters"]["moe/calls"] >= 1


def test_the_layers_ops_carry_its_scopes():
    """Every part of the layer lowers under its scope, forward and
    backward: what the benchmark's ``moe.*`` readers join the trace to."""
    c = config("E", held=(0, 8))
    params = weights_nemotron.make(c, 3)["layer_0"]
    layer = Block(32, table_of(c).layers[0], jnp.float32)
    x = jnp.ones((1, 16, 32))
    text = jax.jit(jax.grad(lambda p: jnp.sum(
        layer.apply({"params": p}, x)))).lower(params).compile().as_text()
    import re

    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for name in ("moe-route", "moe-dispatch", "moe-experts", "moe-shared"):
        inside = [p for p in paths if f"/moe-layer/{name}/" in p
                  or f"/moe-layer/jit(_gmm_call)/{name}/" in p
                  or f"/moe-layer/jit(_dw_call)/{name}/" in p]
        assert any("transpose(jvp(" in p for p in inside), name
        assert any("transpose(jvp(" not in p for p in inside), name


def test_a_rematerialised_layer_keeps_the_forwards_choice():
    """In bfloat16 a backward pass that chose its experts again could
    settle a near-tie otherwise than the forward did, and read the grouped
    products ``remat`` keeps by another layout: the routers' choice is
    kept with them, so the gradients are the unrematerialised model's to
    the rounding of recomputed activations."""
    c = config(held=(2, 2))
    params = weights_nemotron.make(c, 5)
    toks = tokens(1, 2, 65, c["vocab_size"])
    x, y = toks[:, :-1], toks[:, 1:]

    def grads(remat):
        lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                           table=table_of(c), remat=remat)

        def loss(p):
            z = lm.apply({"params": p}, x)
            picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
            return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)

        return weights.flatten(jax.jit(jax.grad(loss))(params))

    kept, plain = grads(True), grads(False)
    for path, want in plain.items():
        gap = float(jnp.linalg.norm(kept[path] - want)
                    / (jnp.linalg.norm(want) + 1e-30))
        assert gap < 0.04, (weights.leaf_name(path), gap)
