"""The ``bailing_hybrid`` rows of the block table (Ling-3.0) — Kimi-Delta-
Attention rows, a latent-attention row whose scores are wider than its
values, a head-wise gate on both, two leading dense FFNs, a group-limited
sigmoid router over one expert-parallel rank's share with a shared expert
— against the plain reference the benchmark compares with on the chip
(``chipbench/refs/ling3.py``: the delta rule as its recurrence, attention
as an explicit masked softmax, the router by its definition with a sort,
dense masked sums over the held experts, none of the program's code)."""

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
    BAILING_HYBRID_KEYS,
    ExpertsSpec,
    KDASpec,
    LayerSpec,
    MLASpec,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    TransformerLM,
    causal_mask,
    remat_kept,
    remat_names,
    remat_policy,
    rotate_partial,
)
from chainermn_tpu.observability import device_trace, spans  # noqa: E402
from chainermn_tpu.ops import make_flash_attention_fn  # noqa: E402
from chainermn_tpu.parallel import moe_dropless  # noqa: E402
from chipbench import weights, weights_ling3  # noqa: E402
from chipbench.refs import ling3 as reference  # noqa: E402
from test_remat_policy import kernel_calls  # noqa: E402

D_MODEL, VOCAB = 32, 96
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: The benchmark's own keys in a configuration, beside the published ones.
OWN_KEYS = ("num_experts_published", "experts_held_first", "n_layer",
            "optimizer", "balancing")


def config(held=(0, 16), n_layer=6, **over):
    """A ``bailing_hybrid`` config at toy widths, keys as published, plus
    the benchmark's own: the layers kept and the experts held.  Six
    layers: kda, kda, mla, kda, kda, mla; the first dense."""
    c = {
        "model_type": "bailing_hybrid", "num_hidden_layers": 6,
        "hidden_size": D_MODEL, "vocab_size": VOCAB,
        "intermediate_size": 48, "rms_norm_eps": 1e-06,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "layer_group_size": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
        "kv_lora_rank": 12, "q_lora_rank": None, "qk_head_dim": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 10000, "rope_interleave": True, "rope_scaling": None,
        "rotary_dim": 8, "partial_rotary_factor": 0.5, "use_qk_norm": True,
        "use_mla_nope": False,
        "gated_attention_proj_granularity_type": "head_wise",
        "short_conv_kernel_size": 4, "linear_silu": True,
        "kda_safe_gate": True, "kda_lower_bound": -5, "no_kda_lora": True,
        "use_kda_lora": False, "group_norm_size": 1,
        "num_kv_heads_for_linear_attn": 0, "num_experts": held[1],
        "num_experts_per_tok": 3, "num_shared_experts": 1,
        "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 24,
        "moe_router_enable_expert_bias": True, "n_group": 4,
        "topk_group": 2, "topk_method": "noaux_tc", "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "score_function": "sigmoid",
        "scoring_func": "sigmoid", "scale_router_input": False,
        "expert_swiglu_limit_list": [0] * 6,
        "share_expert_swiglu_limit_list": [0] * 6, "use_nGPT": False,
        "value_norm": False, "up_proj_norm": False, "use_bias": False,
        "use_qkv_bias": False, "mtp_use_kda": False,
        "mtp_loss_scaling_factor": 0, "num_nextn_predict_layers": 1,
        "max_position_embeddings": 1024, "max_window_layers": 20,
        "seq_aux": True,
        # the benchmark's own keys
        "num_experts_published": 16, "experts_held_first": held[0],
        "n_layer": n_layer,
    }
    c.update(over)
    return c


def table_of(c):
    published = {k: v for k, v in c.items() if k not in OWN_KEYS}
    published["num_experts"] = c["num_experts_published"]
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


def catalog_config():
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Ling-3.0-flash"]
    return row["config"]


# ------------------------------------------------- the table from the keys

@pytest.mark.parametrize("i", range(12))
def test_the_catalog_rows_layer_kinds(i):
    """Layers 0-11 of the published row: latent attention where ``(i + 1)
    % 6 == 0``, else KDA; layers 0 and 1 dense at 6144, then the experts."""
    row = table_from_config(catalog_config(), n_layers=12,
                            experts_held=(0, 8)).layers[i]
    if (i + 1) % 6 == 0:
        assert row.mixer == "attention" and row.kda is None
        assert row.mla == MLASpec(kv_rank=512, d_nope=128, d_rope=64,
                                  d_v=128, rope_theta=6e6, interleave=True)
        assert row.head_gate and row.qk_norm and row.n_heads == 32
    else:
        assert row.mixer == "kda" and row.mla is None
        assert row.kda == KDASpec(n_heads=32, d_k=128, d_v=128, d_conv=4,
                                  chunk=64, lower_bound=-5.0)
    if i < 2:
        assert (row.ffn, row.d_ff, row.experts) == ("swiglu", 6144, None)
    else:
        assert row.ffn == "experts"
        assert row.experts == ExpertsSpec(
            n_experts=512, top_k=8, d_expert=768, d_shared=768, held=(0, 8),
            scaling=2.5, router="sigmoid", expert="swiglu", n_group=8,
            topk_group=4)
    assert (row.norm, row.norm_eps) == ("rmsnorm", 1e-6)


def test_the_catalog_rows_table_and_count():
    """The whole published model cannot be built (layers 34-41 clamp);
    stage 0 as the cell cuts it counts what the configuration reckons."""
    c = catalog_config()
    with pytest.raises(ValueError, match="SwiGLU clamp .layers .34, 35"):
        table_from_config(c)
    table = table_from_config(c, n_layers=34)
    assert len(table.layers) == 34
    assert (table.positions, table.final_norm, table.tied_head) == (
        "rotary", "rmsnorm", False)
    cell = dict(c, num_experts=8, num_experts_published=512,
                experts_held_first=0, n_layer=6, vocab_size=19648)
    assert weights_ling3.n_params(cell) == 707_780_640


@pytest.mark.parametrize("key,value,needle", [
    ("expert_swiglu_limit_list", [0, 0, 0, 4, 0, 0], "SwiGLU clamp"),
    ("share_expert_swiglu_limit_list", [0, 0, 5, 0, 0, 0], "SwiGLU clamp"),
    ("expert_swiglu_limit_list", [0] * 5, "do not list"),
    ("foo", 1, "bailing_hybrid keys .'foo'.; the reader takes"),
    ("use_nGPT", True, "use_nGPT"), ("value_norm", True, "value_norm"),
    ("up_proj_norm", True, "up_proj_norm"),
    ("scale_router_input", True, "scale_router_input"),
    ("use_mla_nope", True, "use_mla_nope"), ("use_bias", True, "use_bias"),
    ("use_qkv_bias", True, "use_qkv_bias"),
    ("use_kda_lora", True, "use_kda_lora"),
    ("mtp_use_kda", True, "mtp_use_kda"),
    ("num_kv_heads_for_linear_attn", 2, "num_kv_heads_for_linear_attn"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("q_lora_rank", 64, "q_lora_rank"),
    ("no_kda_lora", False, "no_kda_lora"),
    ("kda_safe_gate", False, "kda_safe_gate"),
    ("linear_silu", False, "linear_silu"),
    ("use_qk_norm", False, "use_qk_norm"),
    ("group_norm_size", 4, "group_norm_size"),
    ("gated_attention_proj_granularity_type", "element_wise", "head_wise"),
    ("num_key_value_heads", 1, "num_key_value_heads"),
    ("qk_head_dim", 32, "disagree"), ("rotary_dim", 4, "disagree"),
    ("scoring_func", "softmax", "sigmoid"),
    ("topk_method", "greedy", "noaux_tc"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("num_shared_experts", 2, "num_shared_experts"),
    ("mtp_loss_scaling_factor", 0.3, "mtp_loss_scaling_factor"),
    ("tie_word_embeddings", True, "tied"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_table_from_config_refuses_by_key(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_of(config(**{key: value}))


def test_the_reader_takes_every_key_of_the_catalog_row():
    assert set(catalog_config()) <= BAILING_HYBRID_KEYS
    assert set(config()) - set(OWN_KEYS) <= BAILING_HYBRID_KEYS


def test_a_clamp_on_a_layer_that_is_not_kept_is_carried():
    c = config(n_layer=3, expert_swiglu_limit_list=[0, 0, 0, 4, 4, 4])
    assert len(table_of(c).layers) == 3
    # a dense layer has no expert to clamp: its entry is not read
    assert table_of(config(expert_swiglu_limit_list=[7, 0, 0, 0, 0, 0]))


@pytest.mark.parametrize("kw,needle", [
    (dict(mixer="kda"), "KDASpec"),
    (dict(mixer="mamba2", mla=MLASpec(8, 8, 4, 8)), "SSMSpec|attention"),
    (dict(mla=MLASpec(8, 8, 4, 8), rotary_dim=4), "MLASpec"),
    (dict(mla=MLASpec(8, 8, 4, 8), n_kv_heads=1, n_heads=2), "MLASpec"),
    # (a gate a head is a plain attention row's too since PR 51: what a
    # row may not have is both gates, or the gate without attention)
    (dict(head_gate=True, out_gate=True), "head_gate"),
    (dict(mixer="kda", head_gate=True), "KDASpec|head_gate"),
    (dict(ffn="experts", experts=dict(n_group=3)), "router groups"),
    (dict(ffn="experts", experts=dict(n_group=4, topk_group=5)),
     "router groups"),
    (dict(ffn="experts", experts=dict(topk_group=2)), "topk_group"),
    (dict(ffn="experts", experts=dict(n_group=4, topk_group=1, top_k=5)),
     "router groups"),
    (dict(ffn="experts", experts=dict(n_group=4, topk_group=2,
                                      router="softmax")), "router groups"),
])
def test_a_row_states_what_it_has(kw, needle):
    if "experts" in kw:
        base = dict(n_experts=16, top_k=2, d_expert=8, d_shared=0)
        with pytest.raises(ValueError, match=needle):
            LayerSpec(**dict(kw, experts=ExpertsSpec(
                **dict(base, **kw["experts"]))))
        return
    with pytest.raises(ValueError, match=needle):
        LayerSpec(**kw)


def test_the_caches_take_neither_new_row():
    c = config()
    for decode in (dict(decode=True), dict(paged="prefill", page_count=2,
                                           page_size=4)):
        with pytest.raises(ValueError, match="plain attention layers only"):
            model(c, **decode).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))


# ------------------------------------------------------ the rotary lanes

@pytest.mark.parametrize("interleave", [True, False])
def test_rotation_on_the_rope_part_is_the_references(interleave):
    """A head ``[nope | rope]``: the rope part turned by neighbours (or
    halves), the nope part untouched, forward and cotangent."""
    c = config(rope_interleave=interleave)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 11, 2, 24))
    pos = jnp.arange(11)

    def program(x):
        return rotate_partial(x, pos, 8, 10000.0, None, (16, interleave))

    def plain(x):
        return jnp.concatenate(
            [x[0, ..., :16], reference.rotate(x[0, ..., 16:], c)], -1)[None]

    np.testing.assert_allclose(program(x), plain(x), rtol=1e-6, atol=1e-6)
    g = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    np.testing.assert_allclose(
        jax.vjp(program, x)[1](g)[0], jax.vjp(plain, x)[1](g)[0],
        rtol=1e-6, atol=1e-6)


# ------------------------------------------------- the group-limited router

def _reference_choice(h, w, bias, c):
    mask, weight = reference.router(
        h, {"router": w, "router_bias": bias}, c)
    return np.asarray(mask), np.asarray(weight)


def _as_mask(chosen, n):
    got = np.zeros((chosen.shape[0], n), bool)
    np.put_along_axis(got, np.asarray(chosen), True, axis=-1)
    return got


def _route(h, w, bias, c):
    return moe_dropless.route(
        h, w, bias, top_k=c["num_experts_per_tok"],
        scaling=c["routed_scaling_factor"], n_group=c["n_group"],
        topk_group=c["topk_group"])


@pytest.mark.parametrize("seed", range(3))
def test_group_limited_choice_is_the_definition_with_a_sort(seed):
    c = config()
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (64, D_MODEL))
    w = jax.random.normal(ks[1], (D_MODEL, 16)) * 0.3
    bias = 0.2 * jax.random.normal(ks[2], (16,))
    chosen, weight = _route(h, w, bias, c)
    mask, ref_weight = _reference_choice(h, w, bias, c)
    np.testing.assert_array_equal(_as_mask(chosen, 16), mask)
    # every token's three lie in two groups of four
    assert (np.unique(np.asarray(chosen) // 4, axis=None).size <= 4
            and all(len(set(row // 4)) <= 2 for row in np.asarray(chosen)))
    dense = np.zeros((64, 16), np.float32)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(weight), -1)
    np.testing.assert_allclose(dense, ref_weight, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-6)


def test_ties_go_to_the_lower_index_in_groups_and_experts():
    """A zero router: every score 0.5, every group's score 1.0.  The two
    first groups stay, and the three first experts of the first."""
    c = config()
    h = jnp.ones((5, D_MODEL))
    chosen, weight = _route(h, jnp.zeros((D_MODEL, 16)), jnp.zeros(16), c)
    np.testing.assert_array_equal(chosen, np.tile([0, 1, 2], (5, 1)))
    np.testing.assert_allclose(weight, 2.5 / 3, rtol=1e-6)
    mask, _ = _reference_choice(h, jnp.zeros((D_MODEL, 16)), jnp.zeros(16),
                                c)
    np.testing.assert_array_equal(_as_mask(chosen, 16), mask)


def test_a_bias_flips_a_group_and_leaves_the_weights_alone():
    """Scores that favour groups 0 and 1; a bias on group 3's two best
    lifts it over both for the CHOICE — of the groups and of the experts
    among them — and the weights are still the scores'."""
    c = config()
    logits = np.full((1, 16), -2.0, np.float32)
    logits[0, [0, 1, 4, 5]] = [2.0, 1.5, 1.0, 0.5]
    logits[0, [12, 13]] = [0.0, -0.5]
    w = jnp.asarray(np.linalg.pinv(np.ones((1, D_MODEL), np.float32))
                    @ logits)
    h = jnp.ones((1, D_MODEL))
    plain, _ = _route(h, w, jnp.zeros(16), c)
    assert sorted(np.asarray(plain)[0]) == [0, 1, 4]
    bias = jnp.zeros(16).at[jnp.asarray([12, 13])].set(0.6)
    flipped, weight = _route(h, w, bias, c)
    assert sorted(np.asarray(flipped)[0]) == [0, 12, 13]
    s = jax.nn.sigmoid(jnp.asarray(logits[0]))
    took = s[np.asarray(flipped)[0]]
    np.testing.assert_allclose(weight[0], 2.5 * took / took.sum(), rtol=1e-5)
    np.testing.assert_array_equal(
        _as_mask(flipped, 16), _reference_choice(h, w, bias, c)[0])


def test_every_group_kept_is_the_choice_without_groups():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    h = jax.random.normal(ks[0], (48, D_MODEL))
    w = jax.random.normal(ks[1], (D_MODEL, 16))
    bias = 0.1 * jax.random.normal(ks[2], (16,))
    plain = moe_dropless.route(h, w, bias, top_k=3, scaling=2.5)
    grouped = moe_dropless.route(h, w, bias, top_k=3, scaling=2.5,
                                 n_group=4, topk_group=4)
    for a, b in zip(plain, grouped):
        np.testing.assert_array_equal(a, b)


def test_groups_that_do_not_divide_the_experts_are_refused():
    with pytest.raises(ValueError, match="groups"):
        moe_dropless.keep_groups(jnp.zeros((2, 10)), 4, 2)


def test_load_stats_counts_the_tokens_that_kept_the_held_group():
    chosen = np.array([[0, 1, 5], [8, 9, 12], [4, 13, 14], [3, 8, 9]])
    near = lambda held: moe_dropless.load_stats(  # noqa: E731
        chosen, 16, held, n_group=4)["held_group_token_share"]
    assert near((0, 2)) == 0.5          # group 0: tokens 0 and 3
    assert near((3, 2)) == 0.75         # groups 0 and 1: tokens 0, 2, 3
    assert "held_group_token_share" not in moe_dropless.load_stats(
        chosen, 16, (0, 2))


# ----------------------------------------- the program against the reference

def both_sides(held, flash):
    """Logits, loss and gradients of the program (float32, ``highest``;
    ``flash``: the latent rows through the flash adapter in interpret
    mode at blocks of 8 — scores over 24, values of 16 — else the dense
    masked path) and of the reference on one seeded tree."""
    c = config(held=held)
    params = weights_ling3.make(c, 2**31 + 11)
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
    return both_sides((0, 16), True)


@pytest.fixture(scope="module")
def some_held_dense():
    return both_sides((3, 6), False)     # a share that straddles two groups


@pytest.fixture(params=["all_held_flash", "some_held_dense"])
def sides(request):
    return request.getfixturevalue(request.param)


def test_program_logits_and_loss_match_the_reference(sides):
    # Both sides are float32 at ``highest``: what is left is the order of
    # sums (the chunked rule against the recurrence, the kernels' online
    # softmax against a whole one, sorted row groups against a dense
    # masked sum).
    (logits, loss, _), (ref, ref_loss, _) = sides
    np.testing.assert_allclose(logits, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def _leaves():
    return [weights.leaf_name(p) for p in sorted(
        weights_ling3.shapes(config()))]


@pytest.mark.parametrize("leaf", _leaves())
def test_program_gradient_matches_the_reference(sides, leaf):
    # rtol 1e-3 with an absolute floor of 2e-5 of the leaf's largest
    # entry, as the other families' tests.  The expert bias enters the
    # choice only: no gradient on either side.
    (_, _, grads), (_, _, ref_grads) = sides
    got = weights.flatten(grads)[tuple(leaf.split("/"))]
    want = weights.flatten(ref_grads)[tuple(leaf.split("/"))]
    scale = float(jnp.max(jnp.abs(want)))
    if leaf.endswith("router_bias"):
        assert scale == 0.0 and not np.any(np.asarray(got))
        return
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-5 * scale)


def test_the_seeded_tree_is_the_programs_tree():
    """Names and shapes of ``weights_ling3`` against the program's own
    ``init`` (the reference reads the tree by these names)."""
    c = config(held=(3, 6))
    shapes = jax.eval_shape(
        lambda: model(c).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        weights_ling3.shapes(c))
    kinds = weights_ling3.kinds(c)
    assert [m for m, _ in kinds] == ["kda", "kda", "mla"] * 2
    assert [f for _, f in kinds] == ["dense"] + ["sparse"] * 5


def test_the_programs_choices_are_the_references():
    c = config()
    params = weights_ling3.make(c, 2**31 + 11)
    x = tokens(1, 2, 41)[:, :-1]
    lm = model(c, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, seen = lm.apply({"params": params}, x, mutable=["intermediates"])
        want = reference.chosen_experts(params, x, c)
    assert sorted(want) == [f"layer_{i}" for i in range(1, 6)]
    for name, mask in want.items():
        chosen = seen["intermediates"][name]["ExpertLayer_0"]["chosen"][0]
        assert chosen.shape == (2 * 40, 3)
        np.testing.assert_array_equal(
            _as_mask(chosen, 16).reshape(mask.shape), mask)


def test_the_train_step_follows_the_references_steps():
    """The tiny model through ``create_multi_node_optimizer`` ->
    ``make_train_step`` with fused CE and the flash adapter, AdamW and
    the balancing controller beside it, as the cell's runner drives it:
    two steps' losses, the first gradient (AdamW's first moment) and the
    parameters' change against ``reference.train_steps`` taking the
    step's own choice.  The rate is small because AdamW's first step moves
    an entry by the rate times the SIGN of its gradient, and the two
    sides' near-zero entries (0.05 to 0.4% of a matrix) differ in sign:
    at 1e-3 that alone moves the second loss by 1.4e-4 of itself."""
    import optax

    import chainermn_tpu
    from chainermn_tpu.communicators import build_mesh
    from chainermn_tpu.models.transformer import rebalance_routers
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    c = config(held=(3, 6), optimizer={
        "learning_rate": 1e-5, "weight_decay": 0.1, "b1": 0.9, "b2": 0.999,
        "eps": 1e-8}, balancing={"rate": 0.05})
    lm = model(c, dtype=jnp.float32, remat=True,
               attention_fn=make_flash_attention_fn(
                   causal=True, block_q=8, block_k=8))
    comm = chainermn_tpu.create_communicator("xla_ici", mesh=build_mesh(
        inter_size=1, intra_size=1, devices=jax.devices()[:1]))
    o = c["optimizer"]
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"]), comm)

    def loss_fn(p, batch):
        h, seen = lm.apply({"params": p}, batch[0], return_hidden=True,
                           mutable=["intermediates"])
        chosen = {name: layer["ExpertLayer_0"]["chosen"][0]
                  for name, layer in seen["intermediates"].items()}
        return fused_cross_entropy(h, p["lm_head"], batch[1],
                                   chunk=16), chosen

    step = opt.make_train_step(loss_fn, has_aux=True, donate=False)
    make = lambda: weights_ling3.make(c, 2**31 + 3)  # noqa: E731
    batches = [(np.asarray(t[:, :-1]), np.asarray(t[:, 1:]))
               for t in (tokens(5, 2, 33), tokens(6, 2, 33))]
    with jax.default_matmul_precision("highest"):
        params, state = make(), None
        state = opt.init(params)
        losses, routed, first = [], [], None
        for batch in batches:
            params, state, loss, chosen = step(params, state, batch)
            params = rebalance_routers(params, chosen, 0.05)
            losses.append(float(loss))
            routed.append(jax.device_get(chosen))
            if first is None:
                first = jax.tree.map(
                    lambda m: m / (1 - o["b1"]),
                    [s for s in jax.tree.leaves(
                        state, is_leaf=lambda s: hasattr(s, "mu"))
                     if hasattr(s, "mu")][0].mu)
        ref = reference.train_steps(make, batches, c, forced=routed)
    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-5)
    start = make()
    for path, want in weights.flatten(ref["delta_norms"]).items():
        name = weights.leaf_name(path)
        got = float(jnp.linalg.norm(
            weights.flatten(params)[path] - weights.flatten(start)[path]))
        # (5%: one entry of a 16-entry norm scale whose gradient is near
        # zero steps the other way on the two sides, 2.6% of the norm)
        assert got == pytest.approx(float(want), rel=5e-2, abs=1e-9), name
        g = float(jnp.linalg.norm(weights.flatten(first)[path]))
        assert g == pytest.approx(
            float(weights.flatten(ref["grad_norms"])[path]), rel=1e-2,
            abs=1e-9), name   # (A_log: two numbers, sums that cancel)
    # the controller moved the biases on both sides, by the same pairs
    assert float(weights.flatten(ref["delta_norms"])[
        ("layer_1", "ExpertLayer_0", "router_bias")]) > 0


def test_remat_on_and_off_give_the_same_gradients():
    c = config(held=(3, 6))
    params = weights_ling3.make(c, 2**31 + 1)
    x = tokens(3, 1, 24)

    def grads(remat):
        lm = model(c, dtype=jnp.float32, remat=remat)
        return jax.grad(lambda p: jnp.sum(
            lm.apply({"params": p}, x) ** 2))(params)

    with jax.default_matmul_precision("highest"):
        a, b = grads(True), grads(False)
    for path, want in weights.flatten(b).items():
        np.testing.assert_allclose(
            weights.flatten(a)[path], want, rtol=1e-5, atol=1e-7,
            err_msg=weights.leaf_name(path))


def test_remat_kept_reckons_an_mla_rows_output_at_its_value_width():
    table = table_from_config(catalog_config(), n_layers=6,
                              experts_held=(0, 8))
    kept = remat_kept(table, 2560, 16384, 2, seq=16384)
    assert (kept["layers"], kept["flash_layers"],
            kept["expert_layers"]) == (6, 1, 4)
    assert kept["flash-residuals_bytes"] == 16384 * 32 * (128 * 2 + 4)
    assert kept["gdn-residuals_bytes"] == 0
    # five KDA rows: ``o`` (134 MB) and a float32 state a head and tile of
    # 512 tokens (67 MB), 201 MB a row
    assert kept["kda-residuals_bytes"] == 5 * 32 * 128 * (
        16384 * 2 + 32 * 128 * 4) == 5 * 201326592


def test_remat_kept_for_kda_rows_is_what_the_kept_names_hold():
    """``remat_kept`` against the arrays the forward rule names, at a
    tiny shape: two rows of 64 tokens through four KDA rows of 2 heads of
    16, chunk 64."""
    from chainermn_tpu.ops import kda

    c = config()
    table = table_of(c)
    rows = [row for row in table.layers if row.mixer == "kda"]
    assert len(rows) == 4 and "kda-residuals" in remat_names()
    z = rows[0].kda
    q = jnp.zeros((2, 64, z.n_heads, z.d_k), jnp.float32)
    o, starts = jax.eval_shape(
        lambda q, v, beta: kda._kda_fwd_call(
            q, q, v, q, beta, beta[0, 0], q[0, 0], C=z.chunk,
            floor=z.lower_bound, keep=True, interpret=True),
        q, jnp.zeros((2, 64, z.n_heads, z.d_v), jnp.float32),
        jnp.zeros((2, 64, z.n_heads), jnp.float32))
    held = sum(x.size * x.dtype.itemsize for x in (o, starts))
    assert remat_kept(table, D_MODEL, 128, 4, seq=64)[
        "kda-residuals_bytes"] == 4 * held


@pytest.mark.parametrize("remat", [True, False])
def test_a_train_step_calls_the_forward_kernel_once_a_kda_layer(remat):
    """Under ``remat=True`` the policy keeps ``KDA_RESIDUALS``: the
    gradient of the tiny model holds ONE ``kda-fwd`` and one ``kda-bwd``
    call a KDA layer, as without remat — the layer's recomputation does
    not run the kernel again."""
    c = config(held=(3, 6), n_layer=3)
    lm = model(c, dtype=jnp.float32, remat=remat)
    x = tokens(4, 1, 32)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), x))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        lm.apply(p, x) ** 2)))(params).jaxpr
    assert kernel_calls(jaxpr, "kda-fwd") == 2
    assert kernel_calls(jaxpr, "kda-bwd") == 2


def test_a_checkpoint_without_the_name_would_run_the_forward_twice():
    from chainermn_tpu.ops.kda import kda_rule

    q = jnp.ones((1, 32, 1, 8)) / 4

    def loss(q, f):
        return jnp.sum(kda_rule(q, q, q, f, jnp.ones((1, 32, 1)) / 2,
                                jnp.ones((1,)), jnp.zeros((1, 8)),
                                lower_bound=-5.0, chunk=16))

    for policy, fwd_calls in ((remat_policy(), 1), (None, 2)):
        jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(
            loss, policy=policy), argnums=(0, 1)))(q, -q).jaxpr
        assert kernel_calls(jaxpr, "kda-fwd") == fwd_calls
        assert kernel_calls(jaxpr, "kda-bwd") == 1


def test_the_kda_layer_says_its_kernels_geometry_when_someone_listens(
        tmp_path):
    from chainermn_tpu.observability import reporter, step_log

    c = config(n_layer=1)
    x = jnp.zeros((2, 32, D_MODEL))
    layer = Block(D_MODEL, table_of(c).layers[0], jnp.float32)
    rep, path = reporter.Reporter(), str(tmp_path / "steps.jsonl")
    with reporter.scope(rep), step_log.recording(path):
        layer.init(jax.random.PRNGKey(0), x, None)
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    assert gauges["kda/kernel"] == 1 and "kda/xla_chunked" not in gauges
    assert gauges["kda/heads"] == 2 and gauges["kda/d_k"] == 16
    assert gauges["kda/chunk"] == 32 and gauges["kda/chunks"] == 1
    assert gauges["kda/sub_block"] == 16
    assert gauges["kda/tokens_a_step"] == 32
    assert gauges["kda/heads_a_step"] == 2          # the heads pair up
    assert gauges["kda/grid_steps"] == 2 and gauges["kda/vmem_bytes"] > 0
    # q, k, f, v in float32 and the beta row, two heads of 32 tokens
    assert gauges["kda/operand_bytes_a_step"] == 2 * 32 * (
        4 * (3 * 16 + gauges["kda/d_v"]) + 4)
    rows = {r["event"]: r for r in map(json.loads, open(path))}
    assert rows["kda_geometry"]["form"] == "kernel"
    assert rows["kda_geometry"]["gate_side"] == "kernel"


def test_the_latent_row_through_flash_is_the_dense_path():
    """Scores over 24, values of 16: the kernels at a value width of
    their own against the row's own dense softmax."""
    c = config(n_layer=3)
    row = table_of(c).layers[2]
    assert row.mla is not None
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, D_MODEL))
    dense = Block(D_MODEL, row, jnp.float32)
    params = dense.init(jax.random.PRNGKey(0), x, causal_mask(32))
    flash = Block(D_MODEL, row, jnp.float32, make_flash_attention_fn(
        causal=True, block_q=8, block_k=16))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            flash.apply(params, x), dense.apply(params, x, causal_mask(32)),
            rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="without a scale"):
        Block(D_MODEL, row, jnp.float32, make_flash_attention_fn(
            causal=True, scale=0.1)).apply(params, x)


# ------------------------------------------------ the shares of one layer

def test_the_shares_of_a_sparse_layer_add_up_to_the_uncut_layer():
    """What ties one rank's share to the model: the ``n_experts /
    count`` ranks' layers — shares of six, five and five of sixteen
    experts in four groups of four, so a share straddles two groups —
    add up, the shared expert and the mixer counted once, to the
    reference's layer with all sixteen."""
    whole = config(held=(0, 16), n_layer=2)
    params = weights_ling3.make(whole, 2**31 + 5)["layer_1"]
    e = params["ExpertLayer_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, D_MODEL))
    stacks = ("experts_gate", "experts_up", "experts_down")
    mask = causal_mask(16)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.layer(row, params, whole, "float32")
                          for row in x])
        base, total = None, 0.0
        for first, count in ((0, 6), (6, 5), (11, 5)):
            c = config(held=(first, count), n_layer=2)
            row = table_of(c).layers[1]
            assert row.experts.held == (first, count)
            share = dict(e, **{k: e[k][first:first + count]
                               for k in stacks})
            out = Block(D_MODEL, row, jnp.float32).apply(
                {"params": dict(params, ExpertLayer_0=share)}, x, mask)
            if base is None:    # x + mixer + shared expert, no routed one
                hollow = dict(e, **{k: jnp.zeros_like(share[k])
                                    for k in stacks})
                base = Block(D_MODEL, row, jnp.float32).apply(
                    {"params": dict(params, ExpertLayer_0=hollow)}, x, mask)
            total = total + out - base
            # and each share is the reference's share
            ref_share = jnp.stack([reference.layer(
                r, dict(params, ExpertLayer_0=share), c, "float32")
                for r in x])
            np.testing.assert_allclose(out, ref_share, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total + base, want, rtol=2e-4, atol=2e-5)
    # the shared expert is in ``base`` once: leaving it out moves the sum
    no_shared = jnp.stack([
        row + reference.experts(
            reference.rms_norm(row, params["RMSNorm_1"]["scale"], 1e-6), e,
            whole, "float32", shared=False) for row in x])
    assert float(jnp.max(jnp.abs(no_shared - want))) > 1e-3


# ------------------------------------------------ the new scopes in a trace

@pytest.fixture(scope="module")
def compiled_text():
    """The tiny model through ``make_train_step`` under ``remat`` with
    the flash adapter, compiled on the CPU."""
    import optax

    import chainermn_tpu
    from chainermn_tpu.communicators import build_mesh
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    c = config(held=(3, 6), n_layer=3)
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


def test_the_new_regions_are_on_their_rows_ops(compiled_text):
    for name in ("kda-mixer", "kda-scan", "mla-mixer"):
        assert spans.is_region(name) and spans.is_scope(name)
    table = device_trace.scope_table(compiled_text)
    by_layer, under = {}, {}
    for path in table.values():
        layer, on = device_trace.layer_of(path), device_trace.scopes_on(path)
        for name in ("kda-mixer", "mla-mixer"):
            if name in on:
                by_layer.setdefault(name, set()).add(layer)
                under.setdefault(name, set()).add(
                    device_trace.owner(path)[1])
    assert by_layer == {"kda-mixer": {"0", "1"}, "mla-mixer": {"2"}}
    assert {"kda-scan", "ssm-conv", "mixer-proj", "mixer-gate"} <= under[
        "kda-mixer"]
    assert {"flash-fwd", "flash-bwd-dkv", "attn-rope",
            "mixer-proj", "mixer-gate"} <= under["mla-mixer"]
    assert "flash-bwd-dq" not in under["mla-mixer"]   # one backward pass
    # the latent row is an attention row: its region sits inside the part
    assert all("attn-mixer" in device_trace.scopes_on(p)
               for p in table.values()
               if "mla-mixer" in device_trace.scopes_on(p))
