"""The ``qwen3_next`` rows of the block table — a Gated DeltaNet mixer (the
chunked gated delta rule), a gated attention row with QK-norm and partial
rotary positions, a softmax top-k router over one expert-parallel rank's
share of the experts and a gated shared expert — against the plain
reference the benchmark compares with on the chip
(``chipbench/refs/qwen3_next.py``: the delta rule one step a token, dense
masked sums, none of the program's code)."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chainermn_tpu.models.block_table import (  # noqa: E402
    BlockTable,
    ExpertsSpec,
    GDNSpec,
    LayerSpec,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    ExpertLayer,
    GatedDeltaNetMixer,
    MultiHeadAttention,
    TransformerLM,
    ZeroCentredRMSNorm,
    causal_mask,
)
from chainermn_tpu.ops import gated_delta  # noqa: E402
from chainermn_tpu.parallel import moe_dropless  # noqa: E402
from chipbench import weights, weights_qwen3next  # noqa: E402
from chipbench.refs import qwen3_next as reference  # noqa: E402

D_MODEL, VOCAB = 32, 96


def config(held=(0, 8), n_layer=4, **over):
    """A ``qwen3_next`` config at toy widths, keys as published, plus the
    benchmark's own: the layers kept and the experts held."""
    c = {
        "model_type": "qwen3_next", "decoder_sparse_step": 1,
        "full_attention_interval": 4, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": D_MODEL, "intermediate_size": 64,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_value_head_dim": 8, "max_position_embeddings": 1024,
        "mlp_only_layers": [], "moe_intermediate_size": 24,
        "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts": held[1], "num_experts_published": 8,
        "experts_held_first": held[0], "num_experts_per_tok": 3,
        "num_hidden_layers": 8, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 24, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": VOCAB,
        "n_layer": n_layer,
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


# ------------------------------------------------- the table from the keys

def test_the_published_keys_give_three_gdn_rows_to_one_gated_attention_row():
    c = config()
    table = table_from_config(dict(c, num_experts=8))
    assert len(table.layers) == 8 and table.positions == "rotary"
    assert table.final_norm == "rmsnorm_zc" and not table.tied_head
    assert [r.mixer for r in table.layers] == [
        "gdn", "gdn", "gdn", "attention"] * 2
    gdn, att = table.layers[0], table.layers[3]
    assert gdn.gdn == GDNSpec(n_k_heads=2, n_v_heads=4, d_k=8, d_v=8,
                              d_conv=4, chunk=64)
    assert (gdn.gdn.key_dim, gdn.gdn.value_dim, gdn.gdn.conv_dim) == (
        16, 32, 64)
    assert (att.n_heads, att.n_kv_heads, att.d_head) == (4, 2, 16)
    assert att.rotary_dim == 4 and att.rope_theta == 1e7
    assert att.qk_norm and att.out_gate and att.attn_scale is None
    for row in table.layers:
        assert row.norm == "rmsnorm_zc" and row.norm_eps == 1e-6
        assert row.ffn == "experts" and row.experts == ExpertsSpec(
            n_experts=8, top_k=3, d_expert=24, d_shared=24,
            router="softmax", expert="swiglu", shared_gate=True)
    cut = table_of(config(held=(2, 4)))
    assert len(cut.layers) == 4
    assert cut.layers[0].experts.experts_held == (2, 4)
    assert cut.layers[0].experts.n_experts == 8      # the router's width


def test_the_catalog_rows_keys_build_the_cells_table_and_count():
    """The published widths, through the configuration file: three gdn
    rows and one gated attention row with four 512-wide softmax-routed
    expert FFNs, and the parameter count of the file's own reckoning."""
    import json

    with open(os.path.join(
            ROOT, "chipbench/configs/qwen3next-80b-a3b-train.json")) as f:
        c = json.load(f)
    table = table_from_config(
        dict(c, num_experts=c["num_experts_published"]), n_layers=4,
        experts_held=(0, 32))
    assert [r.mixer for r in table.layers] == ["gdn"] * 3 + ["attention"]
    assert table.layers[0].gdn == GDNSpec(16, 32, 128, 128, 4, 64)
    att = table.layers[3]
    assert (att.n_heads, att.n_kv_heads, att.d_head, att.rotary_dim) == (
        16, 2, 256, 64)
    for row in table.layers:
        z = row.experts
        assert (z.n_experts, z.top_k, z.d_expert, z.d_shared, z.router,
                z.experts_held) == (512, 10, 512, 512, "softmax", (0, 32))
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table)
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert count == c["reckoning"]["total"] == 625_667_136
    assert count == weights_qwen3next.n_params(c)
    r = c["reckoning"]
    assert r["period"] == 3 * r["gdn_layer"] + r["attention_layer"]
    assert r["gdn_layer"] == (r["gdn_mixer"] + r["router_shared_gate"]
                              + 32 * r["routed_expert"] + r["layer_norms"])


@pytest.mark.parametrize("key,value,needle", [
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("tie_word_embeddings", True, "tied"),
])
def test_table_from_config_refuses_by_key(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_from_config(dict(config(), **{key: value}))


@pytest.mark.parametrize("kw,needle", [
    (dict(mixer="gdn"), "GDNSpec"),
    (dict(mixer="mamba2", rotary_dim=4), "SSMSpec"),
    (dict(mixer="none", ffn="gelu", qk_norm=True), "attention row's"),
    (dict(rotary_dim=3, d_head=16), "rotary_dim"),
    (dict(rotary_dim=32, d_head=16), "rotary_dim"),
])
def test_a_row_states_what_it_has(kw, needle):
    with pytest.raises(ValueError, match=needle):
        LayerSpec(**kw)


def test_specs_and_tables_refuse_what_does_not_fit():
    with pytest.raises(ValueError, match="n_k_heads"):
        GDNSpec(n_k_heads=3, n_v_heads=4, d_k=8, d_v=8)
    with pytest.raises(ValueError, match="shared_gate"):
        ExpertsSpec(n_experts=8, top_k=2, d_expert=8, d_shared=0,
                    shared_gate=True)
    with pytest.raises(ValueError, match="d_router"):
        ExpertsSpec(n_experts=8, top_k=2, d_expert=8, d_shared=0,
                    router="softmax", d_router=4)
    # a rotating attention row says so in its table; a table of rotary
    # positions may hold an attention row now (it rotates by its own spec)
    row = LayerSpec(rotary_dim=4, d_head=16)
    with pytest.raises(ValueError, match="rotary"):
        BlockTable(layers=(row,), positions="none")
    assert BlockTable(layers=(row, LayerSpec()), positions="rotary")


# ------------------------------------------- the model against the reference

def both_sides(held):
    """Logits, loss and gradients of the program (float32, ``highest``)
    and of the reference on one seeded tree."""
    c = config(held=held)
    params = weights_qwen3next.make(c, 2**31 + 11)
    toks = tokens(1, 2, 41)
    x, y = toks[:, :-1], toks[:, 1:]
    lm = model(c, dtype=jnp.float32, remat=True)

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
def some_held():
    return both_sides((2, 4))


@pytest.fixture(params=["all_held", "some_held"])
def sides(request):
    return request.getfixturevalue(request.param)


def test_program_logits_and_loss_match_the_reference(sides):
    # Both sides are float32 at ``highest``: what is left is the order of
    # sums (the chunked rule against the recurrence, sorted row groups
    # against a dense masked sum), 1e-6 of the largest logit here.
    (logits, loss, _), (ref_logits, ref_loss, _) = sides
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def _leaves():
    return [weights.leaf_name(p) for p in sorted(
        weights_qwen3next.shapes(config()))]


@pytest.mark.parametrize("leaf", _leaves())
def test_program_gradient_matches_the_reference(sides, leaf):
    # rtol 1e-3 with an absolute floor of 2e-5 of the leaf's largest
    # entry, as the other families' tests: the float32 reorderings above
    # read 1e-6 to 5e-6 of a leaf's largest entry here (a decay's A_log
    # or dt_bias, 32 numbers summed over every token, the largest).
    (_, _, grads), (_, _, ref_grads) = sides
    got = weights.flatten(grads)[tuple(leaf.split("/"))]
    want = weights.flatten(ref_grads)[tuple(leaf.split("/"))]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-5 * scale)


def test_the_seeded_tree_is_the_programs_tree():
    """Names and shapes of ``weights_qwen3next`` against the program's own
    ``init`` (the reference reads the tree by these names)."""
    c = config(held=(2, 4))
    shapes = jax.eval_shape(
        lambda: model(c).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        weights_qwen3next.shapes(c))
    assert not any("router_bias" in "/".join(p)
                   for p in weights_qwen3next.shapes(c))


def test_the_programs_choices_are_the_references():
    c = config()
    params = weights_qwen3next.make(c, 2**31 + 11)
    x = tokens(1, 2, 41)[:, :-1]
    lm = model(c, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, seen = lm.apply({"params": params}, x, mutable=["intermediates"])
        want = reference.chosen_experts(params, x, c)
    assert sorted(want) == [f"layer_{i}" for i in range(4)]
    for name, mask in want.items():
        chosen = seen["intermediates"][name]["ExpertLayer_0"]["chosen"][0]
        assert chosen.shape == (2 * 40, 3)
        got = np.zeros(mask.shape, bool).reshape(-1, 8)
        np.put_along_axis(got, np.asarray(chosen), True, axis=-1)
        np.testing.assert_array_equal(got.reshape(mask.shape), mask)


def test_remat_on_and_off_give_the_same_gradients():
    """In float32 the rematerialised model (the layers under the one
    policy, the delta rule's head groups under their own checkpoint)
    recomputes what the plain one kept: the same numbers, to the order of
    a recomputed sum."""
    c = config(held=(2, 4))
    params = weights_qwen3next.make(c, 5)
    toks = tokens(1, 2, 41)
    x, y = toks[:, :-1], toks[:, 1:]

    def grads(remat):
        lm = model(c, dtype=jnp.float32, remat=remat)

        def loss(p):
            z = lm.apply({"params": p}, x)
            picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
            return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)

        with jax.default_matmul_precision("highest"):
            return weights.flatten(jax.jit(jax.grad(loss))(params))

    kept, plain = grads(True), grads(False)
    for path, want in plain.items():
        np.testing.assert_allclose(
            kept[path], want, rtol=1e-5,
            atol=1e-6 * float(jnp.max(jnp.abs(want))),
            err_msg=weights.leaf_name(path))


# --------------------------------------------- the share and the whole layer

def test_four_shares_add_up_to_the_uncut_layer():
    """What ties one rank's share to the model: at 16 experts the four
    ranks' layers, four experts each, with the shared expert counted once,
    add up to the reference's layer with all sixteen."""
    whole = config(held=(0, 16), n_layer=1, num_experts_published=16)
    params = weights_qwen3next.make(whole, 2**31 + 5)["layer_0"]
    e = params["ExpertLayer_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, D_MODEL))
    stacks = ("experts_gate", "experts_up", "experts_down")
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.layer(row, params, whole, "float32")
                          for row in x])
        base, total = None, 0.0
        for first in (0, 4, 8, 12):
            c = config(held=(first, 4), n_layer=1, num_experts_published=16)
            row = table_of(c).layers[0]
            share = dict(e, **{k: e[k][first:first + 4] for k in stacks})
            out = Block(D_MODEL, row, jnp.float32).apply(
                {"params": dict(params, ExpertLayer_0=share)}, x)
            if base is None:    # x + mixer + the shared expert, no expert
                hollow = dict(e, **{k: jnp.zeros_like(share[k])
                                    for k in stacks})
                base = Block(D_MODEL, row, jnp.float32).apply(
                    {"params": dict(params, ExpertLayer_0=hollow)}, x)
            total = total + out - base
    np.testing.assert_allclose(total + base, want, rtol=2e-4, atol=2e-5)


def test_a_share_is_the_references_share():
    c = config(held=(3, 4), n_layer=1)
    params = weights_qwen3next.make(c, 2**31 + 6)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, D_MODEL))
    with jax.default_matmul_precision("highest"):
        got = Block(D_MODEL, table_of(c).layers[0], jnp.float32).apply(
            {"params": params}, x)
        want = reference.layer(x[0], params, c, "float32")
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_a_stage_cut_of_two_periods_is_the_references_first_period():
    """``n_layers`` cuts a pipeline stage: the first four rows of the
    eight-layer table, fed the same tree, give what the reference's first
    period gives, and the second stage's rows go on from there."""
    c8 = config(n_layer=8)
    params = weights_qwen3next.make(c8, 2**31 + 7)
    x = tokens(2, 2, 24)
    first = {k: v for k, v in params.items()
             if not k.startswith("layer_") or int(k.split("_")[1]) < 4}
    lm4 = model(config(n_layer=4), dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = lm4.apply({"params": first}, x)
        want = reference.logits(first, reference.layers(
            first, reference.embed(first, x), c8), c8)
        whole = model(c8, dtype=jnp.float32).apply({"params": params}, x)
        ref_whole = reference.logits(params, reference.layers(
            params, reference.embed(params, x), c8), c8)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(whole, ref_whole, rtol=2e-4, atol=2e-5)
    assert [r.mixer for r in table_of(c8).layers[4:]] == [
        "gdn", "gdn", "gdn", "attention"]


# ----------------------------------------- the chunked rule and its solve

def delta_inputs(seed, b=2, S=100, hk=2, hv=4, dk=16, dv=8, decay="mixed",
                 write="mixed"):
    """Seeded operands of the rule; ``decay`` / ``write`` push ``e^g`` and
    ``beta`` to an end of (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = reference.l2_norm(jax.random.normal(ks[0], (b, S, hk, dk))) / 4
    k = reference.l2_norm(jax.random.normal(ks[1], (b, S, hk, dk)))
    v = jax.random.normal(ks[2], (b, S, hv, dv))
    u = jax.random.normal(ks[3], (b, S, hv))
    g = {"mixed": -jnp.exp(2 * u - 2),
         "near_one": -1e-4 * jnp.exp(u),          # e^g in (0.999, 1)
         "near_zero": -8.0 - jnp.exp(u)}[decay]   # e^g under 3.4e-4
    w = jax.random.normal(ks[4], (b, S, hv))
    beta = jax.nn.sigmoid({"mixed": 3 * w, "near_one": 9 + w,
                           "near_zero": -9 + w}[write])
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    return jnp.stack([reference.delta_rule(
        jnp.repeat(q[i], rep, 1), jnp.repeat(k[i], rep, 1), v[i], g[i],
        beta[i])[0] for i in range(q.shape[0])])


@pytest.mark.parametrize("decay,write", [
    ("mixed", "mixed"), ("near_one", "near_one"), ("near_zero", "mixed"),
    ("near_one", "near_zero"), ("mixed", "near_one")])
@pytest.mark.parametrize("chunk", [4, 16, 64, 128])
def test_chunked_rule_is_the_recurrence(chunk, decay, write):
    """Forward and every gradient, float32 on both sides: what differs is
    the order of sums, and the solve's conditioning where beta is near 1
    and the decay near 1 (``I + A`` then has entries of the keys' own
    products): 1e-5 of the largest entry holds with room (read: 2e-6),
    over a floor of 1e-7 for a gradient that is itself rounding (a decay
    near zero passes next to nothing back to ``g``)."""
    args = delta_inputs(3, decay=decay, write=write)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        got = gated_delta.gated_delta_rule(*args, chunk=chunk)
        want_g = jax.grad(
            lambda *a: jnp.sum(recurrence(*a) * w), argnums=range(5))(*args)
        got_g = jax.grad(
            lambda *a: jnp.sum(gated_delta.gated_delta_rule(
                *a, chunk=chunk) * w), argnums=range(5))(*args)
    for a, b, name in zip((got, *got_g), (want, *want_g),
                          "o q k v g beta".split()):
        np.testing.assert_allclose(
            a, b, rtol=1e-4,
            atol=1e-7 + 1e-5 * float(jnp.max(jnp.abs(b))), err_msg=name)


def rule_and_gradients(rule, args, w):
    """``o`` and the gradients of ``sum(o w)`` by the five operands."""
    def loss(*a):
        o = rule(*a)
        return jnp.sum(o.astype(jnp.float32) * w), o

    with jax.default_matmul_precision("highest"):
        (_, o), grads = jax.value_and_grad(
            loss, argnums=range(5), has_aux=True)(*args)
    return (o, *grads)


def assert_close_by_operand(got, want, rtol, share, floor=0.0):
    """``o`` and the five gradients, each within ``rtol`` over ``floor``
    + ``share`` of the wanted one's largest entry."""
    for a, b, name in zip(got, want, "o q k v g beta".split()):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), b, rtol=rtol,
            atol=floor + share * float(jnp.max(jnp.abs(b))), err_msg=name)


@pytest.mark.parametrize("hk,hv", [(2, 2), (2, 4), (1, 3)])
@pytest.mark.parametrize("S,chunk,tokens", [
    (96, 8, 32),        # three tiles of four chunks
    (92, 8, 16),        # a ragged last chunk: six tiles of two, padded
    (48, 16, 16)])      # a chunk a tile
def test_state_and_cotangent_cross_a_tiles_edge(monkeypatch, S, chunk,
                                                tokens, hk, hv):
    """A grid step holds several chunks and a sequence several tiles: the
    state in forward, its cotangent in backward, are carried in VMEM from
    tile to tile; ``dq`` and ``dk`` sum over the ``hv / hk`` value heads
    of a key head inside the kernel.  Against the recurrence, float32, at
    the tolerances of the whole-sequence cases."""
    monkeypatch.setattr(gated_delta, "_GDN_TOKENS", tokens)
    jax.clear_caches()
    assert gated_delta.gdn_tiles(S, chunk, hk, hv, 16, 8, jnp.float32)[:2] \
        == (tokens, hv // hk)
    args = delta_inputs(5, b=1, S=S, hk=hk, hv=hv)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = rule_and_gradients(
        lambda *a: gated_delta.gated_delta_rule(*a, chunk=chunk), args, w)
    want = rule_and_gradients(recurrence, args, w)
    jax.clear_caches()
    assert_close_by_operand(got, want, rtol=1e-4, share=1e-5, floor=1e-7)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("hk,hv", [(2, 2), (2, 4)])
def test_bfloat16_rule_against_the_float32_recurrence(chunk, hk, hv):
    """The cell's precision — ``q``, ``k``, ``v`` and the products'
    operands rounded, the decays, the solve and the state float32 —
    against the recurrence on the same (rounded) operands in float32: a
    few roundings of the largest term, the ``ssd`` tests' tolerances."""
    args = delta_inputs(6, hk=hk, hv=hv)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    same = tuple(a.astype(jnp.float32) for a in low)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = rule_and_gradients(
        lambda *a: gated_delta.gated_delta_rule(*a, chunk=chunk), low, w)
    want = rule_and_gradients(recurrence, same, w)
    assert got[1].dtype == jnp.bfloat16 and got[4].dtype == jnp.float32
    assert_close_by_operand(got, want, rtol=2e-2, share=2e-2)


@pytest.mark.parametrize("n,side", [(16, 1), (64, 2), (64, 1), (24, 2),
                                    (5, 3)])
def test_the_kernels_solve_against_a_triangular_solve(n, side):
    """``(I + A_c^T)^-1`` as the kernels make it — substitution a column a
    step from the last, ``side`` chunks side by side on the lanes — in a
    kernel of its own (interpret mode), at the tolerance the XLA form's
    solve was held to."""
    from jax.experimental import pallas as pl

    a = 0.2 * jnp.triu(jax.random.normal(
        jax.random.PRNGKey(n), (side, n, n)), 1)

    def kernel(a_ref, t_ref):
        t_ref[...] = gated_delta._unit_upper_inverse(a_ref, n, side)

    side_by_side = jnp.moveaxis(a, 0, 1).reshape(n, side * n)
    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(side_by_side.shape,
                                               jnp.float32),
        interpret=True)(side_by_side)
    got = jnp.moveaxis(got.reshape(n, side, n), 1, 0)
    want = jax.scipy.linalg.solve_triangular(
        jnp.eye(n) + a, jnp.broadcast_to(jnp.eye(n), a.shape),
        lower=False, unit_diagonal=True)
    # two substitutions in another order: 1e-5 of the largest entry
    np.testing.assert_allclose(
        got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_tiles_follow_the_shapes(monkeypatch):
    from chainermn_tpu.ops.flash_attention import VMEM_SCOPED_DEFAULT

    tiles = gated_delta.gdn_tiles
    # off the chip any shape runs (interpreted): whole chunks, the most
    # that divide the padded sequence within the cap
    assert tiles(100, 4, 2, 4, 16, 8, jnp.float32)[:2] == (100, 2)
    assert tiles(100, 16, 2, 4, 16, 8, jnp.float32)[:2] == (112, 2)
    assert tiles(100, 128, 2, 2, 16, 8, jnp.float32)[:2] == (100, 1)
    assert tiles(2048, 64, 1, 1, 16, 8, jnp.float32)[0] == 512
    with pytest.raises(ValueError, match="do not divide"):
        tiles(64, 16, 3, 4, 16, 8, jnp.float32)
    # on the chip: the cell's geometry (2 x 8192 tokens, 16 key and 32
    # value heads of 128, chunk 64, bfloat16) is eight chunks, two side
    # by side on the lanes, and both value heads of a key head a grid
    # step, inside the default VMEM
    monkeypatch.setattr(gated_delta, "default_interpret", lambda: False)
    tokens, heads, vmem = tiles(8192, 64, 16, 32, 128, 128, jnp.bfloat16)
    assert (tokens, heads) == (512, 2) and vmem <= VMEM_SCOPED_DEFAULT
    # float32 operands still fit; what cannot be tiled raises by the
    # rule, no other form takes over: channels that fill no register of
    # sublanes, chunks that fill no register of lanes side by side, a
    # sequence whose chunks do not pair up, a head too large to hold
    assert tiles(8192, 64, 16, 32, 128, 128, jnp.float32)[0] == 512
    assert tiles(8192, 128, 16, 32, 64, 256, jnp.bfloat16)[0] == 256
    with pytest.raises(ValueError, match=r"whole\s+registers"):
        tiles(8192, 64, 16, 32, 72, 128, jnp.bfloat16)
    with pytest.raises(ValueError, match=r"whole\s+registers"):
        tiles(8192, 60, 16, 32, 128, 128, jnp.bfloat16)
    with pytest.raises(ValueError, match="no tile of whole chunks"):
        tiles(8192, 16, 16, 32, 128, 128, jnp.bfloat16)
    with pytest.raises(ValueError, match="no tile of whole chunks"):
        tiles(64 * 11, 64, 16, 32, 128, 128, jnp.bfloat16)
    with pytest.raises(ValueError, match="no tile of whole chunks"):
        tiles(8192, 64, 4, 32, 512, 512, jnp.bfloat16)


def test_rule_refuses_operands_that_do_not_fit():
    q, k, v, g, beta = delta_inputs(1, S=8)
    with pytest.raises(ValueError, match="do not fit"):
        gated_delta.gated_delta_rule(q, k[:, :4], v, g, beta)
    with pytest.raises(ValueError, match="do not fit"):
        gated_delta.gated_delta_rule(q, k, v[:, :, :3], g[..., :3],
                                     beta[..., :3])


# ----------------------------------------------------------- mixer, attention

def mixer_and_params(seed=7):
    c = config(n_layer=1)
    params = weights_qwen3next.make(c, seed)["layer_0"]
    return (GatedDeltaNetMixer(D_MODEL, table_of(c).layers[0].gdn,
                               dtype=jnp.float32),
            params["GatedDeltaNetMixer_0"], c)


@pytest.mark.parametrize("t", [0, 5, 11, 23])
def test_gdn_mixer_is_causal(t):
    mixer, p, _ = mixer_and_params()
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, D_MODEL))
    other = h.at[:, t + 1:].set(jax.random.normal(
        jax.random.PRNGKey(2), (1, 24 - t - 1, D_MODEL)))
    a = mixer.apply({"params": p}, h)
    b = mixer.apply({"params": p}, other)
    np.testing.assert_allclose(a[:, :t + 1], b[:, :t + 1], atol=1e-6)


def test_gdn_mixer_is_the_references():
    mixer, p, c = mixer_and_params()
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 40, D_MODEL))
    with jax.default_matmul_precision("highest"):
        got = mixer.apply({"params": p}, h)
        want = jnp.stack([reference.gated_delta_net(row, p, c, "float32")
                          for row in h])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def attention_and_params(seed=8, **kw):
    c = config()
    row = table_of(c).layers[3]
    p = weights_qwen3next.make(c, seed)["layer_3"]["MultiHeadAttention_0"]
    fields = dict(n_kv_heads=row.n_kv_heads, d_head=row.d_head,
                  rotary_dim=row.rotary_dim, rope_theta=row.rope_theta,
                  qk_norm=row.norm, norm_eps=row.norm_eps, out_gate=True)
    fields.update(kw)
    return MultiHeadAttention(D_MODEL, row.n_heads, jnp.float32,
                              **fields), p, c


def test_gated_attention_row_is_the_references():
    att, p, c = attention_and_params()
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D_MODEL))
    with jax.default_matmul_precision("highest"):
        got = att.apply({"params": p}, h, h, causal_mask(24))
        want = jnp.stack([reference.attention(row, p, c, "float32")
                          for row in h])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_each_attention_option_changes_the_row():
    """The rotation, the QK-norm and the gate each do something: with one
    switched off the row is another function (and with the gate off the
    query matrix has half its columns)."""
    att, p, _ = attention_and_params()
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, D_MODEL))
    mask = causal_mask(24)
    full = att.apply({"params": p}, h, h, mask)
    no_rope, _, _ = attention_and_params(rotary_dim=0)
    assert not np.allclose(no_rope.apply({"params": p}, h, h, mask), full,
                           atol=1e-4)
    no_norm, _, _ = attention_and_params(qk_norm=None)
    bare = {k: v for k, v in p.items() if k not in ("q_norm", "k_norm")}
    assert not np.allclose(no_norm.apply({"params": bare}, h, h, mask),
                           full, atol=1e-4)
    no_gate, _, _ = attention_and_params(out_gate=False)
    ungated = dict(p, query={"kernel": p["query"]["kernel"][..., :16]})
    assert not np.allclose(no_gate.apply({"params": ungated}, h, h, mask),
                           full, atol=1e-4)


def test_the_caches_refuse_a_row_they_cannot_hold():
    att, p, _ = attention_and_params(decode=True, cache_len=8)
    h = jnp.zeros((1, 1, D_MODEL))
    with pytest.raises(ValueError, match="KV caches take no positions"):
        att.apply({"params": p}, h, h, mutable=["cache"])
    c = config()
    with pytest.raises(ValueError, match="gdn layer keeps no recurrent"):
        Block(D_MODEL, table_of(c).layers[0], jnp.float32,
              decode=True).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 1, D_MODEL)))
    with pytest.raises(ValueError, match="rotary positions"):
        model(c).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                      position_offset=4)


def test_zero_centred_norm_scales_by_one_plus_w():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16)) * 5
    norm = ZeroCentredRMSNorm(epsilon=1e-6, dtype=jnp.float32)
    p = norm.init(jax.random.PRNGKey(0), x)
    assert not np.any(p["params"]["scale"])        # w starts at zero
    unit = norm.apply(p, x)
    np.testing.assert_allclose(jnp.mean(unit ** 2, axis=-1), 1.0, rtol=1e-5)
    w = jnp.linspace(-0.5, 0.5, 16)
    np.testing.assert_allclose(
        norm.apply({"params": {"scale": w}}, x), unit * (1 + w), rtol=1e-6)
    np.testing.assert_allclose(
        reference.rms_norm(x, w, 1e-6), unit * (1 + w), rtol=1e-6)


def test_conv_without_a_bias_is_the_conv_with_zeros():
    from chainermn_tpu.ops.ssd import causal_conv_silu

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 24))
    k = jax.random.uniform(jax.random.PRNGKey(1), (4, 24)) - 0.5
    np.testing.assert_array_equal(
        causal_conv_silu(x, k), causal_conv_silu(x, k, jnp.zeros((24,))))


# ------------------------------------------------------------- the router

def hand_router(h, w, top_k):
    """softmax, the top_k largest one after another with the lowest index
    on a tie, weights over the chosen ones' sum: numpy, float64."""
    logits = np.asarray(h, np.float64) @ np.asarray(w, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.argsort(-p, axis=-1, kind="stable")[:, :top_k]
    weight = np.take_along_axis(p, chosen, axis=-1)
    return chosen, weight / weight.sum(-1, keepdims=True)


def test_softmax_router_against_a_hand_written_one_with_ties():
    h = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    w = w.at[:, 5].set(w[:, 2]).at[:, 7].set(w[:, 2])   # three-way ties
    chosen, weight = moe_dropless.route_softmax(h, w, top_k=3)
    want_c, want_w = hand_router(h, w, 3)
    np.testing.assert_array_equal(chosen, want_c)
    np.testing.assert_allclose(weight, want_w, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(weight, axis=-1), 1.0, rtol=1e-6)
    assert chosen.dtype == jnp.int32 and weight.dtype == jnp.float32
    mask, ref_w = reference.router(h, {"router": w}, {
        "num_experts_per_tok": 3})
    got = np.zeros(mask.shape, bool)
    np.put_along_axis(got, np.asarray(chosen), True, axis=-1)
    np.testing.assert_array_equal(got, mask)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(ref_w), want_c, axis=-1), want_w,
        rtol=1e-5)
    _, scaled = moe_dropless.route_softmax(h, w, top_k=3, scaling=2.5)
    np.testing.assert_allclose(scaled, 2.5 * weight, rtol=1e-6)


def test_router_reads_bfloat16_activations_in_float32():
    h = jax.random.normal(jax.random.PRNGKey(0), (32, 16)).astype(
        jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    chosen, weight = moe_dropless.route_softmax(h, w, top_k=2)
    want_c, want_w = hand_router(h.astype(jnp.float32), w, 2)
    np.testing.assert_array_equal(chosen, want_c)
    np.testing.assert_allclose(weight, want_w, rtol=1e-5)


def test_the_shared_expert_is_gated_and_of_the_experts_form():
    c = config(n_layer=1)
    spec = table_of(c).layers[0].experts
    e = weights_qwen3next.make(c, 3)["layer_0"]["ExpertLayer_0"]
    assert "router_bias" not in e
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 12, D_MODEL))
    hollow = dict(e, **{k: jnp.zeros_like(e[k]) for k in (
        "experts_gate", "experts_up", "experts_down")})
    with jax.default_matmul_precision("highest"):
        got = ExpertLayer(D_MODEL, spec, jnp.float32).apply(
            {"params": hollow}, h)
        want = reference.shared_expert(h[0], e, "float32")
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=1e-7)


# ----------------------------------- the older families, to the bit

#: ``float.hex`` of the fused-CE loss and of the whole gradient's norm of
#: one bfloat16 rematerialised step on the seeded tiny trees.  granite's
#: and nemotron's are ``tests/test_zaya1.py``'s (read at PR 32's parent);
#: zaya's was read on the tree PR 36 started from (commit f4ab25f): the
#: new columns of the table, the norm classes and the attention row's and
#: expert layer's options leave these families' programs alone.
ZAYA = ("0x1.2413be0000000p+2", "0x1.6249220000000p-1")


@pytest.mark.parametrize("family", ["gpt2", "granite", "nemotron", "zaya"])
def test_the_four_older_families_are_unchanged_to_the_bit(family):
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    if family != "zaya":
        from tests.test_zaya1 import (
            test_the_older_trees_and_losses_are_unchanged_to_the_bit as older,
        )

        return older(family)
    from chipbench import weights_zaya
    from tests.test_zaya1 import config as zaya
    from tests.test_zaya1 import table_of as zaya_table

    c = zaya(held=(4, 4))
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=zaya_table(c), remat=True)
    p = weights_zaya.make(c, 2**31 + 5)
    made = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {k: v.shape for k, v in weights.flatten(made).items()} == (
        weights_zaya.shapes(c))
    tok = jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, lm.vocab)

    def loss(p):
        h = lm.apply({"params": p}, tok[:, :-1], return_hidden=True)
        return fused_cross_entropy(h, p["embed"]["embedding"], tok[:, 1:],
                                   chunk=64)

    value, grads = jax.jit(jax.value_and_grad(loss))(p)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    assert (float(value).hex(), float(norm).hex()) == ZAYA


# ------------------------------------------- what the layer tells telemetry

def test_the_layers_publish_their_geometry_when_someone_listens(tmp_path):
    import json

    from chainermn_tpu.observability import reporter, spans, step_log

    for name in ("gdn-mixer", "gdn-scan", "attn-rope"):
        assert spans.is_scope(name)
    c = config(held=(2, 4), n_layer=1)
    x = jnp.zeros((2, 8, D_MODEL))
    layer = Block(D_MODEL, table_of(c).layers[0], jnp.float32)
    rep, path = reporter.Reporter(), str(tmp_path / "steps.jsonl")
    with reporter.scope(rep), step_log.recording(path):
        layer.init(jax.random.PRNGKey(0), x, causal_mask(8))
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    assert gauges["gdn/key_heads"] == 2 and gauges["gdn/value_heads"] == 4
    assert gauges["gdn/d_k"] == 8 and gauges["gdn/d_v"] == 8
    assert gauges["gdn/chunk"] == 8 and gauges["gdn/chunks"] == 1
    assert gauges["gdn/tokens_a_step"] == 8 and gauges["gdn/heads_a_step"] == 2
    assert gauges["gdn/grid_steps"] == 2 * 2 and gauges["gdn/vmem_bytes"] > 0
    assert gauges["gdn/kernel"] == 1 and "gdn/heads_a_group" not in gauges
    assert gauges["ssm_conv/channels"] == 64 and gauges["ssm_conv/taps"] == 4
    assert gauges["moe/experts"] == 8 and gauges["moe/experts_held"] == 4
    assert gauges["moe/top_k"] == 3 and gauges["moe/pair_rows"] == 48
    assert gauges["moe/softmax"] == 1 and gauges["moe/swiglu"] == 1
    rows = {r["event"]: r for r in map(json.loads, open(path))}
    assert rows["gdn_geometry"]["form"] == "kernel"
    assert rows["moe_geometry"]["router"] == "softmax"


def test_the_layers_ops_carry_their_scopes():
    """Every part of both rows lowers under its scope, forward and
    backward: what the benchmark's ``qnext.*`` readers join the trace
    to."""
    c = config()
    params = weights_qwen3next.make(c, 3)
    x = jnp.ones((1, 16, D_MODEL))

    def paths_of(i):
        layer = Block(D_MODEL, table_of(c).layers[i], jnp.float32)

        def loss(p):
            return jnp.sum(layer.apply({"params": p}, x, causal_mask(16)))

        text = jax.jit(jax.grad(loss)).lower(
            params[f"layer_{i}"]).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    def both_passes(paths, needle):
        # (a kernel's jitted wrapper stands between the mixer and its scope)
        inside = [p for p in paths
                  if all(part in p for part in needle.split("*"))]
        assert any("transpose(jvp(" in p for p in inside), needle
        assert any("transpose(jvp(" not in p for p in inside), needle

    gdn = paths_of(0)
    for needle in ("/gdn-mixer/*/gdn-scan/", "/gdn-mixer/mixer-gate/",
                   "/gdn-mixer/mixer-proj/", "/moe-layer/moe-route/",
                   "/moe-layer/moe-dispatch/", "/moe-layer/moe-shared/"):
        both_passes(gdn, needle)
    assert any("ssm-conv" in p and "/gdn-mixer/" in p for p in gdn)
    assert any("/moe-layer/moe-shared/shared_gate/" in p for p in gdn)
    att = paths_of(3)
    for needle in ("/attn-mixer/MultiHeadAttention_0/attn-rope/",
                   "/attn-mixer/MultiHeadAttention_0/mixer-gate/",
                   "/attn-mixer/MultiHeadAttention_0/mixer-proj/"):
        both_passes(att, needle)
    # the QK-norms do not read as the layers' pre-norm
    assert not any("/attn-rope/q_norm/" in p or "/attn-rope/k_norm/" in p
                   for p in att)
    assert not any("gdn-" in p for p in att)


def test_device_trace_reads_the_new_regions_without_a_special_case():
    from chainermn_tpu.observability import device_trace, spans

    c = config()
    params = weights_qwen3next.make(c, 3)
    lm = model(c, dtype=jnp.float32)
    x = tokens(0, 1, 16)

    def step(p):
        with spans.named_scope("fwd-bwd"):
            return jax.grad(lambda p: jnp.sum(
                lm.apply({"params": p}, x, return_hidden=True)))(p)

    table = device_trace.scope_table(jax.jit(step).lower(params).compile())
    regions = {device_trace.classify(path)[1] for path in table.values()}
    assert {"gdn-mixer", "gdn-scan", "ssm-conv", "attn-rope", "moe-shared",
            "moe-route"} <= regions
    owners = {device_trace.owner(path)[1] for path in table.values()}
    assert {"gdn-scan", "attn-rope", "mixer-gate", "mixer-proj",
            "attn-mixer"} <= owners


def test_the_serving_engine_names_the_mixer_it_refuses():
    from chainermn_tpu.serving.engine import InferenceEngine

    c = config()
    with pytest.raises(ValueError, match="gdn mixer's"):
        InferenceEngine(model(c, max_len=64), weights_qwen3next.make(c, 1))
