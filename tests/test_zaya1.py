"""The ``zaya`` rows of the block table — a compressed-convolutional-
attention mixer with partial rotary positions, a top-1 MLP router whose
state passes from layer to layer, gated experts as grouped matmuls, one
expert-parallel rank's share — against the plain reference the benchmark
compares with on the chip (``chipbench/refs/zaya1.py``: dense masked sums,
the convolutions as shifts, none of the program's code)."""

import os
import re
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
    CCASpec,
    ExpertsSpec,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    CCAMixer,
    TransformerLM,
    causal_mask,
    rotate_partial,
)
from chainermn_tpu.ops import grouped_matmul as gmm  # noqa: E402
from chainermn_tpu.parallel import moe_dropless  # noqa: E402
from chipbench import weights, weights_zaya  # noqa: E402
from chipbench.refs import zaya1 as reference  # noqa: E402

D_MODEL, VOCAB = 32, 96


def config(held=(0, 8), n_layer=3, **over):
    """A ``zaya`` config at toy widths, keys as published, plus the
    benchmark's own: the layers kept and the experts held."""
    c = {
        "model_type": "zaya", "attention_bias": False, "cca_time0": 2,
        "cca_time1": 2, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": D_MODEL, "layer_types": ["hybrid"] * 6,
        "lm_head_bias": False, "max_position_embeddings": 1024,
        "moe_intermediate_size": 24, "num_attention_heads": 4,
        "num_experts": held[1], "num_experts_published": 8,
        "experts_held_first": held[0], "num_experts_per_tok": 1,
        "num_hidden_layers": 6, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 16, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": VOCAB,
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

def test_the_published_keys_give_a_cca_and_an_expert_branch_a_layer():
    c = config()
    table = table_from_config(dict(c, num_experts=8))
    assert len(table.layers) == 6 and len(set(table.layers)) == 1
    row = table.layers[0]
    assert (row.mixer, row.norm, row.ffn, row.norm_eps) == (
        "cca", "rmsnorm", "experts", 1e-05)
    assert row.cca == CCASpec(n_heads=4, n_kv_heads=2, d_head=16, time0=2,
                              time1=2, rotary_dim=8, rope_theta=5e6)
    assert row.cca.conv_dim == 96
    assert row.experts == ExpertsSpec(
        n_experts=8, top_k=1, d_expert=24, d_shared=0, router="mlp_softmax",
        expert="swiglu", d_router=16)
    assert (table.positions, table.final_norm, table.tied_head) == (
        "rotary", "rmsnorm", True)
    assert table.d_router_state == 16
    cut = table_of(config(held=(4, 4), n_layer=2))
    assert len(cut.layers) == 2
    assert cut.layers[0].experts.experts_held == (4, 4)


@pytest.mark.parametrize("key,value,needle", [
    ("sliding_window", 4096, "sliding_window"),
    ("num_experts_per_tok", 2, "num_experts_per_tok"),
    ("layer_types", ["hybrid"] * 5 + ["hybrid_sliding"], "hybrid_sliding"),
    ("layer_types", ["hybrid"] * 5, "num_hidden_layers"),
    ("attention_bias", True, "attention_bias"),
    ("lm_head_bias", True, "lm_head_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("tie_word_embeddings", False, "untied"),
    ("partial_rotary_factor", 1.0, "partial_rotary_factor"),
    ("model_type", "zaya2", "granitemoehybrid"),
])
def test_table_from_config_refuses_by_key(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_from_config(dict(config(), **{key: value}))


@pytest.mark.parametrize("kw,needle", [
    (dict(router="mlp_softmax"), "d_router"),
    (dict(d_router=16), "d_router"),
    (dict(router="mlp_softmax", d_router=16, top_k=2), "one expert"),
    (dict(router="hash"), "router must be"),
    (dict(expert="geglu"), "expert one of"),
])
def test_an_experts_spec_states_what_it_has(kw, needle):
    with pytest.raises(ValueError, match=needle):
        ExpertsSpec(**dict(dict(n_experts=8, top_k=1, d_expert=24,
                                d_shared=0), **kw))


def test_a_cca_spec_states_what_it_has():
    with pytest.raises(ValueError, match="n_kv_heads"):
        CCASpec(n_heads=4, n_kv_heads=3, d_head=16)
    with pytest.raises(ValueError, match="rotary_dim"):
        CCASpec(n_heads=4, n_kv_heads=2, d_head=16, rotary_dim=7)
    with pytest.raises(ValueError, match="rotary_dim"):
        CCASpec(n_heads=4, n_kv_heads=2, d_head=16, rotary_dim=18)


# ------------------------------------------------------- program vs reference

def both_sides(held):
    """Logits, loss and gradients of the program (float32, ``highest``)
    and of the reference on one seeded tree."""
    c = config(held=held)
    params = weights_zaya.make(c, 2**31 + 11)
    toks = tokens(1, 2, 33)
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
                params, reference.embed(params, x), c)[0], c),
            *jax.value_and_grad(reference.loss_sum)(params, x, y, c))
    return got, want


@pytest.fixture(scope="module")
def all_held():
    return both_sides((0, 8))


@pytest.fixture(scope="module")
def half_held():
    return both_sides((4, 4))


@pytest.fixture(params=["all_held", "half_held"])
def sides(request):
    return request.getfixturevalue(request.param)


def test_program_logits_and_loss_match_the_reference(sides):
    (logits, loss, _), (ref_logits, ref_loss, _) = sides
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def _leaves():
    return [weights.leaf_name(p) for p in sorted(
        weights_zaya.shapes(config()))]


@pytest.mark.parametrize("leaf", _leaves())
def test_program_gradient_matches_the_reference(sides, leaf):
    (_, _, grads), (_, _, ref_grads) = sides
    got = weights.flatten(grads)[tuple(leaf.split("/"))]
    want = weights.flatten(ref_grads)[tuple(leaf.split("/"))]
    if leaf.endswith("router_bias") or leaf == (
            "layer_0/ExpertLayer_0/router_gamma"):
        # the bias chooses, and the first layer's gamma weighs a state of
        # zeros: neither gets a gradient
        assert not np.any(got) and not np.any(want)
        return
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-5 * scale)


def test_the_seeded_tree_is_the_programs_tree():
    """Names and shapes of ``weights_zaya`` against the program's own
    ``init`` (the reference reads the tree by these names)."""
    c = config(held=(4, 4))
    shapes = jax.eval_shape(
        lambda: model(c).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        weights_zaya.shapes(c))


def test_the_programs_choices_are_the_references(all_held):
    c = config()
    params = weights_zaya.make(c, 2**31 + 11)
    x = tokens(1, 2, 33)[:, :-1]
    lm = model(c, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, seen = lm.apply({"params": params}, x, mutable=["intermediates"])
        want = reference.chosen_experts(params, x, c)
        _, state = reference.layers(
            params, reference.embed(params, x), c)
    assert sorted(want) == ["layer_0", "layer_1", "layer_2"]
    for name, mask in want.items():
        chosen = seen["intermediates"][name]["ExpertLayer_0"]["chosen"][0]
        assert chosen.shape == (2 * 32, 1)
        got = np.zeros(mask.shape, bool).reshape(-1, 8)
        np.put_along_axis(got, np.asarray(chosen), True, axis=-1)
        np.testing.assert_array_equal(got.reshape(mask.shape), mask)
    # what the last layer hands on is there for the stage after
    np.testing.assert_allclose(
        seen["intermediates"]["router_state"][0], state, rtol=2e-4,
        atol=2e-5)


# --------------------------------------------- the share and the whole layer

def test_both_shares_add_up_to_the_uncut_layer():
    """What ties one rank's share to the model: the two ranks' layers,
    four experts each, add up to the reference's layer with all eight;
    both hand on the same router state, the whole layer's."""
    whole = config(n_layer=1)
    params = weights_zaya.make(whole, 2**31 + 5)["layer_0"]
    e = params["ExpertLayer_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, D_MODEL))
    r = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (2, 16, 16))
    mask = causal_mask(16)
    with jax.default_matmul_precision("highest"):
        want, want_r = zip(*(reference.layer(row, s, params, whole,
                                             "float32")
                             for row, s in zip(x, r)))
        after_mixer = None
        total = 0.0
        for first in (0, 4):
            row = table_of(config(held=(first, 4), n_layer=1)).layers[0]
            share = dict(e, **{k: e[k][first:first + 4] for k in (
                "experts_gate", "experts_up", "experts_down")})
            out, got_r = Block(D_MODEL, row, jnp.float32).apply(
                {"params": dict(params, ExpertLayer_0=share)}, x, mask, r)
            np.testing.assert_allclose(got_r, jnp.stack(want_r), rtol=2e-4,
                                       atol=2e-5)
            if after_mixer is None:    # x + CCA: a rank with no expert held
                hollow = dict(e, **{k: jnp.zeros_like(share[k]) for k in (
                    "experts_gate", "experts_up", "experts_down")})
                after_mixer, _ = Block(D_MODEL, row, jnp.float32).apply(
                    {"params": dict(params, ExpertLayer_0=hollow)}, x,
                    mask, r)
            total = total + out - after_mixer
    np.testing.assert_allclose(total + after_mixer, jnp.stack(want),
                               rtol=2e-4, atol=2e-5)


def test_a_share_is_the_references_share():
    c = config(held=(3, 4), n_layer=1)
    params = weights_zaya.make(c, 2**31 + 6)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, D_MODEL))
    r = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (1, 24, 16))
    with jax.default_matmul_precision("highest"):
        got, got_r = Block(D_MODEL, table_of(c).layers[0],
                           jnp.float32).apply(
            {"params": params}, x, causal_mask(24), r)
        want, want_r = reference.layer(x[0], r[0], params, c, "float32")
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_r[0], want_r, rtol=2e-4, atol=2e-5)


class Stage(nn.Module):
    """One pipeline stage: some of the table's layers on ``(x, r)``."""

    rows: tuple

    @nn.compact
    def __call__(self, x, r):
        mask = causal_mask(x.shape[1])
        for i, row in enumerate(self.rows):
            x, r = Block(D_MODEL, row, jnp.float32, name=f"layer_{i}")(
                x, mask, r)
        return x, r


def test_two_stages_chained_through_x_and_r_give_the_uncut_model():
    """The carry is the stage's input, not hidden state: stage 0 starts it
    from zeros, stage 1 from what stage 0 hands over, and the two give the
    reference's uncut four layers — through ``Block`` s and through two
    ``TransformerLM`` s (``inputs_embeds`` / ``router_state`` in, the
    ``router_state`` it sows out)."""
    c = config(n_layer=4)
    params = weights_zaya.make(c, 2**31 + 3)
    x = tokens(2, 2, 16)
    table = table_of(c)
    with jax.default_matmul_precision("highest"):
        start = reference.embed(params, x)
        want, want_r = reference.layers(params, start, c)
        stream, r = start, jnp.zeros(x.shape + (16,))
        for k in range(2):
            stream, r = Stage(table.layers[2 * k:2 * k + 2]).apply(
                {"params": {f"layer_{i}": params[f"layer_{2 * k + i}"]
                            for i in range(2)}}, stream, r)
        # a stage that forgot the carry is another model
        forgot, _ = Stage(table.layers[2:]).apply(
            {"params": {f"layer_{i}": params[f"layer_{2 + i}"]
                        for i in range(2)}},
            reference.layers({f"layer_{i}": params[f"layer_{i}"]
                              for i in range(2)}, start, c)[0],
            jnp.zeros(x.shape + (16,)))
    np.testing.assert_allclose(stream, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(r, want_r, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(forgot - want))) > 1e-4     # 5x atol

    half = model(config(n_layer=2), dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        h, r = None, None
        for k in range(2):
            stage = {"embed": params["embed"],
                     "final_norm": {"scale": jnp.ones(D_MODEL)},
                     **{f"layer_{i}": params[f"layer_{2 * k + i}"]
                        for i in range(2)}}
            hidden, seen = half.apply(
                {"params": stage}, x, return_hidden=True,
                inputs_embeds=None if k == 0 else h, router_state=r,
                mutable=["intermediates"])
            r = seen["intermediates"]["router_state"][0]
            # undo the stage's final norm (scale 1): the stream itself is
            # what a stage hands on; read it back from the reference
            h = reference.layers(
                {f"layer_{i}": params[f"layer_{i}"]
                 for i in range(2 * k + 2)}, start, c)[0]
            np.testing.assert_allclose(
                hidden, reference.rms_norm(h, 1.0, 1e-05), rtol=2e-4,
                atol=2e-5)
    np.testing.assert_allclose(r, want_r, rtol=2e-4, atol=2e-5)


def test_a_router_state_is_for_tables_that_keep_one():
    from tests.test_hybrid import config as granite

    g = granite()
    lm = TransformerLM(vocab=g["vocab_size"], d_model=g["hidden_size"],
                       table=table_from_config(g))
    with pytest.raises(ValueError, match="keep none"):
        lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                router_state=jnp.zeros((1, 8, 16)))
    with pytest.raises(ValueError, match="sharded or offset"):
        model(config()).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32),
                             position_offset=8)


# --------------------------------------------------------------- the mixer

def mixer_and_params(seed=7):
    c = config(n_layer=1)
    spec = table_of(c).layers[0].cca
    params = weights_zaya.make(c, seed)["layer_0"]["CCAMixer_0"]
    return CCAMixer(D_MODEL, spec, jnp.float32), params, c


@pytest.mark.parametrize("t", [0, 5, 11, 23])
def test_cca_is_causal(t):
    """Changing token t moves nothing before t — through both
    convolutions' taps, the value's shifted half and the attention — and
    does move t and, by the taps and the shift alone, t + 1."""
    mixer, params, _ = mixer_and_params()
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, D_MODEL))
    bumped = h.at[0, t].add(1.0)
    mask = causal_mask(24)
    with jax.default_matmul_precision("highest"):
        a = mixer.apply({"params": params}, h, mask)
        b = mixer.apply({"params": params}, bumped, mask)
    np.testing.assert_array_equal(a[0, :t], b[0, :t])
    assert float(jnp.max(jnp.abs(a[0, t] - b[0, t]))) > 1e-4
    if t + 1 < 24:
        assert float(jnp.max(jnp.abs(a[0, t + 1] - b[0, t + 1]))) > 1e-5


def test_cca_mixer_is_the_references():
    mixer, params, c = mixer_and_params()
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, D_MODEL))
    with jax.default_matmul_precision("highest"):
        got = mixer.apply({"params": params}, h, causal_mask(24))
        want = jnp.stack([reference.cca(row, params, c, "float32")
                          for row in h])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_rotary_touches_the_first_half_of_a_head_only():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 16))
    pos = jnp.arange(12)
    y = rotate_partial(x, pos, 8, 5e6)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(y[:, 0], x[:, 0])        # position 0
    assert float(jnp.max(jnp.abs(y[:, 1:, :, :8] - x[:, 1:, :, :8]))) > 0.1
    # a rotation: norms kept, and dimension i pairs with i + 4 at the
    # angle t x theta^(-2 i / 8)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    for i in range(4):
        angle = np.arange(12) * 5e6 ** (-2 * i / 8)
        want = (x[0, :, 1, i] * np.cos(angle)
                - x[0, :, 1, i + 4] * np.sin(angle))
        np.testing.assert_allclose(y[0, :, 1, i], want, rtol=1e-4,
                                   atol=1e-5)
    # the reference's own rotation is the same one
    c = config()
    np.testing.assert_allclose(
        rotate_partial(x[:1], pos, 8, 5e6)[0], reference.rotate(x[0], c),
        rtol=1e-6, atol=1e-6)
    # q.k depends on the distance alone
    q = jnp.broadcast_to(x[:1, :1], (1, 12, 3, 16))
    r = rotate_partial(q, pos, 8, 5e6)
    dots = jnp.einsum("bshd,bthd->hst", r, r)
    np.testing.assert_allclose(dots[:, 2, 5], dots[:, 7, 10], rtol=1e-4)


# ------------------------------------------------------------------ the router

def hand_router(h, state, p, bias, eps):
    """Equation 5 in float64 numpy, the argmax by a loop: the lowest
    index among equals."""
    from math import erf, sqrt

    gelu = np.vectorize(lambda v: 0.5 * v * (1.0 + erf(v / sqrt(2.0))))
    r = h @ p["down"] + p["gamma"] * state
    x = r / np.sqrt(np.mean(r * r, axis=-1, keepdims=True) + eps)
    x = x * p["norm"]
    z = gelu(gelu(x @ p["w1"]) @ p["w2"]) @ p["w3"]
    z = z - z.max(-1, keepdims=True)
    prob = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    chosen = []
    for row in (prob.astype(np.float32) + bias.astype(np.float32)):
        best = 0
        for e in range(1, len(row)):
            if row[e] > row[best]:
                best = e
        chosen.append(best)
    return np.array(chosen), prob, r


def test_top1_router_against_a_hand_written_one_with_ties():
    """Probabilities that tie exactly (equal columns of ``w3``), the tie
    broken one way by the index and the other way by the balancing bias;
    the weight is the bare probability and never sees the bias; the state
    handed on is ``h W_r + gamma * r``."""
    rng = np.random.default_rng(0)
    T, d, r, E = 12, 6, 4, 8
    p = {"down": rng.normal(size=(d, r)), "gamma": rng.normal(size=r),
         "norm": 1 + 0.1 * rng.normal(size=r),
         "w1": rng.normal(size=(r, r)), "w2": rng.normal(size=(r, r)),
         "w3": rng.normal(size=(r, E))}
    p["w3"][:, 5] = p["w3"][:, 2]          # experts 2 and 5 always tie
    p["w3"][:, 7] = p["w3"][:, 2]          # and 7 with them
    p = {k: v.astype(np.float32) for k, v in p.items()}
    h = rng.normal(size=(T, d)).astype(np.float32)
    state = rng.normal(size=(T, r)).astype(np.float32)
    tipping = np.zeros(E, np.float32)
    tipping[5] = 1e-4
    chosen_by = {}
    for name, b in (("none", np.zeros(E, np.float32)), ("tip", tipping)):
        chosen, weight, handed = moe_dropless.route_mlp_softmax(
            jnp.asarray(h), jnp.asarray(state),
            {k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(b),
            eps=1e-5)
        want, prob, want_r = hand_router(
            *(a.astype(np.float64) for a in (h, state)),
            {k: v.astype(np.float64) for k, v in p.items()}, b, 1e-5)
        assert chosen.shape == (T, 1) and chosen.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(chosen)[:, 0], want)
        np.testing.assert_allclose(
            weight[:, 0], prob[np.arange(T), want], rtol=2e-5)
        np.testing.assert_allclose(handed, want_r, rtol=1e-5, atol=1e-5)
        chosen_by[name] = np.asarray(chosen)[:, 0]
    tied = np.isin(chosen_by["none"], (2, 5, 7))
    assert tied.any()
    assert set(chosen_by["none"][tied]) == {2}    # the index breaks the tie
    assert set(chosen_by["tip"][tied]) == {5}     # the bias breaks it first
    # ... and moves the choice, not the weight: p[5] == p[2]
    w_none = moe_dropless.route_mlp_softmax(
        jnp.asarray(h), jnp.asarray(state),
        {k: jnp.asarray(v) for k, v in p.items()}, jnp.zeros(E),
        eps=1e-5)[1]
    w_tip = moe_dropless.route_mlp_softmax(
        jnp.asarray(h), jnp.asarray(state),
        {k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(tipping),
        eps=1e-5)[1]
    np.testing.assert_allclose(w_none[tied], w_tip[tied], rtol=1e-6)


def test_the_bias_gets_no_gradient_and_the_weight_is_not_renormalised():
    c = config(n_layer=1)
    params = weights_zaya.make(c, 9)["layer_0"]["ExpertLayer_0"]
    h = jax.random.normal(jax.random.PRNGKey(0), (10, D_MODEL))
    p = {k: params[f"router_{k}"] for k in (
        "down", "gamma", "norm", "w1", "w2", "w3")}

    def total(bias, p):
        return jnp.sum(moe_dropless.route_mlp_softmax(
            h, jnp.ones((10, 16)), p, bias, eps=1e-5)[1])

    g_bias, g_p = jax.grad(total, argnums=(0, 1))(params["router_bias"], p)
    assert not np.any(g_bias)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in g_p.values())
    weight = moe_dropless.route_mlp_softmax(
        h, jnp.ones((10, 16)), p, params["router_bias"], eps=1e-5)[1]
    assert float(jnp.max(weight)) < 0.9       # a probability of 8, not 1


# ------------------------------------------------ the balancing controller

def test_the_balancing_controller_moves_the_bias_against_the_load():
    bias = jnp.asarray([0.0, 0.1, -0.1, 0.0])
    chosen = jnp.asarray([[0], [0], [0], [1], [2], [0], [0], [3]])
    got = moe_dropless.rebalance(bias, chosen, 0.2)
    share = np.array([0.625, 0.125, 0.125, 0.125])
    np.testing.assert_allclose(got, np.asarray(bias) + 0.2 * (0.25 - share),
                               rtol=1e-6)
    assert got[0] < bias[0] and all(got[1:] > bias[1:])
    np.testing.assert_allclose(jnp.sum(got), jnp.sum(bias), atol=1e-7)
    # several experts a token: shares of the PAIRS, still summing to one
    pairs = jnp.asarray([[0, 1], [0, 2], [0, 3], [0, 1]])
    np.testing.assert_allclose(
        moe_dropless.rebalance(jnp.zeros(4), pairs, 1.0),
        0.25 - np.array([4, 2, 1, 1]) / 8.0, rtol=1e-6)
    # an even load leaves the bias where it is
    even = jnp.arange(8).reshape(8, 1) % 4
    np.testing.assert_array_equal(
        moe_dropless.rebalance(bias, even, 0.5), bias)


def test_rebalance_routers_moves_the_biases_alone():
    from chainermn_tpu.models.transformer import rebalance_routers

    c = config(held=(4, 4))
    params = weights_zaya.make(c, 3)
    x = tokens(1, 2, 32)
    _, seen = model(c, dtype=jnp.float32).apply(
        {"params": params}, x, mutable=["intermediates"])
    chosen = {name: layer["ExpertLayer_0"]["chosen"][0]
              for name, layer in seen["intermediates"].items()
              if name.startswith("layer_")}
    moved = jax.jit(lambda p, c: rebalance_routers(p, c, 0.05))(
        params, chosen)
    before, after = weights.flatten(params), weights.flatten(moved)
    assert sorted(before) == sorted(after)
    for path, leaf in after.items():
        if path[-1] != "router_bias":
            np.testing.assert_array_equal(leaf, before[path])
            continue
        share = np.bincount(np.asarray(chosen[path[0]]).reshape(-1),
                            minlength=8) / 64.0
        np.testing.assert_allclose(
            leaf, np.asarray(before[path]) + 0.05 * (0.125 - share),
            rtol=1e-5, atol=1e-8)
    # the reference's controller is the same step
    ref = reference.rebalanced(params, chosen, 0.05)
    for path, leaf in weights.flatten(ref).items():
        np.testing.assert_allclose(leaf, after[path], rtol=1e-6, atol=1e-9)
    masks = reference.chosen_experts(params, x, c)
    by_mask = reference.rebalanced(params, masks, 0.05)
    for path, leaf in weights.flatten(by_mask).items():
        np.testing.assert_allclose(leaf, after[path], rtol=1e-6, atol=1e-9)


# --------------------------------------------------- the gated grouped MLP

def gated_case(seed, sizes, tile, d, f, spare_tiles=2):
    rng = np.random.default_rng(seed)
    tiles = [max(1, -(-n // tile)) for n in sizes]
    n_tiles = sum(tiles) + spare_tiles
    x = np.zeros((n_tiles * tile, d), np.float32)
    live = np.zeros(n_tiles * tile, bool)
    tile_group, at = [], 0
    for g, (n, t) in enumerate(zip(sizes, tiles)):
        x[at:at + n] = rng.normal(size=(n, d))
        live[at:at + n] = True
        tile_group += [g] * t
        at += t * tile
    tile_group += [len(sizes) - 1] * spare_tiles
    w = [0.3 * rng.normal(size=(len(sizes), f, d)).astype(np.float32)
         for _ in range(3)]
    return (jnp.asarray(x), *map(jnp.asarray, w),
            jnp.asarray(tile_group, jnp.int32),
            jnp.asarray([sum(tiles)], jnp.int32), live,
            np.repeat(tile_group, tile))


@pytest.mark.parametrize("case", [
    dict(sizes=[5, 0, 17, 8, 1], tile=8, d=32, f=24),
    dict(sizes=[11], tile=8, d=16, f=16),
    dict(sizes=[9, 3], tile=8, d=256, f=128, spare_tiles=0),
], ids=["ragged", "one_group", "wide"])
def test_gated_grouped_mlp_and_its_backward_against_a_loop(case):
    x, w_gate, w_up, w_down, tile_group, n_live, live, group = gated_case(
        3, **case)
    cot = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    live_rows = jnp.asarray(live)[:, None]

    def grouped(x, w_gate, w_up, w_down):
        y = gmm.grouped_swiglu_mlp(x, w_gate, w_up, w_down, tile_group,
                                   n_live)
        return jnp.sum(jnp.where(live_rows, y, 0.0) * cot)

    def loop(x, w_gate, w_up, w_down):
        total = 0.0
        for g in range(w_gate.shape[0]):
            rows = jnp.asarray(live & (group == g))[:, None]
            y = (jax.nn.silu(x @ w_gate[g].T) * (x @ w_up[g].T)) @ w_down[g]
            total = total + jnp.sum(jnp.where(rows, y, 0.0) * cot)
        return total

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(grouped, argnums=(0, 1, 2, 3))(
            x, w_gate, w_up, w_down)
        want = jax.value_and_grad(loop, argnums=(0, 1, 2, 3))(
            x, w_gate, w_up, w_down)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for name, a, b in zip(("dx", "dw_gate", "dw_up", "dw_down"), got[1],
                          want[1]):
        if name == "dx":                 # dead rows: whatever memory held
            a, b = a[live], b[live]
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5 * scale,
                                   err_msg=name)


# ------------------------------------- what the other families' tables build

#: ``float.hex`` of the fused-CE loss and of the whole gradient's norm of
#: one bfloat16 rematerialised step on the seeded tiny trees, read on the
#: tree PR 32 started from (commit 2ebacef): the carry, the third mixer
#: and the expert layer's kinds leave these families' programs alone.
OLDER = {
    "granite": ("0x1.2410b80000000p+2", "0x1.5bbc540000000p-4"),
    "nemotron": ("0x1.2393c40000000p+2", "0x1.7239ee0000000p+0"),
}


@pytest.mark.parametrize("family", ["gpt2", "granite", "nemotron"])
def test_the_older_trees_and_losses_are_unchanged_to_the_bit(family):
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    if family == "gpt2":
        from tests.test_hybrid import CGPT_TINY, CGPT_TINY_LOSS

        lm = TransformerLM(
            vocab=CGPT_TINY["vocab_size"], d_model=CGPT_TINY["n_embd"],
            n_heads=CGPT_TINY["n_head"], d_ff=CGPT_TINY["n_inner"],
            n_layers=CGPT_TINY["n_layer"],
            max_len=CGPT_TINY["n_positions"])
        p = weights.make(CGPT_TINY, 2**31 + 5)
        tok = jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, 211)
        h = lm.apply({"params": p}, tok[:, :-1], return_hidden=True)
        assert float(fused_cross_entropy(
            h, p["embed"]["embedding"], tok[:, 1:], chunk=64)) == (
            CGPT_TINY_LOSS)
        shapes, seeded = lm, weights.shapes(CGPT_TINY)
    elif family == "granite":
        from chipbench import weights_hybrid
        from tests.test_hybrid import config as granite

        c = granite()
        lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                           table=table_from_config(c), remat=True)
        p, head = weights_hybrid.make(c, 2**31 + 5), (
            lambda p: p["embed"]["embedding"])
        seeded = weights_hybrid.shapes(c)
    else:
        from chipbench import weights_nemotron
        from tests.test_nemotron_h import config as nemo
        from tests.test_nemotron_h import table_of as nemo_table

        c = nemo(held=(2, 2))
        lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                           table=nemo_table(c), remat=True)
        p, head = weights_nemotron.make(c, 2**31 + 5), (
            lambda p: p["lm_head"])
        seeded = weights_nemotron.shapes(c)
    made = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {k: v.shape for k, v in weights.flatten(made).items()} == seeded
    if family == "gpt2":
        return
    tok = jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, lm.vocab)

    def loss(p):
        h = lm.apply({"params": p}, tok[:, :-1], return_hidden=True)
        return fused_cross_entropy(h, head(p), tok[:, 1:], chunk=64)

    value, grads = jax.jit(jax.value_and_grad(loss))(p)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    assert (float(value).hex(), float(norm).hex()) == OLDER[family]


# ------------------------------------------- what the layer tells telemetry

def test_the_layer_publishes_its_geometry_when_someone_listens(tmp_path):
    import json

    from chainermn_tpu.observability import reporter, spans, step_log

    for name in ("cca-mixer", "cca-conv", "cca-rope"):
        assert spans.is_scope(name)
    c = config(held=(4, 4), n_layer=1)
    x = jnp.zeros((2, 8, D_MODEL))
    layer = Block(D_MODEL, table_of(c).layers[0], jnp.float32)
    rep, path = reporter.Reporter(), str(tmp_path / "steps.jsonl")
    with reporter.scope(rep), step_log.recording(path):
        layer.init(jax.random.PRNGKey(0), x, causal_mask(8),
                   jnp.zeros((2, 8, 16)))
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    assert gauges["cca/q_heads"] == 4 and gauges["cca/kv_heads"] == 2
    assert gauges["cca/latent_q"] == 64 and gauges["cca/latent_kv"] == 32
    assert gauges["cca/taps0"] == 2 and gauges["cca/taps1"] == 2
    assert gauges["cca/rotary_dim"] == 8 and gauges["cca/seq"] == 8
    assert gauges["cca/xla_shifts"] == 1
    assert gauges["moe/experts"] == 8 and gauges["moe/experts_held"] == 4
    assert gauges["moe/top_k"] == 1 and gauges["moe/pair_rows"] == 16
    assert gauges["moe/mlp_softmax"] == 1 and gauges["moe/swiglu"] == 1
    assert rep.summary()["counters"]["cca/calls"] >= 1
    rows = {r["event"]: r for r in map(json.loads, open(path))}
    assert rows["cca_geometry"]["d_head"] == 16
    assert rows["moe_geometry"]["router"] == "mlp_softmax"
    assert rows["moe_geometry"]["expert"] == "swiglu"


def test_the_layers_ops_carry_its_scopes():
    """Every part of the layer lowers under its scope, forward and
    backward: what the benchmark's ``cca.*`` and ``zaya.*`` readers join
    the trace to."""
    c = config(n_layer=1)
    params = weights_zaya.make(c, 3)["layer_0"]
    layer = Block(D_MODEL, table_of(c).layers[0], jnp.float32)
    x = jnp.ones((1, 16, D_MODEL))
    mask, r = causal_mask(16), jnp.ones((1, 16, 16))

    def loss(p):
        out, state = layer.apply({"params": p}, x, mask, r)
        return jnp.sum(out) + jnp.sum(state)

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for name in ("cca-conv", "cca-rope"):
        inside = [p for p in paths if f"/cca-mixer/{name}/" in p]
        assert any("transpose(jvp(" in p for p in inside), name
        assert any("transpose(jvp(" not in p for p in inside), name
    assert any(p.split("/cca-mixer/")[-1].split("/")[0] not in (
        "cca-conv", "cca-rope") for p in paths if "/cca-mixer/" in p)
    for name in ("moe-route", "moe-dispatch", "moe-experts"):
        inside = [p for p in paths if f"/moe-layer/{name}/" in p
                  or f"/moe-layer/jit(_gmm_call)/{name}/" in p
                  or f"/moe-layer/jit(_dw_call)/{name}/" in p]
        assert any("transpose(jvp(" in p for p in inside), name
        assert any("transpose(jvp(" not in p for p in inside), name
    assert not any("moe-shared" in p for p in paths)


def test_a_rematerialised_layer_keeps_the_forwards_choice():
    """In bfloat16 a backward pass that chose its expert again could
    settle a near-tie otherwise than the forward did: the routers' choice
    is kept, the carried state with it, so the gradients are the
    unrematerialised model's to the rounding of recomputed activations."""
    c = config(held=(4, 4))
    params = weights_zaya.make(c, 5)
    toks = tokens(1, 2, 65)
    x, y = toks[:, :-1], toks[:, 1:]

    def grads(remat):
        lm = model(c, remat=remat)

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


def test_the_serving_engine_names_the_mixer_it_refuses():
    from chainermn_tpu.serving.engine import InferenceEngine

    c = config()
    with pytest.raises(ValueError, match="cca"):
        InferenceEngine(model(c, max_len=64), weights_zaya.make(c, 1))
