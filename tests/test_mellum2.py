"""The ``mellum`` rows of the block table — sliding-window attention rows
and a full-attention row with YaRN-scaled rotary positions in one table,
each row handing its own window to the one ``attention_fn``, QK-norm, a
softmax top-k router over one expert-parallel rank's share of the experts
with no shared expert — against the plain reference the benchmark compares
with on the chip (``chipbench/refs/mellum2.py``: attention as an explicit
masked softmax, YaRN from its formulas, dense masked sums over the held
experts, none of the program's code)."""

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
    ExpertsSpec,
    LayerSpec,
    YarnSpec,
    rotary_frequencies,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    MultiHeadAttention,
    TransformerLM,
    causal_mask,
    remat_kept,
    rotate_partial,
)
from chainermn_tpu.observability import device_trace, spans  # noqa: E402
from chainermn_tpu.ops import make_flash_attention_fn  # noqa: E402
from chainermn_tpu.parallel import moe_dropless  # noqa: E402
from chipbench import weights, weights_mellum2  # noqa: E402
from chipbench.refs import mellum2 as reference  # noqa: E402

sys.path.insert(0, os.path.join(ROOT, "tests"))
import _older_families  # noqa: E402

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

D_MODEL, VOCAB = 32, 96
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 2,
        "beta_slow": 0.5, "attention_factor": 1.1386294361119891}


def config(held=(0, 8), n_layer=4, **over):
    """A ``mellum`` config at toy widths, keys as published, plus the
    benchmark's own: the layers kept and the experts held."""
    c = {
        "model_type": "mellum", "attention_bias": False, "head_dim": 16,
        "hidden_act": "silu", "hidden_size": D_MODEL,
        "intermediate_size": 64,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
        + ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 8, "max_position_embeddings": 1024,
        "max_window_layers": 0, "moe_intermediate_size": 24,
        "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts": held[1], "num_experts_published": 8,
        "experts_held_first": held[0], "num_experts_per_tok": 3,
        "num_hidden_layers": 8, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": dict(YARN),
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}},
        "sliding_window": 12, "use_sliding_window": True,
        "tie_word_embeddings": False, "vocab_size": VOCAB,
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


def ref_logits(params, x, c):
    return reference.logits(params, reference.layers(
        params, reference.embed(params, x), c), c)


# ------------------------------------------------- the table from the keys

def test_the_published_keys_give_three_sliding_rows_to_one_full_row():
    c = config()
    table = table_from_config(dict(c, num_experts=8))
    assert len(table.layers) == 8 and table.positions == "rotary"
    assert table.final_norm == "rmsnorm" and not table.tied_head
    assert [r.window for r in table.layers] == [12, 12, 12, None] * 2
    sliding, full = table.layers[0], table.layers[3]
    assert sliding.yarn is None and full.yarn == YarnSpec(
        factor=4.0, original_max_position=16, beta_fast=2.0, beta_slow=0.5,
        attention_factor=1.1386294361119891)
    assert dataclasses.replace(full, yarn=None, window=12) == sliding
    for row in table.layers:
        assert (row.mixer, row.norm, row.norm_eps) == (
            "attention", "rmsnorm", 1e-6)
        assert (row.n_heads, row.n_kv_heads, row.d_head) == (4, 2, 16)
        assert row.rotary_dim == 16 and row.rope_theta == 1e4
        assert row.qk_norm and not row.out_gate and row.attn_scale is None
        assert row.ffn == "experts" and row.experts == ExpertsSpec(
            n_experts=8, top_k=3, d_expert=24, d_shared=0,
            router="softmax", expert="swiglu")
    cut = table_of(config(held=(2, 4)))
    assert len(cut.layers) == 4
    assert cut.layers[0].experts.experts_held == (2, 4)
    assert cut.layers[0].experts.n_experts == 8      # the router's width


def test_the_catalog_rows_keys_build_the_cells_table_and_count():
    """The published widths, through the configuration file: three rows
    under a window of 1024 and one YaRN-scaled full row, four 896-wide
    softmax-routed expert FFNs, and the parameter count of the file's own
    reckoning."""
    with open(os.path.join(
            ROOT, "chipbench/configs/mellum2-12b-a2.5b-train.json")) as f:
        c = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct"][0]
        assert c["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if c.get(k) != v} == {
            "num_experts", "vocab_size"}
    table = table_from_config(
        dict(c, num_experts=c["num_experts_published"]), n_layers=4,
        experts_held=(0, 8))
    assert [r.window for r in table.layers] == [1024, 1024, 1024, None]
    assert table.layers[3].yarn == YarnSpec(
        factor=16.0, original_max_position=8192, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.2772588722239782)
    for row in table.layers:
        assert (row.n_heads, row.n_kv_heads, row.d_head, row.rotary_dim,
                row.rope_theta, row.qk_norm) == (
                    32, 4, 128, 128, 500000.0, True)
        z = row.experts
        assert (z.n_experts, z.top_k, z.d_expert, z.d_shared, z.router,
                z.experts_held) == (64, 8, 896, 0, "softmax", (0, 8))
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table)
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert count == c["reckoning"]["total"] == 340_350_208
    assert count == weights_mellum2.n_params(c)
    r = c["reckoning"]
    assert r["layer"] == (r["attention"] + r["qk_norm"] + r["router"]
                          + r["layer_norms"] + 8 * r["routed_expert"])
    assert r["total"] == (4 * r["layer"] + r["table_and_head"]
                          + r["final_norm"])
    assert r["state_bytes"] == 16 * r["total"]


@pytest.mark.parametrize("key,value,needle", [
    ("use_sliding_window", False, "use_sliding_window"),
    ("sliding_window", None, "sliding_window"),
    ("mlp_layer_types", ["sparse"] * 7 + ["dense"], "mlp_layer_types"),
    ("layer_types", ["chunked_attention"] * 8, "layer_types"),
    ("layer_types", ["full_attention"] * 7, "num_hidden_layers"),
    ("rope_parameters", {"full_attention": dict(YARN, rope_type="llama3"),
                         "sliding_attention": {"rope_theta": 1e4}},
     "rope_type"),
    ("rope_parameters", {"full_attention": dict(YARN)}, "rope_parameters"),
    ("rope_parameters", {"full_attention": dict(YARN, truncate=False),
                         "sliding_attention": {"rope_theta": 1e4}},
     "truncated"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("tie_word_embeddings", True, "tied"),
])
def test_table_from_config_refuses_by_key(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_from_config(dict(config(), **{key: value}))


@pytest.mark.parametrize("kw,needle", [
    (dict(mixer="none", ffn="gelu", window=8), "attention row's"),
    (dict(window=0), "window"),
    (dict(yarn=YarnSpec(2.0, 16)), "rotary_dim"),
])
def test_a_row_states_what_it_has(kw, needle):
    with pytest.raises(ValueError, match=needle):
        LayerSpec(**kw)
    with pytest.raises(ValueError, match="factor"):
        YarnSpec(factor=0.5, original_max_position=16)


def test_the_caches_take_no_window():
    row = table_of(config()).layers[0]
    layer = MultiHeadAttention(D_MODEL, 4, jnp.float32, decode=True,
                               cache_len=8, window=row.window)
    with pytest.raises(ValueError, match="window"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, D_MODEL)),
                   jnp.zeros((1, 1, D_MODEL)))


# ------------------------------------------------------ YaRN's frequencies

def test_yarn_frequencies_of_the_published_parameters_by_hand():
    """d = 128, b = 500000, L = 8192, s = 16: ``c(r) = 128 ln(8192 / (2 pi
    r)) / (2 ln 500000)`` is 18.08 at r = 32 and 34.98 at r = 1, so ``low``
    18 and ``high`` 35: dimensions 0..18 keep ``b^(-i/64)``, 35..63 turn 16
    times slower, 19..34 blend by ``(i - 18) / 17``."""
    d, b, L, s = 128, 500000.0, 8192.0, 16.0
    c = lambda r: d * np.log(L / (2 * np.pi * r)) / (2 * np.log(b))  # noqa: E731
    assert (round(c(32), 2), round(c(1), 2)) == (18.08, 34.98)
    low, high = 18, 35
    want = []
    for i in range(64):
        plain = b ** (-i / 64.0)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain * ((1 - ramp) + ramp / s))
    # a few of them written out
    assert want[0] == 1.0
    assert want[18] == pytest.approx(0.0249554, rel=1e-5)    # plain still
    assert want[26] == pytest.approx(0.00270438, rel=1e-5)   # ramp 8/17
    assert want[35] == pytest.approx(4.7781062e-05, rel=1e-6)  # plain / 16
    assert want[63] == pytest.approx(500000 ** (-63 / 64) / 16)
    rope = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    np.testing.assert_allclose(reference.yarn_frequencies(rope, 128), want,
                               rtol=1e-12)
    spec = YarnSpec(factor=16.0, original_max_position=8192)
    freq, scale = rotary_frequencies(128, 500000.0, spec)
    np.testing.assert_allclose(freq, want, rtol=1e-12)
    # the published attention factor is the formula's own value
    assert scale == pytest.approx(1.2772588722239782, rel=1e-15)
    assert dataclasses.replace(spec, attention_factor=1.5).scale == 1.5
    plain, one = rotary_frequencies(128, 500000.0)
    assert one == 1.0 and np.array_equal(
        plain, 500000.0 ** (-np.arange(64) * 2.0 / 128))


def test_rotation_under_yarn_is_the_references():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 3, 16))
    spec = table_of(config()).layers[3].yarn
    got = rotate_partial(x, jnp.arange(40), 16, 1e4, spec)
    np.testing.assert_allclose(got[0], reference.rotate(x[0], YARN),
                               rtol=1e-6, atol=1e-6)
    # the scale is on cos and sin both: the rotated vector is 1.1386 long
    np.testing.assert_allclose(
        jnp.linalg.norm(got, axis=-1) / jnp.linalg.norm(x, axis=-1),
        1.1386294361119891, rtol=1e-5)


# ------------------------------------------- the model against the reference

def both_sides(held, flash):
    """Logits, loss and gradients of the program (float32, ``highest``;
    ``flash``: through the flash adapter in interpret mode at blocks of
    8, so that the band crosses tiles, else the dense masked path) and of
    the reference on one seeded tree."""
    c = config(held=held)
    params = weights_mellum2.make(c, 2**31 + 11)
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
        weights_mellum2.shapes(config()))]


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


def test_the_seeded_tree_is_the_programs_tree():
    """Names and shapes of ``weights_mellum2`` against the program's own
    ``init`` (the reference reads the tree by these names)."""
    c = config(held=(2, 4))
    shapes = jax.eval_shape(
        lambda: model(c).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        weights_mellum2.shapes(c))
    assert not any(part in ("router_bias", "shared", "shared_gate")
                   for p in weights_mellum2.shapes(c) for part in p)


def test_the_programs_choices_are_the_references():
    c = config()
    params = weights_mellum2.make(c, 2**31 + 11)
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
    """In float32 the rematerialised model — every layer under the one
    policy, a windowed row's flash output and row statistics kept by name
    as a full row's — recomputes what the plain one kept: the same
    numbers, to the order of a recomputed sum."""
    c = config(held=(2, 4))
    params = weights_mellum2.make(c, 5)
    toks = tokens(1, 2, 41)
    x, y = toks[:, :-1], toks[:, 1:]

    def grads(remat):
        lm = model(c, dtype=jnp.float32, remat=remat,
                   attention_fn=make_flash_attention_fn(
                       causal=True, block_q=8, block_k=8))

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


def test_remat_kept_reckons_a_windowed_row_as_a_full_row():
    table = table_of(config())
    kept = remat_kept(table, D_MODEL, 2 * 40, 2, seq=40)
    assert kept["flash_layers"] == 4 and kept["expert_layers"] == 4
    # o and lse of every row, the windowed ones too: 4 heads of 16 in
    # bfloat16 and 4 bytes a token and head
    assert kept[f"{fa.FLASH_RESIDUALS}_bytes"] == 4 * 80 * 4 * (16 * 2 + 4)


# ------------------------------------------------- a sliding row's window

def sliding_row(window, length, block, seed=3):
    """One sliding row's attention through the flash adapter at ``block``
    (interpret mode) and the reference's at ``window`` and at ``window +
    1``, over one seeded row."""
    c = config(sliding_window=window, n_layer=1)
    att = weights_mellum2.make(c, seed)["layer_0"]["MultiHeadAttention_0"]
    row = table_of(c).layers[0]
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, length, D_MODEL))
    layer = MultiHeadAttention(
        D_MODEL, row.n_heads, jnp.float32,
        make_flash_attention_fn(causal=True, block_q=block, block_k=block),
        n_kv_heads=row.n_kv_heads, d_head=row.d_head,
        rotary_dim=row.rotary_dim, rope_theta=row.rope_theta,
        window=row.window, qk_norm="rmsnorm", norm_eps=row.norm_eps)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": att}, h, h)[0]
        want, wider = (reference.attention(
            h[0], att, "sliding_attention",
            dict(c, sliding_window=w), "float32")
            for w in (window, window + 1))
    return got, want, wider


@pytest.mark.parametrize("window,block", [
    (32, 16),     # the band's edge on a tile's edge
    (24, 16),     # inside a tile
    (20, 8), (7, 8), (1, 8), (64, 16), (100, 16)])
def test_a_sliding_row_sees_exactly_its_window(window, block):
    got, want, wider = sliding_row(window, 64, block)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    if window >= 64:
        return      # wider than the row: the whole triangle either way
    # The query AT the band's edge (position ``window``, the first to
    # lose a key: key 0 is ``window`` behind it) tells a window off by
    # one; the queries before it see every earlier key either way.
    np.testing.assert_allclose(got[:window], wider[:window], rtol=2e-4,
                               atol=2e-6)
    gap = jnp.abs(got[window:] - wider[window:]).max(axis=-1)
    assert float(gap.min()) > 1e-4 * float(jnp.abs(want).max())


def test_the_row_is_the_one_source_of_the_window():
    q = jnp.zeros((1, 16, 2, 8))
    # the adapter's own window is a default: what a row hands over wins
    assert fa.row_window(8, None) == fa.row_window(None, 8) == 8
    assert fa.row_window(8, 12) == 12 and fa.row_window(None, None) is None
    from chainermn_tpu.parallel.ring_attention import (
        make_zigzag_ring_attention_fn)

    with pytest.raises(ValueError, match="zigzag"):
        make_zigzag_ring_attention_fn("sp")(q, q, q, None, window=4)
    # a full row hands nothing over: an adapter of four arguments, as a
    # caller may have written one, still serves it
    seen = []

    def four_arguments(q, k, v, mask):
        seen.append(mask)
        return q

    h = jnp.ones((1, 8, D_MODEL))
    layer = MultiHeadAttention(D_MODEL, 4, jnp.float32, four_arguments)
    layer.apply(layer.init(jax.random.PRNGKey(0), h, h), h, h)
    assert len(seen) == 2


# ---------------------------------------------- broken tables are told apart

def _logits_gap(c, table):
    params = weights_mellum2.make(c, 2**31 + 3)
    # (the table at the other matrices' scale: beside a unit-scale row
    # the four branches are a hundredth of the stream, and so is a fault)
    params["embed"]["embedding"] = 0.02 * params["embed"]["embedding"]
    x = tokens(4, 2, 40)
    lm = TransformerLM(vocab=VOCAB, d_model=D_MODEL, table=table,
                       dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = lm.apply({"params": params}, x)
        want = ref_logits(params, x, c)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def _with_row(table, i, **changes):
    rows = list(table.layers)
    rows[i] = dataclasses.replace(rows[i], **changes)
    return dataclasses.replace(table, layers=tuple(rows))


@pytest.mark.parametrize("broken", [
    "sound", "window_dropped_on_one_row", "window_off_by_one",
    "yarn_blend_dropped", "attention_factor_dropped",
    "renormalisation_dropped"])
def test_a_broken_table_is_not_the_reference(broken, monkeypatch):
    c = config()
    table = table_of(c)
    yarn = table.layers[3].yarn
    if broken == "window_dropped_on_one_row":
        table = _with_row(table, 1, window=None)
    elif broken == "window_off_by_one":
        table = _with_row(table, 1, window=13)
    elif broken == "yarn_blend_dropped":    # the plain frequencies, scaled
        table = _with_row(table, 3, yarn=dataclasses.replace(
            yarn, factor=1.0))
    elif broken == "attention_factor_dropped":
        table = _with_row(table, 3, yarn=dataclasses.replace(
            yarn, attention_factor=1.0))
    elif broken == "renormalisation_dropped":
        from jax import lax

        def raw(h, w_router, *, top_k, scaling=1.0):
            p = jax.nn.softmax(jnp.dot(
                h.astype(jnp.float32), w_router,
                precision=lax.Precision.HIGHEST), axis=-1)
            _, chosen = lax.top_k(p, top_k)
            chosen = chosen.astype(jnp.int32)
            return chosen, jnp.take_along_axis(p, chosen, axis=-1) * scaling

        monkeypatch.setattr(moe_dropless, "route_softmax", raw)
    gap = _logits_gap(c, table)
    if broken == "sound":
        assert gap < 1e-5
    else:
        assert gap > 1e-3, gap


# --------------------------------------------- the share and the whole layer

def test_eight_shares_add_up_to_the_uncut_layer():
    """What ties one rank's share to the model: at 16 experts the eight
    ranks' layers, two experts each, add up to the reference's layer with
    all sixteen (there is no shared expert to count once)."""
    whole = config(held=(0, 16), n_layer=1, num_experts_published=16)
    params = weights_mellum2.make(whole, 2**31 + 5)["layer_0"]
    e = params["ExpertLayer_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, D_MODEL))
    stacks = ("experts_gate", "experts_up", "experts_down")
    mask = causal_mask(16)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.layer(
            row, params, "sliding_attention", whole, "float32")
            for row in x])
        base, total = None, 0.0
        for first in range(0, 16, 2):
            c = config(held=(first, 2), n_layer=1, num_experts_published=16)
            row = table_of(c).layers[0]
            share = dict(e, **{k: e[k][first:first + 2] for k in stacks})
            out = Block(D_MODEL, row, jnp.float32).apply(
                {"params": dict(params, ExpertLayer_0=share)}, x, mask)
            if base is None:    # x + attention, no expert
                hollow = dict(e, **{k: jnp.zeros_like(share[k])
                                    for k in stacks})
                base = Block(D_MODEL, row, jnp.float32).apply(
                    {"params": dict(params, ExpertLayer_0=hollow)}, x, mask)
            total = total + out - base
    np.testing.assert_allclose(total + base, want, rtol=2e-4, atol=2e-5)


def test_a_share_is_the_references_share():
    c = config(held=(3, 4), n_layer=1)
    params = weights_mellum2.make(c, 2**31 + 6)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, D_MODEL))
    with jax.default_matmul_precision("highest"):
        got = Block(D_MODEL, table_of(c).layers[0], jnp.float32).apply(
            {"params": params}, x, causal_mask(24))
        want = reference.layer(x[0], params, "sliding_attention", c,
                               "float32")
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_a_stage_cut_of_two_periods_is_the_references_first_period():
    """``n_layers`` cuts a pipeline stage: the first four rows of the
    eight-layer table, fed the same tree, give what the reference's first
    period gives, and the second stage's rows go on from there."""
    c8 = config(n_layer=8)
    params = weights_mellum2.make(c8, 2**31 + 7)
    x = tokens(2, 2, 24)
    first = {k: v for k, v in params.items()
             if not k.startswith("layer_") or int(k.split("_")[1]) < 4}
    with jax.default_matmul_precision("highest"):
        got = model(config(n_layer=4), dtype=jnp.float32).apply(
            {"params": first}, x)
        want = ref_logits(first, x, c8)
        whole = model(c8, dtype=jnp.float32).apply({"params": params}, x)
        ref_whole = ref_logits(params, x, c8)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(whole, ref_whole, rtol=2e-4, atol=2e-5)
    assert [r.window for r in table_of(c8).layers[4:]] == [
        12, 12, 12, None]


# ------------------------------------------- the older families, unchanged

def test_the_older_families_tables_carry_the_new_columns_empty():
    for name, (table, _, _) in _older_families.tables().items():
        for row in table.layers:
            assert row.window is None and row.yarn is None, name
    row = LayerSpec()
    assert (row.window, row.yarn) == (None, None)


@pytest.fixture(scope="module")
def older_digests():
    with open(os.path.join(ROOT, "tests/golden/older_families.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("family", [
    "gpt2", "granitemoehybrid", "nemotron_h", "zaya", "qwen3_next"])
def test_an_older_familys_outputs_are_unchanged_to_the_bit(
        family, older_digests):
    """The traced program of the family's logits and gradient (every
    equation, every constant) is the one the golden file's commit traced
    (``tests/_older_families.py`` says why the program and not its
    output's bits).  All five digests are PR 48's: every family's
    gradient runs the flash backward, one Mosaic kernel since, and
    nothing else of their programs moved — the test below holds the
    two-kernel side to the digests they had before."""
    assert _older_families.digest(
        *_older_families.tables()[family]) == older_digests[family], (
        f"the {family} family traces another program than "
        f"tests/golden/older_families.json holds.  Not meant: the change "
        f"reached a family it should have left alone.  Meant (a change "
        f"to a path this family runs): remake THIS family's digest and "
        f"no other -- `PYTHONPATH=. JAX_PLATFORMS=cpu python "
        f"tests/_older_families.py out.json`, copy the one line -- and "
        f"show in CHANGES.md that the families left alone kept theirs "
        f"byte for byte")


@pytest.mark.parametrize("family", [
    "gpt2", "granitemoehybrid", "nemotron_h", "zaya", "qwen3_next"])
def test_past_the_footprint_rule_an_older_family_traces_the_parents_program(
        family, older_digests, monkeypatch):
    """With the flash backward's footprint rule brought down under every
    row (``VMEM_LIMIT_MAX`` 0: what a 128k row meets at the real limit)
    a family's logits and gradient trace, equation for equation, the
    program the golden file held BEFORE the one-pass backward (its
    ``two_kernels`` group: ``zaya`` and ``qwen3_next`` PR 41's, the three
    others PR 39's parent's) — the two kernels are the parent's to the
    letter, and nothing but the choice of backward moved.  (The jitted
    wrapper around the two kernels was called ``_flash_bh_bwd`` then.)"""
    monkeypatch.setattr(fa, "VMEM_LIMIT_MAX", 0)
    got = _older_families.digest(
        *_older_families.tables()[family],
        text_of=lambda traced: str(traced).replace(
            "_flash_bwd_pair", "_flash_bh_bwd"))
    assert got == older_digests["two_kernels"][family]


def test_plain_rotation_is_the_old_formula_to_the_bit():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16))
    pos = jnp.arange(24)
    half = 4
    freq = 1e7 ** (-np.arange(half, dtype=np.float64) * 2.0 / 8)
    angle = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:8], x[..., 8:]
    old = jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)
    assert np.array_equal(rotate_partial(x, pos, 8, 1e7), old)


# ------------------------------------------------ the new scope in a trace

@pytest.fixture(scope="module")
def compiled_text():
    """A tiny ``mellum`` model through ``make_train_step`` under ``remat``
    with the flash adapter, compiled on the CPU."""
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


def test_the_window_scope_is_on_the_sliding_rows_ops(compiled_text):
    assert "attn-window" in spans.MODEL_PARTS
    assert spans.is_scope("attn-window")
    assert not spans.is_region("attn-window")
    table = device_trace.scope_table(compiled_text)
    by_layer = {}
    for path in table.values():
        layer, on = device_trace.layer_of(path), device_trace.scopes_on(path)
        for name in ("attn-window", "attn-mixer"):
            if name in on:
                by_layer.setdefault(name, set()).add(layer)
    assert by_layer == {"attn-window": {"0", "1", "2"}, "attn-mixer": {"3"}}
    # the flash regions, the projections and the rotation nest in it
    # unchanged, forward and backward
    under = {device_trace.owner(p)[1] for p in table.values()
             if "attn-window" in device_trace.scopes_on(p)}
    assert {"flash-fwd", "flash-bwd-dkv", "attn-rope",
            "mixer-proj"} <= under
    assert "flash-bwd-dq" not in under      # the backward is one pass
    # and the regions' census rides in the path with the row's window: a
    # band of 8 at blocks of 8 over 32 tokens runs 7 tiles in a grid of
    # 4 x 2 steps (the band, not the 16 of the rectangle), the triangle 10
    (census,) = table.tiles_within["attn-window"]["flash-fwd"]
    whole = fa.tile_census(32, 32, 8, 8, True, 8)["fwd"]
    assert census == {field: whole[field] for field in spans.TILE_FIELDS}
    assert (census["live"], census["visited"]) == (7, 8)
    assert table.tiles_within["attn-mixer"]["flash-fwd"][0]["live"] == 10
    assert len(table.tiles["flash-fwd"]) == 2


def test_device_trace_splits_flash_time_by_row_kind(compiled_text):
    """One second to every instruction the compiled step runs: ``within``
    has the flash regions' seconds under each kind of row, with no name of
    this model in ``device_trace``."""
    table = device_trace.scope_table(compiled_text)
    kernels = {}
    for name in table:
        phase, owned = device_trace.owner(table.owner_path(name))
        on = device_trace.scopes_on(table.owner_path(name))
        if phase == "fwd-bwd" and owned and owned.startswith("flash-") and (
                name not in table.containers):
            kind = "attn-window" if "attn-window" in on else "attn-mixer"
            kernels.setdefault((kind, owned), []).append(name)
    assert {k for k, _ in kernels} == {"attn-window", "attn-mixer"}
    ops = [(name, float(i), float(i) + 1.0) for i, name in enumerate(
        n for names in kernels.values() for n in names)]
    got = device_trace.attribute(ops, table)
    for (kind, region), names in kernels.items():
        assert got["within"][kind][region] == pytest.approx(len(names))
        assert got["within"][region][region] == pytest.approx(sum(
            len(v) for (_, r), v in kernels.items() if r == region))
    assert "attn-window" not in open(
        device_trace.__file__).read().split('"""', 2)[2]
    report = device_trace.report_from(
        [{"name": "/device:TPU:0",
          "modules": [(table.program, 0.0, float(len(ops)))],
          "ops": ops}], [], {"train_step": table})
    row = report["programs"]["train_step"]
    assert row["within_ms"]["attn-window"]["flash-fwd"] == pytest.approx(
        1e3 * len(kernels[("attn-window", "flash-fwd")]))
    assert row["region_tiles_within"]["attn-window"]["flash-fwd"]
    json.dumps(report)
