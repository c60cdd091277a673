"""The ``sdar_moe`` table — identical layers of a GQA row with QK-norm and
whole-head rotary positions and a softmax top-k sparse-expert FFN, an
untied head — TRAINED BY BLOCK DIFFUSION: the table states the block
once, every attention row then sees a document's clean and noised copies
through one block-causal mask at the positions it is handed, and the loss
is a weighted masked-token loss over the noisy rows — against the plain
reference the benchmark compares with on the chip
(``chipbench/refs/sdar_moe.py``: the mask built by comparison, attention
as an explicit masked softmax, dense masked sums over the held experts,
none of the program's code)."""

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

from chainermn_tpu.datasets.block_diffusion import noise_batch  # noqa: E402
from chainermn_tpu.models.block_diffusion import (  # noqa: E402
    block_diffusion_loss,
)
from chainermn_tpu.models.block_table import (  # noqa: E402
    BlockTable,
    ExpertsSpec,
    LayerSpec,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    TransformerLM,
)
from chainermn_tpu.observability import spans  # noqa: E402
from chainermn_tpu.ops import make_flash_attention_fn  # noqa: E402
from chipbench import traffic_bd, weights, weights_sdar_moe  # noqa: E402
from chipbench.refs import sdar_moe as reference  # noqa: E402
from chipbench.refs.gpt2_dense import _adamw  # noqa: E402

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

D_MODEL, VOCAB, L, B = 32, 96, 32, 4
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OPT = {"learning_rate": 1e-3, "weight_decay": 0.1, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8}


def config(held=(0, 8), n_layer=2, **over):
    """An ``sdar_moe`` config at toy widths, keys as published, plus the
    benchmark's own: the layers kept, the experts held, the block."""
    c = {
        "model_type": "sdar_moe", "attention_bias": False,
        "decoder_sparse_step": 1, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": D_MODEL, "intermediate_size": 64,
        "max_position_embeddings": 1024, "max_window_layers": 4,
        "mlp_only_layers": [], "moe_intermediate_size": 24,
        "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts": held[1], "num_experts_published": 8,
        "experts_held_first": held[0], "num_experts_per_tok": 3,
        "num_hidden_layers": 4, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000,
        "sliding_window": None, "use_sliding_window": False,
        "tie_word_embeddings": False, "vocab_size": VOCAB,
        "n_layer": n_layer, "block_length": B, "optimizer": OPT,
    }
    c.update(over)
    return c


def table_of(c):
    published = dict(c, num_experts=c["num_experts_published"])
    return table_from_config(
        published, n_layers=c["n_layer"],
        experts_held=(c["experts_held_first"], c["num_experts"]))


def batch(seed, docs=2, length=L):
    """``(x0, xt, weights)`` by the library's own noising: ids from the
    non-mask rows, the mask id the slice's last row."""
    rng = np.random.default_rng(seed)
    x0 = rng.integers(0, VOCAB - 1, size=(docs, length)).astype(np.int32)
    return (x0,) + noise_batch(x0, B, VOCAB - 1, rng)


def model(c, **kw):
    return TransformerLM(vocab=VOCAB, d_model=D_MODEL, table=table_of(c),
                         **kw)


def program_loss(lm, p, x0, xt, w, **kw):
    return block_diffusion_loss(
        lambda t, at: lm.apply({"params": p}, t, position_offset=at,
                               return_hidden=True),
        p["lm_head"], x0, xt, w, chunk=16, **kw)[0]


# ------------------------------------------------- the table from the keys

def test_the_published_keys_give_identical_rows_under_one_mask():
    c = config()
    table = table_from_config(dict(c, num_experts=8))
    assert len(table.layers) == 4 and table.positions == "rotary"
    assert table.final_norm == "rmsnorm" and not table.tied_head
    assert table.block_diffusion == B
    assert len(set(table.layers)) == 1
    row = table.layers[0]
    assert (row.mixer, row.norm, row.norm_eps) == (
        "attention", "rmsnorm", 1e-6)
    assert (row.n_heads, row.n_kv_heads, row.d_head) == (4, 2, 16)
    assert row.rotary_dim == 16 and row.rope_theta == 1e4
    assert row.qk_norm and row.window is None and row.yarn is None
    assert not row.out_gate and row.attn_scale is None
    assert row.ffn == "experts" and row.experts == ExpertsSpec(
        n_experts=8, top_k=3, d_expert=24, d_shared=0, router="softmax",
        expert="swiglu")
    cut = table_of(config(held=(2, 4)))
    assert len(cut.layers) == 2
    assert cut.layers[0].experts.experts_held == (2, 4)
    assert cut.layers[0].experts.n_experts == 8      # the router's width
    # config.json has no key for the block: the release's 4 stands in
    bare = {k: v for k, v in c.items() if k != "block_length"}
    assert table_from_config(dict(bare, num_experts=8)).block_diffusion == 4
    assert table_from_config(
        dict(c, num_experts=8, block_length=8)).block_diffusion == 8


def test_the_catalog_rows_keys_build_the_cells_table_and_count():
    """The published widths, through the configuration file: six GQA 32/4
    rows of 128 under the block-diffusion mask, six 768-wide
    softmax-routed expert FFNs of 128 with 16 held, and the parameter
    count of the file's own reckoning."""
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"]
    table = table_from_config(row["config"])
    assert len(table.layers) == 48 and table.block_diffusion == 4
    with open(os.path.join(
            ROOT, "chipbench/configs/sdar-30b-a3b-train.json")) as f:
        c = json.load(f)
    for key, value in row["config"].items():
        if key not in ("num_experts", "vocab_size"):
            assert c[key] == value, key
    assert (c["num_experts"], c["num_experts_published"]) == (16, 128)
    assert (c["vocab_size"], c["vocab_size_published"]) == (18992, 151936)
    table = table_of(c)
    assert len(table.layers) == c["n_layer"] and table.block_diffusion == 4
    z = table.layers[0]
    assert (z.n_heads, z.n_kv_heads, z.d_head, z.rotary_dim) == (
        32, 4, 128, 128)
    assert z.rope_theta == 1e6 and z.qk_norm
    e = z.experts
    assert (e.n_experts, e.top_k, e.d_expert, e.d_shared, e.router,
            e.expert, e.experts_held) == (128, 8, 768, 0, "softmax",
                                          "swiglu", (0, 16))
    count = weights_sdar_moe.n_params(c)
    assert count == c["reckoning"]["total"] == 645_623_296
    assert c["reckoning"]["state_bytes"] == 16 * count


@pytest.mark.parametrize("key,value,needle", [
    ("use_sliding_window", True, "use_sliding_window"),
    ("mlp_only_layers", [1], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("tie_word_embeddings", True, "tied output head"),
])
def test_table_from_config_refuses_by_key(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_from_config(dict(config(), **{key: value}))


@pytest.mark.parametrize("row", [
    LayerSpec(mixer="attention", window=8, n_heads=4),
    LayerSpec(mixer="none", ffn="gelu"),
])
def test_the_tables_statement_is_held_to_what_has_the_mask(row):
    if row.mixer == "none":         # an FFN-only row has no mask to have
        assert BlockTable(layers=(row,), block_diffusion=4)
        with pytest.raises(ValueError, match="block_diffusion"):
            BlockTable(layers=(row,), block_diffusion=0)
        return
    with pytest.raises(ValueError, match="block_diffusion"):
        BlockTable(layers=(row,), block_diffusion=4)


def test_the_model_takes_the_rows_positions_and_refuses_the_rest():
    c = config()
    lm = model(c, dtype=jnp.float32)
    x0, xt, w = batch(1)
    rows = jnp.concatenate([x0, xt], axis=1)
    at = jnp.tile(jnp.arange(L), 2)
    params = weights_sdar_moe.make(c, 3)
    # a table under the mask is handed its positions: none is an error
    with pytest.raises(ValueError, match="0 .. L-1 twice"):
        lm.apply({"params": params}, rows)
    with pytest.raises(ValueError, match=r"one \(S,\) array"):
        lm.apply({"params": params}, rows,
                 position_offset=jnp.zeros((2, 2 * L), jnp.int32))
    out = lm.apply({"params": params}, rows, position_offset=at)
    assert out.shape == (2, 2 * L, VOCAB)
    # the serving paths yield a token a step, not a block
    with pytest.raises(ValueError, match="iterative unmasking"):
        model(c, decode=True, max_len=2 * L).init(
            jax.random.PRNGKey(0), rows, position_offset=at)
    # the refusal stays for the mixers that read the token before
    from chainermn_tpu.models.block_table import SSMSpec

    ssm = BlockTable(layers=(LayerSpec(
        mixer="mamba2", norm="rmsnorm", ffn="none", ssm=SSMSpec(
            d_state=8, n_heads=2, d_head=16)),), positions="rotary",
        final_norm="rmsnorm")
    with pytest.raises(ValueError, match="read the token before"):
        TransformerLM(vocab=VOCAB, d_model=D_MODEL, table=ssm).init(
            jax.random.PRNGKey(0), rows, position_offset=at)


def test_a_plain_rotary_table_turns_by_the_positions_it_is_handed():
    """A scalar offset is the first token's position; an explicit array
    as it is; none is 0 .. S-1 as before."""
    c = config()
    table = dataclasses.replace(table_of(c), block_diffusion=None)
    lm = TransformerLM(vocab=VOCAB, d_model=D_MODEL, table=table,
                       dtype=jnp.float32)
    params = weights_sdar_moe.make(c, 3)
    x = jnp.asarray(batch(2)[0])
    plain = lm.apply({"params": params}, x)
    np.testing.assert_array_equal(
        plain, lm.apply({"params": params}, x,
                        position_offset=jnp.arange(L)))
    np.testing.assert_array_equal(
        plain, lm.apply({"params": params}, x, position_offset=0))
    # rotary attention sees differences of positions only
    np.testing.assert_allclose(
        plain, lm.apply({"params": params}, x, position_offset=7),
        atol=2e-5)
    moved = lm.apply({"params": params}, x,
                     position_offset=jnp.arange(L)[::-1])
    assert float(jnp.abs(moved - plain).max()) > 1e-3


# ------------------------------------------- the model against the reference

def both_sides(held, flash):
    """Noisy-row logits, loss and gradients of the program (float32,
    ``highest``; ``flash``: through the flash adapter in interpret mode at
    blocks of 8, so that the mask's walk crosses tiles, else the dense
    masked path) and of the reference on one seeded tree, and one AdamW
    step of each from there."""
    c = config(held=held)
    params = weights_sdar_moe.make(c, 2**31 + 11)
    x0, xt, w = batch(5)
    lm = model(c, dtype=jnp.float32, remat=True,
               attention_fn=make_flash_attention_fn(
                   causal=True, block_q=8, block_k=8) if flash else None)

    def noisy_logits(p):
        return lm.apply({"params": p}, jnp.concatenate([x0, xt], axis=1),
                        position_offset=jnp.tile(jnp.arange(L), 2))[:, L:]

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: program_loss(lm, p, x0, xt, w))(params)
        got = (noisy_logits(params), loss, grads)
        ref_sum, ref_grads = jax.value_and_grad(reference.loss_sum)(
            params, x0, xt, w, c)
        n = float(x0.size)
        want = (reference.noisy_logits(params, x0, xt, c), ref_sum / n,
                jax.tree.map(lambda g: g / n, ref_grads))
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
    # Both sides are float32 at ``highest`` but for the fused loss's
    # bfloat16 logit products (``fused_ce._chunk_logits``): the logits
    # are compared from the model's own float32 head, the loss at the
    # bfloat16 product's rounding.  A bfloat16 matrix product in the
    # reference's place moves the logits by 1e-2 (the control below).
    (logits, loss, _), (ref, ref_loss, _) = sides
    np.testing.assert_allclose(logits, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-3)


def _leaves():
    return [weights.leaf_name(p) for p in sorted(
        weights_sdar_moe.shapes(config()))]


@pytest.mark.parametrize("leaf", _leaves())
def test_program_gradient_matches_the_reference(sides, leaf):
    # Every leaf below the head: rtol 1e-2 with an absolute floor of 1e-2
    # of the leaf's largest entry — the fused loss rounds its logits'
    # operands and ``dlogits`` to bfloat16 (three vocabulary products a
    # chunk), so the cotangent that enters the float32 model carries
    # 2^-8; the broken paths below move a leaf by tenths.
    (_, _, grads), (_, _, ref_grads) = sides
    got = weights.flatten(grads)[tuple(leaf.split("/"))]
    want = weights.flatten(ref_grads)[tuple(leaf.split("/"))]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2 * scale)


def test_one_adamw_step_matches_the_references(all_held_flash):
    """The program's optimizer (``optax.adamw`` under
    ``create_multi_node_optimizer`` in the cell) on the program's
    gradient against the reference's own AdamW on its own: every leaf's
    change by its norm and its direction.  AdamW's first step is ``lr x
    sign(g)`` an entry where ``|g| >> eps``, so an entry whose gradient
    the two sides round to either side of zero moves the other way: a
    few in a thousand, which the cosine takes."""
    import optax

    (_, _, grads), (_, _, ref_grads) = all_held_flash
    c = config()
    start = weights_sdar_moe.make(c, 2**31 + 11)
    opt = optax.adamw(OPT["learning_rate"], b1=OPT["b1"], b2=OPT["b2"],
                      eps=OPT["eps"], weight_decay=OPT["weight_decay"])
    updates, _ = opt.update(grads, opt.init(start), start)
    zeros = jax.tree.map(jnp.zeros_like, start)
    want, _, _ = _adamw(
        weights_sdar_moe.make(c, 2**31 + 11), zeros,
        jax.tree.map(jnp.zeros_like, start), ref_grads, 1.0,
        OPT["learning_rate"], OPT["weight_decay"], OPT["b1"], OPT["b2"],
        OPT["eps"])
    moved = weights.flatten(jax.tree.map(jnp.subtract, want, start))
    for path, got in weights.flatten(updates).items():
        ref = moved[path]
        norm, ref_norm = jnp.linalg.norm(got), jnp.linalg.norm(ref)
        assert abs(float(norm / ref_norm) - 1.0) < 1e-2, path
        assert float(jnp.vdot(got, ref) / (norm * ref_norm)) > 0.98, path


def test_the_seeded_tree_is_the_programs_tree():
    c = config(held=(2, 4))
    rows = jnp.zeros((1, 2 * L), jnp.int32)
    shapes = jax.eval_shape(lambda: model(c).init(
        jax.random.PRNGKey(0), rows,
        position_offset=jnp.tile(jnp.arange(L), 2)))["params"]
    assert {p: v.shape for p, v in weights.flatten(shapes).items()} == (
        weights_sdar_moe.shapes(c))


def test_the_programs_choices_are_the_references():
    c = config()
    params = weights_sdar_moe.make(c, 2**31 + 11)
    x0, xt, _ = batch(5)
    rows = np.concatenate([x0, xt], axis=1)
    lm = model(c, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, seen = lm.apply({"params": params}, rows,
                           position_offset=jnp.tile(jnp.arange(L), 2),
                           mutable=["intermediates"])
        want = reference.chosen_experts(params, rows, c)
    assert sorted(want) == ["layer_0", "layer_1"]
    for name, mask in want.items():
        chosen = seen["intermediates"][name]["ExpertLayer_0"]["chosen"][0]
        assert chosen.shape == (2 * 2 * L, 3)     # every row of [x0 ; xt]
        got = np.zeros(mask.shape, bool).reshape(-1, 8)
        np.put_along_axis(got, np.asarray(chosen), True, axis=-1)
        np.testing.assert_array_equal(got.reshape(mask.shape), mask)


# ------------------------- what tells the program from a broken objective

@pytest.fixture(scope="module")
def program_side():
    """The float32 program on one seeded tree and batch: noisy-row
    logits, loss, gradients — and its flash adapter's output on seeded
    ``q``, ``k``, ``v`` under the mask, as the cell's probe reads it."""
    c = config()
    params = weights_sdar_moe.make(c, 2**31 + 13)
    x0, xt, w = batch(7)
    lm = model(c, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: program_loss(lm, p, x0, xt, w))(params)
        logits = lm.apply(
            {"params": params}, jnp.concatenate([x0, xt], axis=1),
            position_offset=jnp.tile(jnp.arange(L), 2))[:, L:]
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    qkv = tuple(jax.random.normal(k, (2 * L, h, 16))
                for k, h in zip(keys, (4, 2, 2)))
    attend = make_flash_attention_fn(causal=True, block_q=16, block_k=16)
    ctx = attend(*(a[None] for a in qkv), None, block_diffusion=B)[0]
    return c, params, (x0, xt, w), (logits, loss, grads), qkv, ctx


def _gaps(program_side, ref_kw):
    """The widest gaps of the program against the reference under
    ``ref_kw``: (noisy-row logits, loss, worst gradient leaf by its
    largest entry, the attention probe's worst row)."""
    c, params, (x0, xt, w), (logits, loss, grads), qkv, ctx = program_side
    n = float(x0.size)
    with jax.default_matmul_precision("highest"):
        ref_logits = reference.noisy_logits(params, x0, xt, c, **ref_kw)
        ref_sum, ref_grads = jax.value_and_grad(reference.loss_sum)(
            params, x0, xt, w, c, **ref_kw)
        ref_ctx = reference.masked_softmax(*qkv, B, **ref_kw)
    flat, ref_flat = weights.flatten(grads), weights.flatten(ref_grads)
    worst = max(float(jnp.abs(flat[p] - ref_flat[p] / n).max()
                      / jnp.abs(ref_flat[p] / n).max()) for p in ref_flat)
    rows = jnp.sqrt(jnp.sum(jnp.square(ctx - ref_ctx), axis=(1, 2))
                    / jnp.sum(jnp.square(ref_ctx), axis=(1, 2)))
    return (float(jnp.abs(logits - ref_logits).max()
                  / jnp.abs(ref_logits).max()),
            abs(float(loss) - float(ref_sum) / n) / (float(ref_sum) / n),
            worst, float(rows.max()))


@pytest.mark.parametrize("what", ["sound", "bfloat16"] + list(
    reference.BROKEN))
def test_a_broken_objective_moves_a_compared_number(program_side, what):
    """The tolerances of the comparison above, written with their
    reasons: logits 2e-4 (float32 sums in another order), loss 2e-3 and
    gradients 1e-2 (the fused loss's bfloat16 products), the attention
    probe's worst row 1e-3 (float32 kernels against a float32 softmax).
    A reference whose matrix products are bfloat16, and each statement of
    the objective broken in the reference, moves one past its tolerance.
    A clean row that sees noisy keys moves the PROBE alone, by order one:
    a branch is a hundredth of the stream under seeded weights, and what
    a clean row attends reaches a noisy row's loss only through a later
    layer's keys and values, below every other tolerance (8.5e-6, 3.7e-6,
    7e-3 here) — which is why the cell's comparison holds the timed
    step's first attention row to the reference's, row by row
    (``chipbench/runners/train_bd_moe.worst_row_gap``)."""
    kw = {} if what == "sound" else (
        {"precision": "bfloat16"} if what == "bfloat16"
        else {"broken": what})
    logits, loss, grad, probe = _gaps(program_side, kw)
    past = (logits > 2e-4, loss > 2e-3, grad > 1e-2, probe > 1e-3)
    if what == "sound":
        assert not any(past), (logits, loss, grad, probe)
    else:
        assert any(past), (logits, loss, grad, probe)
    if what in ("no_weight", "shifted"):
        assert past[1] and past[2] and not (past[0] or past[3])
    if what == "clean_sees_noisy":
        assert past == (False, False, False, True) and probe > 0.3
    if what == "own_clean_block":
        assert probe > 0.3 and past[2]
    if what == "positions_2L":
        assert past[2] and not past[3]      # the rotation, not the mask


# --------------------------------------------- the share and the whole layer

def test_eight_shares_add_up_to_the_uncut_layer():
    """What ties one rank's share to the model: at 16 experts the eight
    ranks' layers, two experts each, add up to the reference's layer with
    all sixteen (there is no shared expert to count once), under the
    mask, at the handed positions."""
    whole = config(held=(0, 16), n_layer=1, num_experts_published=16)
    params = weights_sdar_moe.make(whole, 2**31 + 5)["layer_0"]
    e = params["ExpertLayer_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 2 * L, D_MODEL))
    at = jnp.tile(jnp.arange(L), 2)
    stacks = ("experts_gate", "experts_up", "experts_down")

    def block(c, p):
        return Block(D_MODEL, table_of(c).layers[0], jnp.float32,
                     block_diffusion=B).apply(
            {"params": p}, x, None, positions=at)

    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.layer(row, params, whole, "float32")
                          for row in x])
        base, total = None, 0.0
        for first in range(0, 16, 2):
            c = config(held=(first, 2), n_layer=1, num_experts_published=16)
            share = dict(e, **{k: e[k][first:first + 2] for k in stacks})
            out = block(c, dict(params, ExpertLayer_0=share))
            if base is None:    # x + attention, no expert
                hollow = dict(e, **{k: jnp.zeros_like(share[k])
                                    for k in stacks})
                base = block(c, dict(params, ExpertLayer_0=hollow))
            total = total + out - base
    np.testing.assert_allclose(total + base, want, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------- the noising

def test_the_noising_masks_a_block_at_its_own_level():
    rng = np.random.default_rng(11)
    x0 = rng.integers(0, 999, size=(64, 512)).astype(np.int32)
    xt, w = noise_batch(x0, 4, 999, np.random.default_rng(12))
    masked = xt == 999
    assert xt.dtype == x0.dtype and w.dtype == np.float32
    assert ((xt == x0) | masked).all() and (masked == (w > 0)).all()
    # one level a block: the masked rows of a block share their weight
    blocks = w.reshape(64, 128, 4)
    level = blocks.max(axis=-1, keepdims=True)
    assert ((blocks == 0) | (blocks == level)).all()
    assert 1.0 <= w[masked].min() and w.max() <= 1e3
    # t uniform on [1e-3, 1]: half the rows masked; a block at level t
    # masks a share t of its rows (read where the level is known: a block
    # with a masked row), and E[w] = 1 a row
    assert abs(masked.mean() - 0.5) < 0.01
    assert abs(w.mean() - 1.0) < 0.05
    t = 1.0 / level[level > 0]
    assert abs(np.corrcoef(t, (blocks > 0).mean(-1)[level[..., 0] > 0])[
        0, 1]) > 0.6


def test_the_same_seed_gives_the_same_batch_and_the_benchmarks_copy_agrees():
    x0 = np.random.default_rng(1).integers(0, 90, size=(3, 64)).astype(
        np.int32)
    a = noise_batch(x0, 4, 95, np.random.default_rng(7))
    b = noise_batch(x0, 4, 95, np.random.default_rng(7))
    other = noise_batch(x0, 4, 95, np.random.default_rng(8))
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    assert not np.array_equal(a[0], other[0])
    # chipbench/traffic_bd.py's own numpy lines make the same draw
    ours = traffic_bd.noise(x0, 4, 95, np.random.default_rng(7), 1e-3)
    assert all(np.array_equal(p, q) for p, q in zip(a, ours))
    mix = {"global_batch": 2, "seq_len": 64, "sampling_eps": 1e-3,
           "token_dist": {"name": "zipf", "s": 1.0}}
    c = config()
    one = traffic_bd.train_batches(mix, c, 2**31 + 5)(3)
    two = traffic_bd.train_batches(mix, c, 2**31 + 5)(3)
    assert all(np.array_equal(p, q) for p, q in zip(one, two))
    assert one[0].max() < VOCAB - 1 and (one[1] == VOCAB - 1).any()
    with pytest.raises(ValueError, match="whole number of blocks"):
        noise_batch(x0[:, :62], 4, 95, np.random.default_rng(7))


def test_the_loss_hands_back_its_counters():
    c = config()
    lm = model(c, dtype=jnp.float32)
    params = weights_sdar_moe.make(c, 3)
    x0, xt, w = batch(9)
    _, counters = block_diffusion_loss(
        lambda t, at: lm.apply({"params": params}, t, position_offset=at,
                               return_hidden=True),
        params["lm_head"], x0, xt, w, chunk=16)
    assert int(counters["masked_rows"]) == int((xt != x0).sum())
    assert float(counters["weight_sum"]) == pytest.approx(float(w.sum()))


# ---------------------------------------------------------------- the scope

def test_the_blockdiff_scope_is_on_the_rows_ops():
    from chainermn_tpu.observability import device_trace

    assert "attn-blockdiff" in spans.MODEL_PARTS
    assert spans.is_scope("attn-blockdiff")
    assert not spans.is_region("attn-blockdiff")
    c = config()
    lm = model(c, remat=True, attention_fn=make_flash_attention_fn(
        causal=True, block_q=16, block_k=16))
    params = weights_sdar_moe.make(c, 3)
    x0, xt, w = batch(9)
    with spans.named_scope("fwd-bwd"):
        pass
    text = jax.jit(jax.grad(lambda p: program_loss(
        lm, p, x0, xt, w))).lower(params).compile().as_text()
    table = device_trace.scope_table(text)
    on = set()
    for path in table.values():
        on |= set(device_trace.scopes_on(path))
    assert "attn-blockdiff" in on
    assert not on & {"attn-mixer", "attn-window"}
    under = {device_trace.owner(p)[1] for p in table.values()
             if "attn-blockdiff" in device_trace.scopes_on(p)}
    assert {"flash-fwd", "flash-bwd-dkv", "attn-rope",
            "mixer-proj"} <= under
    assert "flash-bwd-dq" not in under      # the backward is one pass
    # the flash calls under the scope carry the mask's census
    (census,) = table.tiles_within["attn-blockdiff"]["flash-fwd"]
    # (the five fields the path spells; the sinks get ``cut`` and
    # ``halved`` beside them)
    whole = fa.tile_census(2 * L, 2 * L, 16, 16, True, None, (L, B))["fwd"]
    assert census == {field: whole[field] for field in spans.TILE_FIELDS}
    assert census["live"] == census["visited"] == 8
