"""The per-layer block table, the Mamba-2 mixer and its chunked scan,
against the plain reference the benchmark compares with on the chip
(``chipbench/refs/granite_hybrid.py``: the recurrence step by step, none of
the program's code) and against the recurrence itself."""

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
    gpt2_table,
    table_from_config,
)
from chainermn_tpu.models.transformer import (  # noqa: E402
    Block,
    TransformerLM,
    causal_mask,
)
from chainermn_tpu.ops.ssd import causal_conv_silu, ssd_scan  # noqa: E402
from chipbench import weights, weights_hybrid  # noqa: E402
from chipbench.refs import granite_hybrid as reference  # noqa: E402

PERIOD = ["mamba", "mamba", "attention", "mamba"]


def config(layer_types=PERIOD, vocab=96, **over):
    """A ``granitemoehybrid`` config at toy widths, keys as published."""
    c = {
        "model_type": "granitemoehybrid", "attention_bias": False,
        "attention_multiplier": 0.125, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
        "layer_types": list(layer_types), "logits_scaling": 8,
        "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 8, "mamba_d_state": 16, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 8, "mamba_proj_bias": False,
        "normalization_function": "rmsnorm", "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": len(layer_types),
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": True, "vocab_size": vocab,
        "n_layer": len(layer_types),
    }
    c.update(over)
    return c


def tokens(seed, batch, length, vocab):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0,
                              vocab)


# ------------------------------------------------------- program vs reference

@pytest.fixture(scope="module")
def both_sides():
    """Loss, logits and gradients of the program (float32, ``highest``)
    and of the reference on one seeded tree."""
    c = config()
    params = weights_hybrid.make(c, 2**31 + 11)
    toks = tokens(1, 2, 33, c["vocab_size"])
    x, y = toks[:, :-1], toks[:, 1:]
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table_from_config(c), dtype=jnp.float32,
                       remat=True)

    def program_loss(p):
        z = lm.apply({"params": p}, x)
        picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - picked)

    with jax.default_matmul_precision("highest"):
        got = (lm.apply({"params": params}, x),
               *jax.value_and_grad(program_loss)(params))
        want = (
            reference.logits(params, reference.layers(
                params, reference.embed(params, x, c), c), c),
            *jax.value_and_grad(reference.loss_sum)(params, x, y, c))
    return got, want


def test_program_logits_and_loss_match_the_reference(both_sides):
    (logits, loss, _), (ref_logits, ref_loss, _) = both_sides
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def _leaves():
    return [weights.leaf_name(p) for p in sorted(
        weights_hybrid.shapes(config()))]


@pytest.mark.parametrize("leaf", _leaves())
def test_program_gradient_matches_the_reference(both_sides, leaf):
    (_, _, grads), (_, _, ref_grads) = both_sides
    got = weights.flatten(grads)[tuple(leaf.split("/"))]
    want = weights.flatten(ref_grads)[tuple(leaf.split("/"))]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-5 * scale)


def test_return_hidden_folds_the_logits_scaling(both_sides):
    c = config()
    params = weights_hybrid.make(c, 2**31 + 11)
    x = tokens(1, 2, 33, c["vocab_size"])[:, :-1]
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table_from_config(c), dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = lm.apply({"params": params}, x, return_hidden=True)
        z = jnp.einsum("bsd,vd->bsv", h, params["embed"]["embedding"])
    np.testing.assert_allclose(z, both_sides[0][0], rtol=1e-5, atol=1e-6)


# ------------------------------------------- chunked scan vs the recurrence

#: ``base``: the first cases'; ``one_group``: the granite cell's geometry
#: cut small (every head shares one B and C); ``groups``: the nemotron
#: cell's (several groups of a few heads); ``runs``: more heads a group
#: than a grid step holds, so the rule splits them into runs.
_GEOMETRIES = {
    "base": dict(H=4, P=8, G=2, N=16),
    "one_group": dict(H=8, P=8, G=1, N=16),
    "groups": dict(H=8, P=8, G=4, N=16),
    "runs": dict(H=32, P=8, G=1, N=8),
}


def _scan_inputs(seed, b=2, S=32, H=4, P=8, G=2, N=16, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (b, S, H, P)).astype(dtype),
        dt=0.3 * jax.nn.softplus(jax.random.normal(k[1], (b, S, H))),
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (b, S, G, N)).astype(dtype),
        C=jax.random.normal(k[4], (b, S, G, N)).astype(dtype),
        D=jax.random.normal(k[5], (H,)),
    ), jax.random.normal(k[6], (b, S, H, P))


def _recurrence(x, dt, A, B, C, D):
    f32 = jnp.float32
    return jax.vmap(reference.recurrence, in_axes=(0, 0, None, 0, 0, None))(
        x.astype(f32), dt, A, B.astype(f32), C.astype(f32), D)


def _close(got, want, dtype):
    """float32: to rounding.  bfloat16 (the cells' precision: operands of
    the four products rounded, sums float32): to a few roundings of the
    largest term."""
    scale = float(jnp.max(jnp.abs(want)))
    rtol, atol = (1e-4, 1e-5) if dtype == jnp.float32 else (2e-2, 2e-2)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=rtol, atol=atol * scale)


_scan_cases = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])


@_scan_cases
@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
@pytest.mark.parametrize("chunk", [8, 32], ids=["blocks4", "block1"])
def test_chunked_scan_matches_the_recurrence_forward(chunk, geometry, dtype):
    args, _ = _scan_inputs(0, dtype=dtype, **_GEOMETRIES[geometry])
    with jax.default_matmul_precision("highest"):
        _close(ssd_scan(**args, chunk=chunk), _recurrence(**args), dtype)


@_scan_cases
@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
@pytest.mark.parametrize("name", ["x", "dt", "A", "B", "C", "D"])
@pytest.mark.parametrize("chunk", [8, 32], ids=["blocks4", "block1"])
def test_chunked_scan_matches_the_recurrence_gradient(chunk, name, geometry,
                                                      dtype):
    args, w = _scan_inputs(1, dtype=dtype, **_GEOMETRIES[geometry])

    def grad_of(fn):
        return jax.grad(lambda a: jnp.sum(
            w * fn(**{**args, name: a}).astype(jnp.float32)))(args[name])

    with jax.default_matmul_precision("highest"):
        got = grad_of(lambda **a: ssd_scan(**a, chunk=chunk))
        want = grad_of(_recurrence)
    assert got.dtype == args[name].dtype
    _close(got, want, dtype)


@_scan_cases
@pytest.mark.parametrize("name", ["y", "x", "dt", "A", "B", "C", "D"])
def test_chunked_scan_tiles_a_block_wider_than_the_lanes(name, dtype):
    """Chunk 256 (the granite cell's): a block's ``Q x Q`` matrices in
    2 x 2 tiles of 128 — the tile before the diagonal takes its decay as
    two factors on the product's operand and result, the one past it is
    never made.  Two blocks, so the state crosses one.  ``y``, then the
    gradient by operand."""
    args, w = _scan_inputs(3, b=1, S=512, dtype=dtype, **_GEOMETRIES["base"])

    def of(fn):
        if name == "y":
            return fn(**args)
        return jax.grad(lambda a: jnp.sum(
            w * fn(**{**args, name: a}).astype(jnp.float32)))(args[name])

    with jax.default_matmul_precision("highest"):
        _close(of(lambda **a: ssd_scan(**a, chunk=256)), of(_recurrence),
               dtype)


def test_scan_refuses_a_length_that_is_no_multiple_of_the_chunk():
    args, _ = _scan_inputs(0)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd_scan(**args, chunk=24)


@pytest.mark.parametrize("cell,groups,chunk,heads_a_step", [
    ("granite4hm-train-1chip", 1, 256, 16),
    ("nemo3nano-train-1chip", 8, 128, 8)])
def test_scan_tiles_of_the_cells_fit_the_default_vmem(cell, groups, chunk,
                                                      heads_a_step):
    """Both hybrid cells' scans (2 x 8192 tokens, 64 heads of 64, state
    128, bfloat16) run the kernels at tiles inside the scoped VMEM a
    kernel gets by default: one group's 64 heads in runs of 16, eight
    groups' 8 heads a run each."""
    from chainermn_tpu.ops.flash_attention import VMEM_SCOPED_DEFAULT
    from chainermn_tpu.ops.ssd import ssd_tiles

    hb, vmem = ssd_tiles(8192, chunk, 64, groups, 64, 128, jnp.bfloat16)
    assert hb == heads_a_step and vmem <= VMEM_SCOPED_DEFAULT


def test_scan_tiles_follow_the_shapes():
    from chainermn_tpu.ops.ssd import ssd_tiles

    # more heads a group than a step holds: runs of the longest divisor
    assert ssd_tiles(32, 8, 32, 1, 8, 8, jnp.float32)[0] == 16
    assert ssd_tiles(32, 8, 24, 2, 8, 8, jnp.float32)[0] == 12
    assert ssd_tiles(32, 8, 14, 2, 8, 8, jnp.float32)[0] == 7
    # float32 operands at the granite cell's shape: fewer heads fit
    hb, _ = ssd_tiles(8192, 256, 64, 1, 64, 128, jnp.float32)
    assert 64 % hb == 0 and hb < 16
    # what cannot be tiled raises by the rule: no other form takes over
    with pytest.raises(ValueError, match="bytes of VMEM"):
        ssd_tiles(8192, 2048, 64, 1, 64, 128, jnp.bfloat16)
    with pytest.raises(ValueError, match="do not divide"):
        ssd_tiles(32, 8, 6, 4, 8, 8, jnp.float32)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd_tiles(40, 16, 4, 2, 8, 8, jnp.float32)


def test_causal_conv_sees_no_later_token():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 6))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jnp.zeros((6,))
    y = causal_conv_silu(x, kernel, bias)
    bumped = causal_conv_silu(x.at[:, 7].add(1.0), kernel, bias)
    np.testing.assert_array_equal(y[:, :7], bumped[:, :7])
    assert not np.allclose(y[:, 7:11], bumped[:, 7:11])
    # tap K-1 weighs the current token: the first output sees x_0 alone
    np.testing.assert_allclose(
        y[0, 0], jax.nn.silu(x[0, 0] * kernel[3]), rtol=1e-6)


def test_scan_geometry_reaches_the_sinks(tmp_path):
    """A traced scan publishes its geometry once, beside the flash one."""
    import json

    from chainermn_tpu.observability import Reporter, step_log
    from chainermn_tpu.observability import reporter as reporter_mod

    args, _ = _scan_inputs(0)
    f = jax.jit(lambda a: ssd_scan(**a, chunk=16))
    rep = Reporter()
    path = str(tmp_path / "steps.jsonl")
    with reporter_mod.scope(rep), step_log.recording(path):
        f(args)
        f(args)                         # no retrace: no second record
    summary = rep.summary()
    assert summary["counters"]["ssd/calls"] == 1
    want = {"chunk": 16, "chunks": 2, "heads": 4, "d_head": 8,
            "d_state": 16, "groups": 2, "heads_a_step": 2, "grid_steps": 8}
    gauges = {n: g["value"] for n, g in summary["gauges"].items()}
    assert gauges.pop("ssd/vmem_bytes") > 0
    # the mechanism that engaged: both passes one Mosaic kernel
    assert gauges == {"ssd/kernel": 1,
                      **{f"ssd/{k}": v for k, v in want.items()}}
    rows = [json.loads(line) for line in open(path)]
    rows = [r for r in rows if r["event"] == "ssd_geometry"]
    assert len(rows) == 1 and {k: rows[0][k] for k in want} == want
    assert rows[0]["form"] == "kernel"


def test_conv_geometry_reaches_the_sinks(tmp_path):
    """A traced convolution publishes its geometry once, with the
    backward it compiles with: the tiles of the one-pass kernel."""
    import json

    from chainermn_tpu.observability import Reporter, step_log
    from chainermn_tpu.observability import reporter as reporter_mod

    f = jax.jit(jax.grad(
        lambda x, k, b: causal_conv_silu(x, k, b).sum(), argnums=(0, 1, 2)))
    args = (jnp.ones((3, 40, 200)), jnp.ones((4, 200)), jnp.zeros((200,)))
    rep = Reporter()
    path = str(tmp_path / "steps.jsonl")
    with reporter_mod.scope(rep), step_log.recording(path):
        f(*args)
        f(*args)                        # no retrace: no second record
    summary = rep.summary()
    assert summary["counters"]["ssm_conv/calls"] == 1
    # 40 tokens in one tile of 128 (a register of lanes), 200 channels in
    # four blocks of 64 rows, three batch rows
    want = {"seq": 40, "channels": 200, "taps": 4, "seq_tile": 128,
            "channel_tile": 64, "grid_steps": 12}
    gauges = {f"ssm_conv/{k}": v for k, v in want.items()}
    gauges["ssm_conv/one_pass"] = 1
    assert {n: g["value"] for n, g in summary["gauges"].items()} == gauges
    rows = [json.loads(line) for line in open(path)]
    rows = [r for r in rows if r["event"] == "conv_geometry"]
    assert len(rows) == 1 and rows[0]["backward"] == "one_pass"
    assert {k: rows[0][k] for k in want} == want


@pytest.mark.parametrize("with_lse,form", [
    (False, "grad_in_forward"), (True, "recompute")])
def test_loss_head_geometry_reaches_the_sinks(tmp_path, with_lse, form):
    """A traced loss head publishes its geometry once, with the gradient
    rule it carries: the loss-only path makes its gradients in the
    forward scan, the with-lse path recomputes its logits."""
    import json

    from chainermn_tpu.observability import Reporter, step_log
    from chainermn_tpu.observability import reporter as reporter_mod
    from chainermn_tpu.ops import fused_ce

    def loss(h, e, lab):
        if with_lse:
            return fused_ce.fused_cross_entropy_with_lse(
                h, e, lab, chunk=20)[0]
        return fused_ce.fused_cross_entropy(h, e, lab, chunk=20)

    f = jax.jit(jax.grad(loss, argnums=(0, 1)))
    args = (jnp.ones((2, 24, 8), jnp.bfloat16), jnp.ones((11, 8)),
            jnp.zeros((2, 24), jnp.int32))
    rep = Reporter()
    path = str(tmp_path / "steps.jsonl")
    with reporter_mod.scope(rep), step_log.recording(path):
        f(*args)
        f(*args)                        # no retrace: no second record
    summary = rep.summary()
    assert summary["counters"]["fused_ce/calls"] == 1
    # 48 rows in tiles of 16, the largest divisor under the chunk asked for
    want = {"rows": 48, "vocab": 11, "d": 8, "chunk": 16, "chunks": 3}
    gauges = {f"fused_ce/{k}": v for k, v in want.items()}
    gauges["fused_ce/grad_in_forward"] = float(form == "grad_in_forward")
    assert {n: g["value"] for n, g in summary["gauges"].items()} == gauges
    rows = [json.loads(line) for line in open(path)]
    rows = [r for r in rows if r["event"] == "ce_geometry"]
    assert len(rows) == 1 and rows[0]["form"] == form
    assert {k: rows[0][k] for k in want} == want


# ------------------------------------------- the GPT-2 row is today's block

CGPT_TINY = {"vocab_size": 211, "n_embd": 64, "n_head": 2, "n_inner": 128,
             "n_layer": 2, "n_positions": 128}
#: ``fused_cross_entropy`` of the tree below at commit 1fe2e6e (the parent
#: of the PR that made the block a table), on the CPU.
CGPT_TINY_LOSS = 5.359133720397949


def _cgpt_lm(**kw):
    return TransformerLM(vocab=211, d_model=64, n_heads=2, d_ff=128,
                         max_len=128, **kw)


def test_gpt2_row_keeps_the_parameter_tree():
    lm = _cgpt_lm(n_layers=2)
    tree = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    got = {p: leaf.shape for p, leaf in weights.flatten(tree).items()}
    assert got == weights.shapes(CGPT_TINY)


@pytest.mark.parametrize("how", ["fields", "table"])
def test_gpt2_row_keeps_the_loss_to_the_bit(how):
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    lm = (_cgpt_lm(n_layers=2) if how == "fields"
          else _cgpt_lm(table=gpt2_table(2, 2, 128)))
    p = weights.make(CGPT_TINY, 2**31 + 5)
    tok = jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, 211)
    h = lm.apply({"params": p}, tok[:, :-1], return_hidden=True)
    loss = fused_cross_entropy(h, p["embed"]["embedding"], tok[:, 1:],
                               chunk=64)
    assert float(loss) == CGPT_TINY_LOSS


# --------------------------- the chip's share, tied to the uncut 40 layers

class Stage(nn.Module):
    """One pipeline stage: a period of the table's layers on the residual
    stream (the repo's pipeline idiom, as ``examples/vit`` builds it)."""

    d_model: int
    rows: tuple

    @nn.compact
    def __call__(self, x):
        mask = causal_mask(x.shape[1])
        for i, row in enumerate(self.rows):
            x = Block(self.d_model, row, jnp.float32, name=f"layer_{i}")(
                x, mask)
        return x


def test_four_stages_and_four_quarter_tables_give_the_uncut_model():
    """The deployment the benchmark's cell is one chip of: four stages of
    one period each run in turn, the tied table in four vocabulary slices
    (each chip looks up the ids it holds and computes its slice of the
    logits), against the reference's uncut 40 layers and whole table."""
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    c = config(period * 4, vocab=64)
    params = weights_hybrid.make(c, 2**31 + 3)
    x = tokens(2, 1, 16, c["vocab_size"])
    table = table_from_config(c)
    E, quarter = params["embed"]["embedding"], c["vocab_size"] // 4
    with jax.default_matmul_precision("highest"):
        want_stream = reference.layers(
            params, reference.embed(params, x, c), c)
        want_logits = reference.logits(params, want_stream, c)

        stream = jnp.zeros(x.shape + (c["hidden_size"],))
        for j in range(4):              # vocabulary-parallel lookup
            local = x - j * quarter
            held = (local >= 0) & (local < quarter)
            rows = E[j * quarter:(j + 1) * quarter][
                jnp.clip(local, 0, quarter - 1)]
            stream = stream + jnp.where(held[..., None], rows, 0.0)
        stream = table.embedding_multiplier * stream
        for k in range(4):              # the four stages in turn
            stage = Stage(c["hidden_size"], table.layers[10 * k:10 * k + 10])
            stream = stage.apply({"params": {
                f"layer_{i}": params[f"layer_{10 * k + i}"]
                for i in range(10)}}, stream)
        h = nn.RMSNorm(epsilon=table.norm_eps).apply(
            {"params": params["final_norm"]}, stream)
        logits = jnp.concatenate([
            h @ E[j * quarter:(j + 1) * quarter].T / table.logits_scaling
            for j in range(4)], axis=-1)
    np.testing.assert_allclose(stream, want_stream, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------- the refusals

def test_the_serving_engine_refuses_a_block_table():
    from chainermn_tpu.serving.engine import InferenceEngine

    c = config()
    lm = TransformerLM(vocab=c["vocab_size"], d_model=c["hidden_size"],
                       table=table_from_config(c), max_len=64)
    with pytest.raises(ValueError, match="recurrent state"):
        InferenceEngine(lm, weights_hybrid.make(c, 1))


@pytest.mark.parametrize("key,value,needle", [
    ("num_local_experts", 8, "sparse experts"),
    ("position_embedding_type", "rope", "position_embedding_type"),
    ("model_type", "llama", "granitemoehybrid"),
    ("mamba_n_heads", 7, "mamba_expand"),
])
def test_table_from_config_refuses_what_it_cannot_build(key, value, needle):
    with pytest.raises(ValueError, match=needle):
        table_from_config(config(**{key: value}))


def test_a_layer_with_its_own_scale_refuses_a_mismatched_adapter():
    from chainermn_tpu.ops import make_flash_attention_fn

    c = config(["attention"])
    lm = TransformerLM(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        table=table_from_config(c),
        attention_fn=make_flash_attention_fn(causal=True))
    with pytest.raises(ValueError, match="softmax scale"):
        lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
