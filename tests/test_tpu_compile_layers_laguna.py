"""The ``laguna-train-1chip`` cell's two row shapes, forward and backward
under remat as in the step, and its WHOLE step, compiled for a described
TPU v5e (``tests/_tpu_compile.py``) at the published widths, without the
chip.  The whole step's ``memory_analysis()`` is what settles the cell's
``seq_len`` (the issue's rule: 8,192 tokens where arguments + temporaries
+ code hold 15.0 GB or less, else 4,096).
"""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
gm = importlib.import_module("chainermn_tpu.ops.grouped_matmul")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 8192


def cell():
    with open(os.path.join(
            ROOT, "chipbench/configs/laguna-s-2.1-train.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench/traffic/gateswa-b1.json")) as f:
        return config, json.load(f)


def test_the_window_rule_at_the_cells_shapes():
    assert cell()[1]["seq_len"] == S
    for which in ("fwd", "bwd"):
        assert fa.auto_block_size(S, 128, jnp.bfloat16, which) == 1024
        assert fa.auto_block_size(S, 128, jnp.bfloat16, which,
                                  window=512) == 512
    # 72 and 48 query head rows over 8: groups of 9 and 6
    assert (fa._kv_group(72, 8), fa._kv_group(48, 8)) == (9, 6)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_laguna_rows_compile_at_the_cells_shape(one_chip, monkeypatch,
                                                kind):
    """The two row SHAPES of the table at the cell's size (1 x 8,192
    tokens, hidden 3072, D = 128), each as the table builds it, forward
    and backward under remat with the model's policy: layer 1 — 72 query
    heads over 8 under a window of 512, plain rotation, the gate a head,
    8 of 256 gated experts of 1,024 held beside the shared expert — and
    layer 0 — 48 over 8, the triangle, YaRN on half the head, the gate,
    the dense SwiGLU of 12,288.  Two flash calls a row (the forward ONCE,
    the backward one pass) at the tiles ``auto_block_size`` picks: 512
    edges under the window, the band's 31 live tiles in a grid of 32
    steps a head row; 1024 edges over the triangle, 36 of 64."""
    from chainermn_tpu.models.block_table import table_from_config
    from chainermn_tpu.models.transformer import Block, remat_policy
    from chainermn_tpu.observability import device_trace
    from chainermn_tpu.ops import make_flash_attention_fn

    for module in (fa, gm):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    config, _ = cell()
    table = table_from_config(
        dict(config, num_experts=config["num_experts_published"]),
        n_layers=2, experts_held=(0, 8))
    row = table.layers[1 if kind == "sliding" else 0]
    assert (row.n_heads, row.window, row.rotary_dim, row.head_gate) == (
        (72, 512, 128, True) if kind == "sliding" else (48, None, 64, True))
    layer = Block(3072, row, jnp.bfloat16,
                  make_flash_attention_fn(causal=True))

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arr((1, S, 3072), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, 3072), jnp.bfloat16))))

    def loss(params, x):
        fn = jax.checkpoint(lambda p, x: layer.apply(p, x),
                            policy=remat_policy())
        return jnp.sum(fn(params, x).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(
        r'tpu_custom_call[^\n]*' + name + r'\b', text))
        for name in ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")}
    assert calls == {"flash-fwd": 1, "flash-bwd-dq": 0, "flash-bwd-dkv": 1}
    # (nine grouped calls of the held experts in the sparse row)
    assert text.count("tpu_custom_call") == 2 + (
        9 if kind == "sliding" else 0)
    scope = "attn-window" if kind == "sliding" else "attn-mixer"
    table = device_trace.scope_table(text)
    tiles = table.tiles_within
    assert set(tiles) >= {scope} and not (
        {"attn-window", "attn-mixer"} - {scope}) & set(tiles)
    for region in ("flash-fwd", "flash-bwd-dkv"):
        (census,) = tiles[scope][region]
        edge = 512 if kind == "sliding" else 1024
        assert (census["block_q"], census["block_k"]) == (edge, edge)
        assert (census["live"], census["visited"]) == (
            (31, 32) if kind == "sliding" else (36, 64))
    # the gate a head is traced inside the row's scope
    assert any({scope, "mixer-gate"} <= set(device_trace.scopes_on(path))
               for path in table.values())
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_the_whole_step_compiles_and_settles_seq_len(one_chip, monkeypatch):
    """The cell's own job — ``GswaMoeJob``: ``create_communicator`` ->
    ``create_multi_node_optimizer`` -> ``make_train_step`` with the aux
    outputs — on the described chip with abstract parameters, state and
    batch at 8,192 tokens: five layers, two flash calls each, 4 x 9
    grouped calls; and what the compiler counts of its memory is within
    the rule's 15.0 GB (read: 9.73 GB of arguments, 2.95 of temporaries,
    0.24 of code) and is what the configuration file says."""
    from chipbench.runners import train_gswa_moe

    for module in (fa, gm):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    config, mix = cell()
    device = next(iter(one_chip.device_set))
    job = train_gswa_moe.GswaMoeJob(config, mix, [device])
    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=job.rows)
    job.feed = lambda index: (tokens, tokens)
    compiled = job.lowered().compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(
        r'tpu_custom_call[^\n]*' + name + r'\b', text))
        for name in ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")}
    assert calls == {"flash-fwd": 5, "flash-bwd-dq": 0, "flash-bwd-dkv": 5}
    assert text.count("tpu_custom_call") == 10 + 4 * 9
    found = compiled.memory_analysis()
    total = (found.argument_size_in_bytes + found.temp_size_in_bytes
             + found.generated_code_size_in_bytes)
    assert total <= 15.0e9
    said = config["reckoning"]["compiled_step"]
    assert said["seq_len"] == mix["seq_len"] == S
    assert found.argument_size_in_bytes == pytest.approx(
        said["argument_bytes"], rel=0.01)
    assert found.temp_size_in_bytes == pytest.approx(
        said["temporary_bytes"], rel=0.05)
    # arguments: the parameters and AdamW's two moments, float32
    assert found.argument_size_in_bytes == pytest.approx(
        12 * config["reckoning"]["total"], rel=0.001)
