"""Both rows of the ``mellum2-train-1chip`` cell's period, forward and backward under
remat as in the step, compiled for a described TPU v5e
(``tests/_tpu_compile.py``), without the chip.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_mellum_layers_compile_at_the_cells_shape(one_chip, monkeypatch,
                                                  kind):
    """Both rows of the ``mellum`` period at the cell's shape (1 x 16,384
    tokens, GQA 32/4 at D = 128, 8 of 64 gated experts of 896 held: a
    buffer of 65,536 rows), forward and backward under remat with the
    model's policy as in the step: the three flash calls (the forward
    ONCE) at the tiles ``auto_block_size`` picks — 1024-edge without a
    window and in the forward under the row's window of 1024, 512-edge
    in the backward under it; the window reaches the kernels from the
    row, the adapter was given none — and nine grouped calls of the
    experts; the windowed row's census is the band's 31 tiles (93 at
    512) in a grid of 32 (96) steps a head row (its grid is the band,
    PR 40), the full row's the triangle's 136 of 256."""
    from chainermn_tpu.models.block_table import (
        ExpertsSpec,
        LayerSpec,
        YarnSpec,
    )
    from chainermn_tpu.models.transformer import Block, remat_policy
    from chainermn_tpu.observability import device_trace
    from chainermn_tpu.ops import make_flash_attention_fn

    gm = importlib.import_module("chainermn_tpu.ops.grouped_matmul")
    for module in (fa, gm):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    for which in ("fwd", "bwd"):
        assert fa.auto_block_size(16384, 128, jnp.bfloat16, which) == 1024
    assert fa.auto_block_size(16384, 128, jnp.bfloat16, "fwd",
                              window=1024) == 1024
    assert fa.auto_block_size(16384, 128, jnp.bfloat16, "bwd",
                              window=1024) == 512
    row = LayerSpec(
        mixer="attention", norm="rmsnorm", ffn="experts", n_heads=32,
        n_kv_heads=4, d_head=128, rotary_dim=128, rope_theta=5e5,
        qk_norm=True, window=1024 if kind == "sliding" else None,
        yarn=None if kind == "sliding" else YarnSpec(16.0, 8192),
        experts=ExpertsSpec(n_experts=64, top_k=8, d_expert=896, d_shared=0,
                            held=(0, 8), router="softmax", expert="swiglu"))
    layer = Block(2304, row, jnp.bfloat16,
                  make_flash_attention_fn(causal=True))

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arr((1, 16384, 2304), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, 2304), jnp.bfloat16))))

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
    # (one backward pass, under flash-bwd-dkv's name)
    assert calls == {"flash-fwd": 1, "flash-bwd-dq": 0, "flash-bwd-dkv": 1}
    assert text.count("tpu_custom_call") == 2 + 9
    scope = "attn-window" if kind == "sliding" else "attn-mixer"
    tiles = device_trace.scope_table(text).tiles_within
    assert set(tiles) >= {scope} and not (
        {"attn-window", "attn-mixer"} - {scope}) & set(tiles)
    assert "flash-bwd-dq" not in tiles[scope]
    for region in ("flash-fwd", "flash-bwd-dkv"):
        (census,) = tiles[scope][region]
        # a sliding row's grid is its band (PR 40): one step a query
        # block more than live, the backward's tiles at half the window;
        # the full row's is the rectangle
        edge = 512 if kind == "sliding" and region != "flash-fwd" else 1024
        assert (census["block_q"], census["block_k"]) == (edge, edge)
        assert (census["live"], census["visited"]) == (
            (136, 256) if kind == "full"
            else (31, 32) if edge == 1024 else (93, 96))
    # read: 1.67 GB, either row (a window saves time, not memory)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
