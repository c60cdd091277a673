"""A layer of the ``sdar30b-train-1chip`` cell, forward and backward under
remat as in the step, compiled for a described TPU v5e
(``tests/_tpu_compile.py``), without the chip: the three flash kernels
under the block-diffusion mask at the cell's ``(L, B)``, their grids the
scalar-prefetched walk of the mask's live tiles.
"""

import importlib
import re

import jax
import jax.numpy as jnp

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def test_an_sdar_layer_compiles_at_the_cells_shape(one_chip, monkeypatch):
    """One row of the table at the cell's shape (one document of 8,192
    tokens as 16,384 rows ``[clean ; noisy]``, GQA 32/4 at D = 128, 16 of
    128 gated experts of 768 held: a buffer of 65,536 rows), forward and
    backward under remat with the model's policy: the three flash calls
    (the forward ONCE) at the 1024-edge tiles ``auto_block_size`` picks
    under the mask, each a grid over the mask's 80 live tiles a head row
    and no dead one, traced under ``attn-blockdiff``; and nine grouped
    calls of the experts."""
    from chainermn_tpu.models.block_table import ExpertsSpec, LayerSpec
    from chainermn_tpu.models.transformer import Block, remat_policy
    from chainermn_tpu.observability import device_trace
    from chainermn_tpu.ops import make_flash_attention_fn

    gm = importlib.import_module("chainermn_tpu.ops.grouped_matmul")
    for module in (fa, gm):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    L, B = 8192, 4
    for which in ("fwd", "bwd"):
        assert fa.auto_block_size(2 * L, 128, jnp.bfloat16, which,
                                  blockdiff=(L, B)) == 1024
    row = LayerSpec(
        mixer="attention", norm="rmsnorm", ffn="experts", n_heads=32,
        n_kv_heads=4, d_head=128, rotary_dim=128, rope_theta=1e6,
        qk_norm=True,
        experts=ExpertsSpec(n_experts=128, top_k=8, d_expert=768,
                            d_shared=0, held=(0, 16), router="softmax",
                            expert="swiglu"))
    layer = Block(2048, row, jnp.bfloat16,
                  make_flash_attention_fn(causal=True), block_diffusion=B)

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arr((1, 2 * L, 2048), jnp.bfloat16)
    at = arr((2 * L,), jnp.int32)
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, 2048), jnp.bfloat16),
            positions=jnp.tile(jnp.arange(128), 2))))

    def loss(params, x, at):
        fn = jax.checkpoint(
            lambda p, x, at: layer.apply(p, x, positions=at),
            policy=remat_policy())
        return jnp.sum(fn(params, x, at).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x, at).compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(
        r'tpu_custom_call[^\n]*' + name + r'\b', text))
        for name in ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")}
    # (one backward pass, under flash-bwd-dkv's name)
    assert calls == {"flash-fwd": 1, "flash-bwd-dq": 0, "flash-bwd-dkv": 1}
    assert text.count("tpu_custom_call") == 2 + 9
    tiles = device_trace.scope_table(text).tiles_within
    assert "attn-blockdiff" in tiles
    assert not {"attn-window", "attn-mixer"} & set(tiles)
    assert "flash-bwd-dq" not in tiles["attn-blockdiff"]
    for region in ("flash-fwd", "flash-bwd-dkv"):
        (census,) = tiles["attn-blockdiff"][region]
        assert (census["block_q"], census["block_k"]) == (1024, 1024)
        assert (census["live"], census["visited"]) == (80, 80)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
