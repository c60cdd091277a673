"""The ``qwen3next-train-1chip`` cell's Gated DeltaNet mixer and both rows
of its period, forward and backward under remat as in the step, compiled
for a described TPU v5e (``tests/_tpu_compile.py``), without the chip.
"""

import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def test_gdn_mixer_grows_no_copies_around_the_rule(one_chip, monkeypatch):
    """One Gated DeltaNet mixer at the cell's shape, forward and backward
    under remat as in the step: six Mosaic calls and no loop (the
    convolution's forward twice and its backward, ``gdn-fwd`` twice —
    once keeping the tiles' states — and ``gdn-bwd``).  The kernels take
    the tokens on the lanes, as the convolution's do and as the compiler
    lays out ``in_proj``'s result, where the split into heads moves
    nothing: no ``copy`` stands under ``gdn-scan`` but those of the
    per-token scalars (``g``, ``beta`` and their cotangents: (2, 8192,
    32) float32), and the layer holds 14 copies, 4 of them of an
    activation's size (the convolution's padded operand and the gate's
    float32 reshape, forward and recomputed).  With the channels on the
    lanes the same layer held 31 and 12: a transpose of ``q``, ``k``,
    ``v``, ``o`` and of each cotangent a pass, and a float32 relayout a
    reshape between (S, H d) and (H, d) tiles."""
    from chainermn_tpu.models.block_table import GDNSpec
    from chainermn_tpu.models.transformer import GatedDeltaNetMixer

    ssd = importlib.import_module("chainermn_tpu.ops.ssd")
    gd = importlib.import_module("chainermn_tpu.ops.gated_delta")
    for module in (ssd, gd):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    d_model = 2048
    mixer = GatedDeltaNetMixer(d_model, GDNSpec(16, 32, 128, 128), 1e-6,
                               jnp.bfloat16)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, d_model),
                                             jnp.bfloat16))))
    h = jax.ShapeDtypeStruct((2, 8192, d_model), jnp.bfloat16,
                             sharding=one_chip)

    def loss(params, h):
        layer = jax.checkpoint(lambda p, h: h + mixer.apply(p, h))
        return jnp.sum(layer(params, h).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6 and " while(" not in text
    assert len(re.findall(r'tpu_custom_call[^\n]*gdn-fwd', text)) == 2
    sizes, under_rule = [], []
    for line in text.splitlines():
        found = re.search(r"= (\w+)\[([\d,]*)\]\S* (?:copy|transpose)\(", line)
        if found:
            size = (2 if found.group(1) == "bf16" else 4) * math.prod(
                int(d) for d in found.group(2).split(",") if d)
            sizes.append(size)
            if "gdn-scan" in line:
                under_rule.append(size)
    assert len(sizes) <= 14
    assert sum(size >= 2 * 8192 * 2048 * 2 for size in sizes) <= 4
    assert max(under_rule, default=0) <= 2 * 8192 * 32 * 4
    # read: 2.17 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9


@pytest.mark.parametrize("kind", ["gdn", "attention"])
def test_qwen3next_layers_compile_at_the_cells_shape(one_chip, monkeypatch,
                                                     kind):
    """Both rows of the ``qwen3_next`` period at the cell's shape (2 x 8192
    tokens, 32 of 512 gated experts of 512 held: a buffer of 192 tiles),
    forward and backward under remat with the model's policy as in the
    step.  The Gated DeltaNet row: the convolution's two kernels over
    8,192 channels (forward twice: recomputed) and the delta rule's two
    (``gdn-fwd`` ONCE, keeping ``o`` and the tiles' states under the
    policy's ``GDN_RESIDUALS``, and ``gdn-bwd``), the layer's only loops
    the dispatch's row movers, and its temporaries inside 3 GB.  The gated
    attention row: the three flash calls at D = 256 (the forward ONCE),
    under the blocks ``auto_block_size`` picks (1024 forward, 512
    backward).  Each with nine grouped calls of the experts."""
    from chainermn_tpu.models.block_table import (
        ExpertsSpec,
        GDNSpec,
        LayerSpec,
    )
    from chainermn_tpu.models.transformer import Block, remat_policy
    from chainermn_tpu.ops import make_flash_attention_fn

    gm = importlib.import_module("chainermn_tpu.ops.grouped_matmul")
    ssd = importlib.import_module("chainermn_tpu.ops.ssd")
    gd = importlib.import_module("chainermn_tpu.ops.gated_delta")
    for module in (fa, gm, ssd, gd):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    assert fa.auto_block_size(8192, 256, jnp.bfloat16, "fwd") == 1024
    assert fa.auto_block_size(8192, 256, jnp.bfloat16, "bwd") == 512
    common = dict(
        norm="rmsnorm_zc", ffn="experts", norm_eps=1e-6,
        experts=ExpertsSpec(n_experts=512, top_k=10, d_expert=512,
                            d_shared=512, held=(0, 32), router="softmax",
                            expert="swiglu", shared_gate=True))
    row = LayerSpec(mixer="gdn", gdn=GDNSpec(16, 32, 128, 128),
                    **common) if kind == "gdn" else LayerSpec(
        mixer="attention", n_heads=16, n_kv_heads=2, d_head=256,
        rotary_dim=64, rope_theta=1e7, qk_norm=True, out_gate=True,
        **common)
    layer = Block(2048, row, jnp.bfloat16,
                  make_flash_attention_fn(causal=True))

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arr((2, 8192, 2048), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, 2048), jnp.bfloat16))))

    def loss(params, x):
        fn = jax.checkpoint(lambda p, x: layer.apply(p, x),
                            policy=remat_policy())
        return jnp.sum(fn(params, x).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(
        r'tpu_custom_call[^\n]*' + name + r'\b', text))
        for name in ("ssm-conv-fwd", "ssm-conv-bwd", "gdn-fwd", "gdn-bwd",
                     "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")}
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    if kind == "gdn":
        assert text.count("tpu_custom_call") == 3 + 2 + 9
        assert calls == {"ssm-conv-fwd": 2, "ssm-conv-bwd": 1, "gdn-fwd": 1,
                         "gdn-bwd": 1, "flash-fwd": 0, "flash-bwd-dq": 0,
                         "flash-bwd-dkv": 0}
        # the loops left are the dispatch's row movers: none in the mixer
        for line in text.splitlines():
            assert " while(" not in line or "gdn-mixer" not in line, line
        # read: 2.52 GB (3.33 with the XLA form, 8 of 32 heads a group)
        assert temporaries < 3e9
    else:
        # (one backward pass, under flash-bwd-dkv's name)
        assert text.count("tpu_custom_call") == 2 + 9
        assert calls == {"ssm-conv-fwd": 0, "ssm-conv-bwd": 0, "gdn-fwd": 0,
                         "gdn-bwd": 0, "flash-fwd": 1, "flash-bwd-dq": 0,
                         "flash-bwd-dkv": 1}
        assert "gdn-scan" not in text
        assert temporaries < 4e9
