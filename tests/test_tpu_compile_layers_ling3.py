"""The ``ling3flash-train-1chip`` cell's Kimi-Delta-Attention mixer and its
latent-attention row, forward and backward under remat as in the step,
compiled for a described TPU v5e (``tests/_tpu_compile.py``), without the
chip.
"""

import importlib
import math
import re

import jax
import jax.numpy as jnp

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def _copies(text):
    """``(bytes, line)`` of every standalone copy or transpose."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\]\S* (?:copy|transpose)\(", line)
        if m:
            found.append(((2 if m.group(1) == "bf16" else 4) * math.prod(
                int(d) for d in m.group(2).split(",") if d), line))
    return found


def _standing(text, at_least):
    """``(type, line)`` of every float array of ``at_least`` bytes that
    STANDS in the compiled program: a result (or a tuple's part) of an
    instruction of the entry computation — what lies inside a fusion is
    registers, not an array."""
    entry = text[text.index("\nENTRY "):]
    found = []
    for line in entry[:entry.index("\n}")].splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) [\w-]+\(", line)
        for kind, dims in re.findall(
                r"\b(bf16|f32)\[([\d,]+)\]", m.group(1) if m else ""):
            if (2 if kind == "bf16" else 4) * math.prod(
                    int(d) for d in dims.split(",")) >= at_least:
                found.append((kind, line))
    return found


def test_kda_mixer_compiles_at_the_cells_shape(one_chip, monkeypatch):
    """One KDA mixer at the cell's shape (1 x 16,384 tokens, 32 heads of
    128), forward and backward under remat with the model's policy as in
    the step: the convolution's three Mosaic calls and the rule's two
    (``kda-fwd`` ONCE, keeping ``o`` and the tiles' states under the
    policy's ``KDA_RESIDUALS``, and ``kda-bwd``), every head in one call
    and no loop left.  The mixer hands the rule the convolution's ``q``,
    ``k`` and the projection's ``f`` in bfloat16 and the kernels make the
    heads' float32 side in VMEM: no float32 array a token, head and
    channel stands anywhere in the program."""
    from chainermn_tpu.models.block_table import KDASpec
    from chainermn_tpu.models.transformer import KDAMixer, remat_policy

    kd = importlib.import_module("chainermn_tpu.ops.kda")
    for module in (importlib.import_module("chainermn_tpu.ops.ssd"), kd):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    d_model = 2560
    mixer = KDAMixer(d_model, KDASpec(32, 128, 128), 1e-6, jnp.bfloat16)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, d_model),
                                             jnp.bfloat16))))
    h = jax.ShapeDtypeStruct((1, 16384, d_model), jnp.bfloat16,
                             sharding=one_chip)

    def loss(params, h):
        layer = jax.checkpoint(lambda p, h: h + mixer.apply(p, h),
                               policy=remat_policy())
        return jnp.sum(layer(params, h).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile()
    text = compiled.as_text()
    calls = {name: len(re.findall(rf"tpu_custom_call[^\n]*{name}", text))
             for name in ("kda-fwd", "kda-bwd")}
    assert calls == {"kda-fwd": 1, "kda-bwd": 1}
    assert text.count("tpu_custom_call") == 5 and " while(" not in text
    assert "kda-scan" in text and "kda-mixer" in text
    # the rule's own tile, inside the default scoped VMEM (the calls ask
    # for no limit of their own): eight chunks of two heads
    tokens, heads, vmem = kd.kda_tiles(16384, 64, 32, 128, 128, jnp.bfloat16)
    assert (tokens, heads) == (512, 2) and vmem <= fa.VMEM_SCOPED_DEFAULT
    # a head-wide float32 array is 268 MB: ``g``, its running sums and
    # their cotangents live in the kernels' VMEM only
    whole = 16384 * 32 * 128 * 4
    assert [kind for kind, _ in _standing(text, whole // 2)].count(
        "bf16") > 8                         # q, k, v, f, o and cotangents
    assert not [line for kind, line in _standing(text, whole)
                if kind == "f32"]
    # read: 1.63 GB (2.58 with the gate side beside the calls)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2e9


def test_mla_row_compiles_at_the_cells_shape(one_chip, monkeypatch):
    """The latent-attention row with its expert FFN at the cell's shape
    (32 heads scoring over 192 and summing values of 128, 8 of 512 gated
    experts of 768 held in 8 groups of which 4 stay), forward and backward
    under remat with the model's policy as in the step: the three flash
    calls (the forward ONCE) at 1024-edge tiles, which the rule picks for
    D = 192, D_v = 128 inside the default scoped VMEM, and nine grouped
    calls of the experts; no transposing copy of an activation's size
    stands around the flash calls."""
    from chainermn_tpu.models.block_table import (
        ExpertsSpec,
        LayerSpec,
        MLASpec,
    )
    from chainermn_tpu.models.transformer import Block, remat_policy
    from chainermn_tpu.observability import device_trace
    from chainermn_tpu.ops import make_flash_attention_fn

    gm = importlib.import_module("chainermn_tpu.ops.grouped_matmul")
    for module in (fa, gm):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    for which in ("fwd", "bwd"):
        assert fa.auto_block_size(16384, 192, jnp.bfloat16, which,
                                  D_v=128) == 1024
        assert fa.flash_vmem_bytes(1024, 1024, 192, 2, which,
                                   D_v=128) <= fa.VMEM_SCOPED_DEFAULT
    row = LayerSpec(
        mixer="attention", norm="rmsnorm", ffn="experts", n_heads=32,
        qk_norm=True, head_gate=True,
        mla=MLASpec(512, 128, 64, 128, 6e6, True),
        experts=ExpertsSpec(
            n_experts=512, top_k=8, d_expert=768, d_shared=768, held=(0, 8),
            scaling=2.5, router="sigmoid", expert="swiglu", n_group=8,
            topk_group=4))
    layer = Block(2560, row, jnp.bfloat16,
                  make_flash_attention_fn(causal=True))

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arr((1, 16384, 2560), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, 2560), jnp.bfloat16))))

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
    tiles = device_trace.scope_table(text).tiles_within["mla-mixer"]
    assert "flash-bwd-dq" not in tiles
    for region in ("flash-fwd", "flash-bwd-dkv"):
        (census,) = tiles[region]
        assert (census["block_q"], census["block_k"]) == (1024, 1024)
        assert (census["live"], census["visited"]) == (136, 256)
    # q and k are (1, 16384, 32, 192) bfloat16, v and o 128 wide: no copy
    # or transpose of that size under the latent row's scope
    heads = 16384 * 32 * 128 * 2
    assert not [line for size, line in _copies(text)
                if size >= heads and "mla-mixer" in line]
    # read: 2.71 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 3.3e9
