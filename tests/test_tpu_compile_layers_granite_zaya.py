"""The ``granite4hm-train-1chip`` cell's two kinds of layer and the
``zaya1-train-1chip`` cell's layer, forward and backward under remat as
in the step, compiled for a described TPU v5e (``tests/_tpu_compile.py``),
without the chip.
"""

import importlib
import math
import re

import jax
import jax.numpy as jnp

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def test_hybrid_cell_attention_layer_keeps_the_forwards_residuals(
        one_chip, monkeypatch):
    """The attention ``Block`` of the ``granite4hm-train-1chip`` cell (its
    table's own row, 2 x 8192 tokens) forward and backward under the
    model's remat policy: three Mosaic calls, the forward kernel once;
    under ``policy=None`` (what the cell compiled to before PR 35) four.
    How ``lse`` lies between the passes: the kernel writes a column,
    ``f32[64,8192,1]`` tiled (8, 128) — one number a 128-lane row, 268 MB
    — and the backward kernels read one; what is KEPT is
    ``f32[64,8192]``, the tokens on the lanes, 2 MB, made right behind
    the forward kernel and turned back into the column by a copy beside
    the backward kernels.  With ``o`` (64 lanes of 128 filled at this
    head width: 134 MB) that is under 0.15 GB more than the step that ran
    the kernel twice, where a kept column would make it 0.4."""
    import json
    import os

    from chainermn_tpu.models.block_table import table_from_config
    from chainermn_tpu.models.transformer import Block, remat_policy
    from chainermn_tpu.ops import make_flash_attention_fn

    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench", "configs", "granite4hmicro-train.json")) as f:
        config = json.load(f)
    row, = {r for r in table_from_config(
        config, n_layers=config["n_layer"]).layers if r.mixer == "attention"}
    d_model = config["hidden_size"]
    layer = Block(d_model, row, jnp.bfloat16, make_flash_attention_fn(
        causal=True, scale=config["attention_multiplier"]))

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = arr((2, 8192, d_model), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 256, d_model), jnp.bfloat16), None)))

    def compiled(policy):
        def loss(params, x):
            fn = jax.checkpoint(lambda p, x: layer.apply(p, x, None),
                                policy=policy)
            return jnp.sum(fn(params, x).astype(jnp.float32) ** 2)

        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile()

    kept, again = compiled(remat_policy()), compiled(None)
    # (the backward is ONE kernel since PR 48: forward twice and it,
    # against forward once and it)
    assert again.as_text().count("tpu_custom_call") == 3
    entry = kept.as_text().split("\nENTRY ")[1].splitlines()
    assert sum("tpu_custom_call" in line for line in entry) == 2
    column = r"f32\[64,8192,1\]\{2,1,0:T\(8,128\)"
    flat = r"f32\[64,8192\]\{1,0:T\(8,128\)"
    at = {what: [i for i, line in enumerate(entry)
                 if re.match(rf"\s*%{pattern}", line)]
          for what, pattern in (
              ("fwd", rf"flash-fwd\S* = \(bf16\[64,8192,64\]\S+, "
                      rf"{column}\S*\) custom-call\("),
              ("flat", rf"\S+ = {flat}\S* reduce\("),
              ("bwd", r"flash-bwd-d\S+ = .* custom-call\("))}
    assert len(at["fwd"]) == 1 and len(at["bwd"]) == 1, at
    # the column is turned within a few instructions of the kernel that
    # wrote it (a tuple's parts, a constant), long before the backward's
    assert at["flat"] and at["flat"][0] - at["fwd"][0] < 8 < (
        at["bwd"][0] - at["fwd"][0])
    for call in at["bwd"]:
        assert len(re.findall(r"f32\[64,8192,1\]\{2,1,0\}",
                              entry[call])) == 2        # lse and delta
    grew = (kept.memory_analysis().temp_size_in_bytes
            - again.memory_analysis().temp_size_in_bytes)
    assert grew < 0.15e9


def test_mixer_layer_grows_no_copies_around_the_scan(one_chip, monkeypatch):
    """One Mamba-2 mixer layer at the granite cell's shape, forward and
    backward under remat as in the step: six Mosaic calls and no loop
    (the convolution's forward twice and its backward, ``ssd-fwd`` twice
    — once keeping the blocks' states — and ``ssd-bwd``), and no more
    copies beside them than the parent of PR 31 compiled to — 17 ``copy``
    instructions, 2 of them of an activation's size (the loop's bodies
    held 7 of the 17, run once a block); here 9 and 2.  The kernels take
    the tokens on the lanes, as the convolution's do and as the compiler
    lays out ``in_proj``'s result: nothing is turned around between
    them."""
    from chainermn_tpu.models.block_table import SSMSpec
    from chainermn_tpu.models.transformer import Mamba2Mixer

    ssd = importlib.import_module("chainermn_tpu.ops.ssd")
    monkeypatch.setattr(ssd, "default_interpret", lambda: False)
    d_model, spec = 2048, SSMSpec(n_heads=64, d_head=64, d_state=128,
                                  n_groups=1, d_conv=4, chunk=256)
    mixer = Mamba2Mixer(d_model, spec, 1e-5, jnp.bfloat16)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, spec.chunk, d_model), jnp.bfloat16))))
    h = jax.ShapeDtypeStruct((2, 8192, d_model), jnp.bfloat16,
                             sharding=one_chip)

    def loss(params, h):
        layer = jax.checkpoint(lambda p, h: h + mixer.apply(p, h))
        return jnp.sum(layer(params, h).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile().as_text()
    assert text.count("tpu_custom_call") == 6 and " while(" not in text
    sizes = []
    for dtype, dims in re.findall(r"= (\w+)\[([\d,]*)\]\S* copy\(", text):
        sizes.append((2 if dtype == "bf16" else 4) * math.prod(
            int(d) for d in dims.split(",") if d))
    assert len(sizes) <= 17
    assert sum(size >= 2 * 8192 * 1024 for size in sizes) <= 2


def test_zaya_layer_compiles_at_the_cells_shape(one_chip, monkeypatch):
    """One ``zaya`` layer at the cell's shape (2 x 8192 tokens, the CCA
    mixer's latent 8/2 heads of 128, 8 of 16 gated experts of 2048 held:
    a buffer of 18,432 rows), forward and backward under remat with the
    model's policy as in the step: the two flash calls (the forward
    ONCE: its output and row statistics are kept, PR 35; the backward in
    one pass, PR 48) and nine grouped
    ones — gate, up and down once each forward (kept, not recomputed),
    three ``dx`` and three ``dw`` — whole 2048 x 2048 matrices as one
    block inside the VMEM limit the calls state."""
    from chainermn_tpu.models.block_table import CCASpec, ExpertsSpec, LayerSpec
    from chainermn_tpu.models.transformer import Block, remat_policy
    from chainermn_tpu.ops import make_flash_attention_fn

    gm = importlib.import_module("chainermn_tpu.ops.grouped_matmul")
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    monkeypatch.setattr(gm, "default_interpret", lambda: False)
    assert gm.weight_blocks(2048, 2048, 2) == (2048, 2048)
    row = LayerSpec(
        mixer="cca", norm="rmsnorm", ffn="experts", norm_eps=1e-5,
        cca=CCASpec(n_heads=8, n_kv_heads=2, d_head=128, rotary_dim=64,
                    rope_theta=5e6),
        experts=ExpertsSpec(n_experts=16, top_k=1, d_expert=2048,
                            d_shared=0, held=(0, 8), router="mlp_softmax",
                            expert="swiglu", d_router=256))
    layer = Block(2048, row, jnp.bfloat16,
                  make_flash_attention_fn(causal=True))

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x, r = arr((2, 8192, 2048), jnp.bfloat16), arr((2, 8192, 256),
                                                   jnp.float32)
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256, 2048), jnp.bfloat16),
            None, jnp.zeros((1, 256, 256), jnp.float32))))

    def loss(params, x, r):
        fn = jax.checkpoint(
            lambda p, x, r: layer.apply(p, x, None, r),
            policy=remat_policy())
        out, state = fn(params, x, r)
        return (jnp.sum(out.astype(jnp.float32) ** 2)
                + jnp.sum(state ** 2))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        params, x, r).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 + 9    # one backward pass
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9
