"""The loss head's gradient, the causal convolution's kernels, the
state-space scan's and the gated delta rule's two kernels each,
compiled for a described TPU v5e (``tests/_tpu_compile.py``), without
the chip.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


@pytest.mark.parametrize("vocab", [50257, 25088])
def test_loss_head_gradient_one_scan_three_matmuls(one_chip, vocab):
    """The gradient of ``fused_cross_entropy`` at the cells' sizes
    (16,384 rows of 2,048, chunk 1024; the cgpt cells' vocabulary and the
    hybrid cell's): ONE chunk loop of three matmuls, where the
    recomputing rule (``fused_cross_entropy_with_lse``) compiles to two
    loops and four.  Temporaries: the recomputing rule's and at most one
    fp32 logit tile more — the forward's tile now coexists with the
    embedding-gradient carry and the bf16 ``dlogits`` (the recomputing
    backward fuses its remade tile away).  The hybrid cell's whole step,
    1.3 GB under the chip's limit, is unmoved by it (PERF.md §6, PR 27)."""
    from chainermn_tpu.ops import fused_ce

    rows, d, chunk = 16384, 2048, 1024
    operands = (
        jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((vocab, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip))

    def compiled(loss):
        c = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            *operands).compile()
        text = c.as_text()
        return (text.count(" while("), text.count(" convolution("),
                c.memory_analysis().temp_size_in_bytes)

    loops, matmuls, temp = compiled(
        lambda h, e, lab: fused_ce.fused_cross_entropy(
            h, e, lab, chunk=chunk))
    assert (loops, matmuls) == (1, 3)
    loops_r, matmuls_r, temp_r = compiled(
        lambda h, e, lab: fused_ce.fused_cross_entropy_with_lse(
            h, e, lab, chunk=chunk)[0])
    assert (loops_r, matmuls_r) == (2, 4)
    assert temp <= temp_r + chunk * vocab * 4


@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("channels,dtype", [
    (4352, jnp.bfloat16),       # the hybrid cell's
    (4345, jnp.bfloat16),       # ragged: no multiple of 64, of 16 or of 8
    (4352, jnp.float32),        # twice the bytes a tile: still the default
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_conv_kernels_are_one_pass_over_their_operands(one_chip, channels,
                                                       dtype, which):
    """The causal convolution's forward and backward at the hybrid cell's
    shape ((2, 8192, C), four taps), layouts left to the compiler as
    inside a step (it puts the sequence on the lanes, so the transposes
    around a kernel are relabelings): ONE Mosaic call inside the default
    scoped VMEM, HBM traffic within 1.5 x the activations it reads and
    writes (x and y; x, dy and dx), and no temporary — no float32
    ``dpre`` and no copy a tap in HBM.  What autodiff makes of the plain
    forward moves 2.14 GB there with 856 MB of temporaries (ISSUE 29)."""
    from jax.experimental.layout import Format, Layout

    ssd = importlib.import_module("chainermn_tpu.ops.ssd")
    B, S, K = 2, 8192, 4
    auto = Format(Layout.AUTO, one_chip)
    matrix = Format(Layout(major_to_minor=(0, 1)), one_chip)
    vector = Format(Layout(major_to_minor=(0,)), one_chip)
    act = jax.ShapeDtypeStruct((B, S, channels), dtype, sharding=one_chip)
    operands = (
        act,
        jax.ShapeDtypeStruct((K, channels), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one_chip))
    if which == "fwd":
        call, out = ssd._conv_silu_fwd_call, auto
        layouts = (auto, matrix, vector)
    else:
        call, operands = ssd._conv_silu_bwd_call, operands + (act,)
        layouts, out = (auto, matrix, vector, auto), (auto, matrix, vector)
    compiled = jax.jit(
        functools.partial(call, interpret=False), in_shardings=layouts,
        out_shardings=out).lower(*operands).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    moved = ((2 if which == "fwd" else 3) * B * S * channels
             * jnp.dtype(dtype).itemsize)
    assert compiled.cost_analysis()["bytes accessed"] <= 1.5 * moved
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


_SCAN_CELLS = {"granite4hm-train-1chip": (1, 256),     # groups, chunk
               "nemo3nano-train-1chip": (8, 128)}


@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("cell", sorted(_SCAN_CELLS))
def test_scan_kernels_compile_at_the_cells_geometry(one_chip, cell, which):
    """``ssd-fwd`` and ``ssd-bwd`` at both hybrid cells' full geometry (2
    x 8192 tokens, 64 heads of 64, state 128, bfloat16; one group at
    chunk 256, eight at chunk 128): ONE Mosaic call a pass inside the
    default scoped VMEM, at the tiles ``ssd_tiles`` gives — the calls ask
    for no limit of their own."""
    ssd = importlib.import_module("chainermn_tpu.ops.ssd")
    groups, chunk = _SCAN_CELLS[cell]
    b, S, H, P, N = 2, 8192, 64, 64, 128

    def arr(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    hb, vmem = ssd.ssd_tiles(S, chunk, H, groups, P, N, jnp.bfloat16)
    assert vmem <= fa.VMEM_SCOPED_DEFAULT
    operands = (arr(b, S, H, P), arr(b, S, H, dt=jnp.float32),
                arr(b, S, groups, N), arr(b, S, groups, N),
                arr(H, dt=jnp.float32), arr(H, dt=jnp.float32))
    if which == "fwd":
        call = functools.partial(ssd._ssd_fwd_call, chunk=chunk, keep=True,
                                 interpret=False)
    else:
        call = functools.partial(ssd._ssd_bwd_call, chunk=chunk,
                                 interpret=False)
        operands += (arr(b, S // chunk, H // hb, hb * P, N, dt=jnp.float32),
                     arr(b, S, H, P))
    compiled = jax.jit(call).lower(*operands).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert " while(" not in compiled.as_text()


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_delta_rule_kernels_compile_at_the_cells_geometry(one_chip, which):
    """``gdn-fwd`` and ``gdn-bwd`` at the ``qwen3next-train-1chip`` cell's
    full geometry (2 x 8192 tokens, 16 key and 32 value heads of 128,
    chunk 64, bfloat16): ONE Mosaic call a pass inside the default scoped
    VMEM, at the tile ``gdn_tiles`` gives (eight chunks, both value heads
    of a key head) — the calls ask for no limit of their own."""
    gd = importlib.import_module("chainermn_tpu.ops.gated_delta")
    b, S, Hk, Hv, d, chunk = 2, 8192, 16, 32, 128, 64

    def arr(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    operands = (arr(b, S, Hk, d), arr(b, S, Hk, d), arr(b, S, Hv, d),
                arr(b, S, Hv, dt=jnp.float32), arr(b, S, Hv, dt=jnp.float32))
    if which == "fwd":
        call = functools.partial(gd._gdn_fwd_call, C=chunk, keep=True,
                                 interpret=False)
    else:
        call = functools.partial(gd._gdn_bwd_call, C=chunk, interpret=False)
        operands += (arr(b, Hk, S // 512, Hv // Hk, d, d, dt=jnp.float32),
                     arr(b, S, Hv, d))
    # the rule's own tile: what the calls are built with on the chip
    default = gd.default_interpret
    gd.default_interpret = lambda: False
    try:
        tokens, heads, vmem = gd.gdn_tiles(S, chunk, Hk, Hv, d, d,
                                           jnp.bfloat16)
        compiled = jax.jit(call).lower(*operands).compile()
    finally:
        gd.default_interpret = default
    assert (tokens, heads) == (512, 2) and vmem <= fa.VMEM_SCOPED_DEFAULT
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert " while(" not in compiled.as_text()


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_kda_kernels_compile_at_the_cells_geometry(one_chip, which):
    """``kda-fwd`` and ``kda-bwd`` at the ``ling3flash-train-1chip``
    cell's full geometry (1 x 16,384 tokens, 32 heads of 128, chunk 64,
    ``q``, ``k``, ``v``, ``f`` in bfloat16: the heads' float32 side is
    made in the kernels): ONE Mosaic call a pass inside the default
    scoped VMEM, at the tile ``kda_tiles`` gives (eight chunks of two
    heads) — the calls ask for no limit of their own — no loop beside it
    and no float32 array a token, head and channel: the norms, the decay
    and its running sums never leave VMEM."""
    kd = importlib.import_module("chainermn_tpu.ops.kda")
    b, S, H, d, chunk = 1, 16384, 32, 128, 64

    def arr(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    operands = (arr(b, S, H, d), arr(b, S, H, d), arr(b, S, H, d),
                arr(b, S, H, d), arr(b, S, H, dt=jnp.float32),
                arr(H, dt=jnp.float32), arr(H, d, dt=jnp.float32))
    if which == "fwd":
        call = functools.partial(kd._kda_fwd_call, C=chunk, floor=-5.0,
                                 keep=True, interpret=False)
    else:
        call = functools.partial(kd._kda_bwd_call, C=chunk, floor=-5.0,
                                 interpret=False)
        operands += (arr(b, H, S // 512, d, d, dt=jnp.float32),
                     arr(b, S, H, d))
    # the rule's own tile: what the calls are built with on the chip
    default = kd.default_interpret
    kd.default_interpret = lambda: False
    try:
        tokens, heads, vmem = kd.kda_tiles(S, chunk, H, d, d, jnp.bfloat16)
        compiled = jax.jit(call).lower(*operands).compile()
    finally:
        kd.default_interpret = default
    assert (tokens, heads) == (512, 2) and vmem <= fa.VMEM_SCOPED_DEFAULT
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and " while(" not in text
    assert f"f32[{b},{H},{d},{S}]" not in text
    assert f"f32[{b},{S},{H},{d}]" not in text
