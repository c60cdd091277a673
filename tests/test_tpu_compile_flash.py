"""The flash kernels and the rotary positions beside them, compiled for
a described TPU v5e (``tests/_tpu_compile.py``), without the chip.

The static default geometry has to compile inside the default scoped
VMEM at every head dim, dtype and mask the rule sizes it for, and a
pinned geometry past it under the limit the kernels compute.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import one_chip  # noqa: F401

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def _compile(one_chip, *, S, D, dtype, bq, bk, which, segmented=False,
             window=None, BH=128, BHk=None, Dv=None, blockdiff=None):
    """The forward kernel or the backward compiled at one geometry: one
    ``tpu_custom_call`` forward, and in the backward what the footprint
    rule says — one where the KV row's ``dk`` / ``dv`` fit beside the
    tile (``bwd_fused_vmem_bytes``), else the two kernels."""
    Dv = Dv or D

    def arr(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, col = arr(BH, S, D), arr(BH, S, 1, dt=jnp.float32)
    k, v = arr(BHk or BH, S, D), arr(BHk or BH, S, Dv)  # fewer rows: GQA
    o = arr(BH, S, Dv)
    seg = {}
    operands = [q, k, v] if which == "fwd" else [q, k, v, o, col, o]
    if segmented:
        operands += [arr(BH, S, 1, dt=jnp.int32),
                     arr(BHk or BH, S, 1, dt=jnp.int32)]

    def fn(*a):
        if segmented:
            *a, qs, ks = a
            seg.update(q_seg=qs, kv_seg=ks)
        kernel = fa._flash_bh_fwd if which == "fwd" else fa._flash_bh_bwd
        return kernel(*a, scale=0.1, causal=True, block_q=bq, block_k=bk,
                      interpret=False, window=window, blockdiff=blockdiff,
                      **seg)

    fused = fa.bwd_fused_vmem_bytes(
        S, bq, bk, D, jnp.dtype(dtype).itemsize, segmented, Dv) is not None
    compiled = jax.jit(fn).lower(*operands).compile()
    assert compiled.as_text().count("tpu_custom_call") == (
        1 if which == "fwd" or fused else 2)
    return compiled


@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("D,dtype,segmented", [
    (128, jnp.bfloat16, False),      # the benchmark's cells
    (64, jnp.bfloat16, False),
    (256, jnp.bfloat16, False),      # 1024 forward, 512 backward
    (128, jnp.float32, False),
    (128, jnp.bfloat16, True),       # a segment mask halves the tile
    (256, jnp.float32, True),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_default_geometry_compiles_inside_the_default_vmem(
        one_chip, D, dtype, segmented, which):
    S = 2048
    b = fa.auto_block_size(S, D, dtype, which, segmented)
    footprint = fa.flash_vmem_bytes(
        b, b, D, jnp.dtype(dtype).itemsize, which, segmented)
    assert fa._compiler_params(footprint) is None
    _compile(one_chip, S=S, D=D, dtype=dtype, bq=b, bk=b, which=which,
             segmented=segmented)


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_hybrid_cell_attention_compiles_at_its_default(one_chip, which):
    """GQA 32 / 8 at D=64, S=8192, two rows: the attention layer of the
    ``granite4hm-train-1chip`` cell at the geometry the rule gives it."""
    S, D = 8192, 64
    b = fa.auto_block_size(S, D, jnp.bfloat16, which)
    assert fa._compiler_params(
        fa.flash_vmem_bytes(b, b, D, 2, which)) is None
    _compile(one_chip, S=S, D=D, dtype=jnp.bfloat16, bq=b, bk=b,
             which=which, BH=2 * 32, BHk=2 * 8)


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_pinned_geometry_past_the_default_gets_its_limit(one_chip, which):
    """2048 x 2048 needs more than the default 16 MiB: it compiles
    because the kernels ask for their own footprint."""
    footprint = fa.flash_vmem_bytes(2048, 2048, 128, 2, which)
    assert fa._compiler_params(footprint).vmem_limit_bytes > footprint
    _compile(one_chip, S=2048, D=128, dtype=jnp.bfloat16, bq=2048, bk=2048,
             which=which)


#: cell -> BH, BHk, S, D, D_v, window, block-diffusion (L, B): the
#: backward each cell's runner builds (ROADMAP S4; the tile is the
#: rule's, ``auto_block_size(which="bwd")``).
_CELLS_BACKWARD = {
    "cgpt": (128, 128, 2048, 128, 128, None, None),
    "granite4hm": (64, 16, 8192, 64, 64, None, None),
    "nemo3nano": (64, 4, 8192, 128, 128, None, None),
    "zaya1": (16, 4, 8192, 128, 128, None, None),
    "qwen3next": (32, 4, 8192, 256, 256, None, None),
    "mellum2-full": (32, 4, 16384, 128, 128, None, None),
    "mellum2-window": (32, 4, 16384, 128, 128, 1024, None),
    "ling3flash": (32, 32, 16384, 192, 128, None, None),
    "sdar30b": (32, 4, 16384, 128, 128, None, (8192, 4)),
}


@pytest.mark.parametrize("cell", sorted(_CELLS_BACKWARD))
def test_the_cells_backward_is_one_kernel_inside_the_limit(one_chip, cell):
    """The one-pass backward compiled for the described v5e at the nine
    cells' backward geometries: ONE ``tpu_custom_call`` (the rule reads
    fused at every one), its resident rows and whole-row outputs inside
    the ``vmem_limit_bytes`` the kernel asks for, and that within
    ``VMEM_LIMIT_MAX``."""
    BH, BHk, S, D, Dv, window, blockdiff = _CELLS_BACKWARD[cell]
    b = fa.auto_block_size(S, D, jnp.bfloat16, "bwd", window=window,
                           D_v=None if Dv == D else Dv, blockdiff=blockdiff)
    assert b == {"qwen3next": 512, "mellum2-window": 512}.get(cell, 1024)
    footprint = fa.bwd_fused_vmem_bytes(S, b, b, D, 2, False, Dv)
    assert footprint is not None
    assert footprint > fa.VMEM_SCOPED_DEFAULT       # it asks for its own
    compiled = _compile(one_chip, S=S, D=D, Dv=Dv, dtype=jnp.bfloat16, bq=b,
                        bk=b, which="bwd", window=window, BH=BH, BHk=BHk,
                        blockdiff=blockdiff)
    # (the kernel's vmem_limit_bytes, as its custom call carries it, and
    # beside it what the compiler made use of)
    call, = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    config = (r'"%sscoped_memory_configs":\[\{"memory_space":"1",'
              r'"offset":"0","size":"(\d+)"')
    limit, = map(int, re.findall('[^_]' + config % "", call))
    assert limit == fa._compiler_params(footprint).vmem_limit_bytes
    assert footprint < limit <= fa.VMEM_LIMIT_MAX
    used, = map(int, re.findall(config % "used_", call))
    assert used <= footprint
    # (a 1024-edge triangle runs by halves, a 512-edge one whole)
    tiles = fa.tile_census(S, S, b, b, True, window, blockdiff)["dq"]
    assert tiles["halved"] == (
        S // b if b == 1024 and blockdiff is None else 0)


@pytest.mark.parametrize("cell", sorted(_CELLS_BACKWARD))
def test_the_cells_forward_compiles_inside_the_default_vmem(one_chip, cell):
    """The forward kernel compiled for the described v5e at the nine
    cells' forward geometries, inside the default scoped VMEM — mellum's
    windowed row with the guard by row (``_reached``) among them.  The
    census beside it says which tiles an edge of the mask cuts; the
    forward halves none.  (The backward's bodies, the halves of a
    diagonal tile on slices of the same refs among them, compile in the
    test above.)"""
    BH, BHk, S, D, Dv, window, blockdiff = _CELLS_BACKWARD[cell]
    b = fa.auto_block_size(S, D, jnp.bfloat16, "fwd", window=window,
                           D_v=None if Dv == D else Dv, blockdiff=blockdiff)
    assert b == 1024
    assert fa._compiler_params(fa.flash_vmem_bytes(
        b, b, D, 2, "fwd", False, Dv)) is None
    _compile(one_chip, S=S, D=D, Dv=Dv, dtype=jnp.bfloat16, bq=b, bk=b,
             which="fwd", window=window, BH=BH, BHk=BHk,
             blockdiff=blockdiff)
    tiles = fa.tile_census(S, S, b, b, True, window, blockdiff)["fwd"]
    n = S // b
    assert tiles["halved"] == 0
    if blockdiff is not None:
        assert tiles["cut"] == 24
    elif window is not None:        # a diagonal and a far tile a q block
        assert tiles["cut"] == 2 * n - 1 == tiles["live"]
    else:
        assert tiles["cut"] == n
        assert tiles["live"] - tiles["cut"] == n * (n - 1) // 2


def test_a_row_past_the_limit_compiles_as_the_two_kernels(one_chip):
    """A 128k-row block (ring attention's, R7's) is past the footprint
    rule: the parent's two kernels, inside the default scoped VMEM."""
    S, D = 131072, 128
    b = fa.auto_block_size(S, D, jnp.bfloat16, "bwd")
    assert fa.bwd_fused_vmem_bytes(S, b, b, D, 2) is None
    compiled = _compile(one_chip, S=S, D=D, dtype=jnp.bfloat16, bq=b, bk=b,
                        which="bwd", BH=8, BHk=2)
    assert compiled.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("bq,bk,window", [
    (512, 1024, None), (1024, 256, None), (256, 256, 300)])
def test_banded_index_maps_compile(one_chip, bq, bk, window):
    """Rectangular blocks and a sliding window: the clamped index maps
    lower through Mosaic in all three kernels."""
    for which in ("fwd", "bwd"):
        _compile(one_chip, S=2048, D=128, dtype=jnp.bfloat16, bq=bq, bk=bk,
                 which=which, window=window)


def test_rotary_positions_turn_whole_heads(one_chip):
    """``rotate_partial`` forward + backward at mellum's q (1 x 16,384 x
    32 heads of 128, bfloat16, the whole head turned) as a caller runs
    it: one pass over whole heads each way.  Read: 1.39 GB accessed (the
    half-split form it replaced, with the float32 copy a caller made:
    3.79 GB — every 64-lane half is padded to 128 lanes on the chip), and
    no array in the optimised text whose minor axis is ``rotary_dim /
    2``."""
    from chainermn_tpu.models.transformer import rotate_partial

    shape, rotary_dim = (1, 16384, 32, 128), 128
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def both_passes(x, g):
        y, back = jax.vjp(
            lambda x: rotate_partial(
                x, jnp.arange(shape[1]), rotary_dim, 5e5).astype(
                    jnp.bfloat16), x)
        return y, back(g)[0]

    compiled = jax.jit(both_passes).lower(x, x).compile()
    assert compiled.cost_analysis()["bytes accessed"] <= 1.8e9
    text = compiled.as_text()
    assert not re.findall(r"\[(?:\d+,)*%d\]" % (rotary_dim // 2), text)
    assert len(re.findall(r" convolution\(", text)) == 2   # x P, and back
