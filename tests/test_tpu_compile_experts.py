"""The grouped matmuls and the dropless dispatch's row movers at the
expert cells' shapes, compiled for a described TPU v5e
(``tests/_tpu_compile.py``), without the chip.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from _tpu_compile import one_chip  # noqa: F401


@pytest.mark.parametrize("tile_rows", [256, 512])
def test_grouped_matmuls_compile_at_the_expert_cells_widths(one_chip,
                                                            tile_rows):
    """The held experts' two matrices at the published widths (2688 ->
    1856 -> 2688: 1856 is no multiple of 128, its blocks span the axis),
    the forward product, its transposed twin and the weights' gradient,
    four Mosaic calls, inside the VMEM limit the calls state."""
    gm = importlib.import_module("chainermn_tpu.ops.grouped_matmul")
    n_tiles, d, f, held = 24576 // tile_rows + 8, 2688, 1856, 8

    def arr(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(x, w_up, w_down, tile_group, n_live):
        # the public op picks interpret mode off the chip: compile its
        # three calls as the chip would run them, both stacks (held, f, d)
        hidden = gm._gmm_call(x, w_up.astype(x.dtype), tile_group, n_live,
                              transpose_w=True, interpret=False)
        out = gm._gmm_call(hidden, w_down.astype(x.dtype), tile_group,
                           n_live, transpose_w=False, interpret=False)
        dx = gm._gmm_call(out, w_down.astype(x.dtype), tile_group, n_live,
                          transpose_w=True, interpret=False)
        dw = gm._dw_call(hidden, out, tile_group, n_live, n_groups=held,
                         interpret=False)
        return dx, dw

    compiled = jax.jit(step).lower(
        arr(n_tiles * tile_rows, d), arr(held, f, d, dt=jnp.float32),
        arr(held, f, d, dt=jnp.float32), arr(n_tiles, dt=jnp.int32),
        arr(1, dt=jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 4
    rows_mb = n_tiles * tile_rows * d * 2 / 1e6
    assert compiled.memory_analysis().temp_size_in_bytes / 1e6 < 4 * rows_mb


@pytest.mark.parametrize("tokens,d,experts,held,top_k,tiles", [
    (16384, 2688, 128, 8, 6, 104),        # nemo3nano-train-1chip
    (16384, 2048, 16, 8, 1, 72),          # zaya1-train-1chip
])
def test_dispatch_row_movers_compile_at_the_expert_cells_shapes(
        one_chip, tokens, d, experts, held, top_k, tiles):
    """``gather_rows`` and ``combine`` forward and backward (two takes, two
    adds, the plan) at both expert cells' published shapes: loops over the
    live tiles and row gathers, no Mosaic call, the scatter-add left only
    for the further rows of tokens with several (none at one expert a
    token), and no float32 temporary of the buffer's size, as the plain
    whole-buffer bodies (``tests/test_moe_dispatch.py`` keeps them) wrote
    for ``float32(y) * w`` over every row."""
    from chainermn_tpu.parallel import moe_dropless as moe
    from tests.test_moe_dispatch import combine_ref, gather_rows_ref

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    n_rows = moe.rows_bound(tokens * top_k, held, experts)
    assert moe.buffer_tiles(n_rows, held) == tiles

    def compiled(gather_rows, combine):
        def loss(x, weight, chosen):
            plan = moe.dispatch(chosen, (0, held), n_rows)
            out = combine(jnp.tanh(gather_rows(x, plan)), weight, plan,
                          tokens)
            return jnp.sum(out ** 2)

        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            arr((tokens, d), jnp.bfloat16), arr((tokens, top_k), jnp.float32),
            arr((tokens, top_k), jnp.int32)).compile()

    ours, plain = compiled(moe.gather_rows, moe.combine), compiled(
        gather_rows_ref, combine_ref)
    text = ours.as_text()
    assert "tpu_custom_call" not in text
    # take_rows forward, its twin in combine's transpose, and at several
    # experts a token the two loops over the further rows (+ the plan's
    # search for the tiles' groups)
    assert len(re.findall(r" while\(", text)) == (3 if top_k == 1 else 5)
    # no float32 array of the buffer's size is written: the plain bodies'
    # ``float32(y) * w`` over every row was one

    def buffer_sized_f32(compiled):
        entry = compiled.as_text().split("\nENTRY ")[1]
        return re.findall(
            rf"= f32\[{tiles * 256},{d}\]\S* (?:fusion|scatter)\(", entry)

    assert buffer_sized_f32(plain) and not buffer_sized_f32(ours)
