"""The flash kernels, the loss head's gradient, the causal
convolution's backward, the state-space scan's and the gated delta rule's
two kernels each, the grouped matmuls and the dropless dispatch's row
movers, compiled for a described TPU v5e, without the chip.

Interpret mode cannot show what Mosaic refuses: a block that is not
aligned to the tiling, or more scoped VMEM than a kernel may use.  The
TPU's compiler is installed here and compiles for a chip that is
described and not attached (``jax.experimental.topologies``), a few
seconds a kernel.  The static default geometry has to compile inside the
default scoped VMEM at every head dim, dtype and mask the rule sizes it
for, and a pinned geometry past it under the limit the kernels compute.

Only one process at a time may load the TPU's library, so the topology
is described inside a fixture of this one file (never at import), and
every compile runs in the test's own process.
"""

import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: the next run would warn
    # on every entry.  Off for this file's tests.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(one_chip, *, S, D, dtype, bq, bk, which, segmented=False,
             window=None, BH=128, BHk=None):
    def arr(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, col = arr(BH, S, D), arr(BH, S, 1, dt=jnp.float32)
    kv = arr(BHk or BH, S, D)       # fewer kv head rows: GQA
    seg = {}
    operands = [q, kv, kv] if which == "fwd" else [q, kv, kv, q, col, q]
    if segmented:
        operands += [arr(BH, S, 1, dt=jnp.int32)] * 2

    def fn(*a):
        if segmented:
            *a, qs, ks = a
            seg.update(q_seg=qs, kv_seg=ks)
        kernel = fa._flash_bh_fwd if which == "fwd" else fa._flash_bh_bwd
        return kernel(*a, scale=0.1, causal=True, block_q=bq, block_k=bk,
                      interpret=False, window=window, **seg)

    compiled = jax.jit(fn).lower(*operands).compile()
    assert compiled.as_text().count("tpu_custom_call") == (
        1 if which == "fwd" else 2)
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
    assert again.as_text().count("tpu_custom_call") == 4
    entry = kept.as_text().split("\nENTRY ")[1].splitlines()
    assert sum("tpu_custom_call" in line for line in entry) == 3
    column = r"f32\[64,8192,1\]\{2,1,0:T\(8,128\)"
    flat = r"f32\[64,8192\]\{1,0:T\(8,128\)"
    at = {what: [i for i, line in enumerate(entry)
                 if re.match(rf"\s*%{pattern}", line)]
          for what, pattern in (
              ("fwd", rf"flash-fwd\S* = \(bf16\[64,8192,64\]\S+, "
                      rf"{column}\S*\) custom-call\("),
              ("flat", rf"\S+ = {flat}\S* reduce\("),
              ("bwd", r"flash-bwd-d\S+ = .* custom-call\("))}
    assert len(at["fwd"]) == 1 and len(at["bwd"]) == 2, at
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


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_pinned_geometry_past_the_default_gets_its_limit(one_chip, which):
    """2048 x 2048 needs more than the default 16 MiB: it compiles
    because the kernels ask for their own footprint."""
    footprint = fa.flash_vmem_bytes(2048, 2048, 128, 2, which)
    assert fa._compiler_params(footprint).vmem_limit_bytes > footprint
    _compile(one_chip, S=2048, D=128, dtype=jnp.bfloat16, bq=2048, bk=2048,
             which=which)


@pytest.mark.parametrize("bq,bk,window", [
    (512, 1024, None), (1024, 256, None), (256, 256, 300)])
def test_banded_index_maps_compile(one_chip, bq, bk, window):
    """Rectangular blocks and a sliding window: the clamped index maps
    lower through Mosaic in all three kernels."""
    for which in ("fwd", "bwd"):
        _compile(one_chip, S=2048, D=128, dtype=jnp.bfloat16, bq=bq, bk=bk,
                 which=which, window=window)


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


def test_zaya_layer_compiles_at_the_cells_shape(one_chip, monkeypatch):
    """One ``zaya`` layer at the cell's shape (2 x 8192 tokens, the CCA
    mixer's latent 8/2 heads of 128, 8 of 16 gated experts of 2048 held:
    a buffer of 18,432 rows), forward and backward under remat with the
    model's policy as in the step: the three flash calls (the forward
    ONCE: its output and row statistics are kept, PR 35) and nine grouped
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
    assert text.count("tpu_custom_call") == 3 + 9
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


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
        assert text.count("tpu_custom_call") == 3 + 9
        assert calls == {"ssm-conv-fwd": 0, "ssm-conv-bwd": 0, "gdn-fwd": 0,
                         "gdn-bwd": 0, "flash-fwd": 1, "flash-bwd-dq": 1,
                         "flash-bwd-dkv": 1}
        assert "gdn-scan" not in text
        assert temporaries < 4e9


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
    assert calls == {"flash-fwd": 1, "flash-bwd-dq": 1, "flash-bwd-dkv": 1}
    assert text.count("tpu_custom_call") == 3 + 9
    scope = "attn-window" if kind == "sliding" else "attn-mixer"
    tiles = device_trace.scope_table(text).tiles_within
    assert set(tiles) >= {scope} and not (
        {"attn-window", "attn-mixer"} - {scope}) & set(tiles)
    for region in ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv"):
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
