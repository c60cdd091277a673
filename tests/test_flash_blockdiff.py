"""The block-diffusion mask in the three flash kernels — the rows are a
document's clean copy followed by its noised one, and the mask is stated
by ``(L, B)`` alone — against a dense float32 softmax under ``M``, in
interpret mode; the grid's walk of the live tiles against a brute-force
count of ``M``'s tiles; and what the mask refuses."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import make_flash_attention_fn

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

#: (L, B, block_q, block_k, H, Hk): B = 4 under tiles of 128 over an L of
#: three tiles; rectangular tiles either way; a block as wide as a
#: sub-tile; a block that is no power of two; MQA.
CASES = {
    "b4-t128-l384": (384, 4, 128, 128, 2, 2),
    "gqa-q64-k128": (256, 4, 64, 128, 4, 2),
    "q128-k64": (256, 16, 128, 64, 2, 1),
    "b8-q32-k64": (128, 8, 32, 64, 2, 2),
    "b6-odd": (192, 6, 64, 32, 2, 1),
}
D = 32


def dense(q, k, v, L, B):
    """Float32 softmax attention under ``M``, built here by comparison:
    nothing of the kernels' tables."""
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    rows = np.arange(2 * L)
    noisy, blk = rows >= L, (rows % L) // B
    qn, kn, qb, kb = noisy[:, None], noisy[None, :], blk[:, None], blk[None]
    M = np.where(qn, np.where(kn, kb == qb, kb < qb),
                 np.where(kn, False, kb <= qb))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(M[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


@pytest.fixture(scope="module")
def readings():
    out = {}
    for name, (L, B, bq, bk, H, Hk) in CASES.items():
        key = jax.random.split(jax.random.PRNGKey(len(name)), 4)
        q = jax.random.normal(key[0], (2, 2 * L, H, D))
        k = jax.random.normal(key[1], (2, 2 * L, Hk, D))
        v = jax.random.normal(key[2], (2, 2 * L, Hk, D))
        w = jax.random.normal(key[3], (2, 2 * L, H, D))

        def flash(q, k, v):
            return fa.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                      block_diffusion=B)

        def both(fn):
            o, grads = jax.value_and_grad(
                lambda q, k, v: (fn(q, k, v) * w).sum(), (0, 1, 2))(q, k, v)
            return (fn(q, k, v),) + grads

        out[name] = (both(flash), both(lambda q, k, v: dense(q, k, v, L, B)))
    return out


@pytest.mark.parametrize("which", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_under_the_mask_match_the_dense_softmax(
        readings, case, which):
    got, want = readings[case]
    i = ["o", "dq", "dk", "dv"].index(which)
    scale = float(jnp.abs(want[i]).max())
    np.testing.assert_allclose(got[i], want[i], atol=2e-5 * max(scale, 1.0))


def brute_tiles(L, B, bq, bk):
    """Tiles of the (2L / bq, 2L / bk) rectangle holding any pair of
    ``M``, from ``M`` itself."""
    M = np.asarray(fa.blockdiff_mask(L, B))
    return M.reshape(2 * L // bq, bq, 2 * L // bk, bk).any(axis=(1, 3))


@pytest.mark.parametrize("L,B,bq,bk", [
    (384, 4, 128, 128), (256, 4, 64, 128), (256, 16, 128, 64),
    (192, 6, 64, 32), (1024, 4, 256, 256), (512, 32, 128, 256)])
def test_the_census_counts_the_masks_own_tiles(L, B, bq, bk):
    live = brute_tiles(L, B, bq, bk)
    census = fa.tile_census(2 * L, 2 * L, bq, bk, True, None, (L, B))
    for kernel in ("fwd", "dq", "dkv"):
        assert census[kernel]["live"] == int(live.sum())
        # the grid IS the list of live tiles: no dead tile is visited
        assert census[kernel]["visited"] == int(live.sum())
        assert 0 < census[kernel]["copied"] <= census[kernel]["visited"]
    assert int(np.asarray(fa.blockdiff_mask(L, B)).sum()) == (
        fa.blockdiff_pairs(L, B))
    tq, tk, flags = fa._blockdiff_walk(L, B, bq, bk, "kv")
    assert sorted(zip(tq.tolist(), tk.tolist())) == sorted(
        zip(*map(np.ndarray.tolist, np.nonzero(live))))
    assert (np.diff(tq) >= 0).all()          # a q tile's tiles in a run
    kq, kk, _ = fa._blockdiff_walk(L, B, bq, bk, "q")
    assert (np.diff(kk) >= 0).all()          # the transposed statement
    # a tile flagged uncut holds no masked pair
    M = np.asarray(fa.blockdiff_mask(L, B))
    for q, k, f in zip(tq, tk, flags):
        tile = M[q * bq:(q + 1) * bq, k * bk:(k + 1) * bk]
        assert tile.all() == (not f & 4)
    # first and last flags bracket each q tile's run once
    assert (flags & 1 != 0).sum() == (flags & 2 != 0).sum() == 2 * L // bq


def test_the_cells_geometry_is_eighty_tiles_of_the_rectangles_256():
    census = fa.tile_census(16384, 16384, 1024, 1024, True, None, (8192, 4))
    # (56 of the 80 are interior — clean keys wholly before the queries'
    # first block — and run without a compare; the walk halves none)
    assert census["fwd"] == {"block_q": 1024, "block_k": 1024, "live": 80,
                             "visited": 80, "copied": 79, "cut": 24,
                             "halved": 0}
    assert census["dkv"]["live"] == census["dkv"]["visited"] == 80
    assert fa.blockdiff_pairs(8192, 4) == 67_141_632
    # half of the causal triangle of the same 16,384 rows (and a block)
    assert 2 * 67_141_632 - 16384 * 16385 // 2 == 16384 * 4 - 16384 // 2
    causal = fa.tile_census(16384, 16384, 1024, 1024, True, None)
    assert causal["fwd"]["visited"] == 256 and causal["fwd"]["live"] == 136


def test_the_grid_of_a_call_under_the_mask_is_its_live_tiles():
    L, B, bq, bk, BH, BHk = 256, 4, 64, 128, 4, 2
    q = jax.ShapeDtypeStruct((BH, 2 * L, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((BHk, 2 * L, D), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((BH, 2 * L, 1), jnp.float32)
    geometry = dict(scale=1.0, causal=True, block_q=bq, block_k=bk,
                    interpret=False, blockdiff=(L, B))

    def grids(jaxpr):
        out = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out.extend(grids(sub))
        return out

    live = int(brute_tiles(L, B, bq, bk).sum())
    fwd = jax.make_jaxpr(lambda q, k, v: fa._flash_bh_fwd(
        q, k, v, **geometry))(q, k, k)
    assert grids(fwd.jaxpr) == [(BH, live)]
    bwd = jax.make_jaxpr(lambda q, k, v, o, lse, do: fa._flash_bh_bwd(
        q, k, v, o, lse, do, **geometry))(q, k, k, q, lse, q)
    # (one pass on the forward's walk: rows this short fit its footprint)
    assert grids(bwd.jaxpr) == [(BH, live)]
    pair = jax.make_jaxpr(lambda q, k, v, o, lse, do: fa._flash_bwd_pair(
        q, k, v, o, lse, do, **geometry))(q, k, k, q, lse, q)
    assert grids(pair.jaxpr) == [(BH, live), (BHk, BH // BHk * live)]


def test_the_dense_path_and_the_adapter_take_the_same_mask():
    L, B = 64, 4
    key = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(key[0], (1, 2 * L, 2, 16))
    k = jax.random.normal(key[1], (1, 2 * L, 1, 16))
    v = jax.random.normal(key[2], (1, 2 * L, 1, 16))
    want = dense(q, k, v, L, B)
    got = fa._xla_attention(q, k, v, 0.25, True, blockdiff=(L, B))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # a row hands its block to the one adapter, as it hands a window
    fn = make_flash_attention_fn(causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(
        fn(q, k, v, None, block_diffusion=B), want, atol=2e-5)
    assert fa.row_mask({"window": None, "block_diffusion": None}) == {}
    assert fa.row_mask({"window": 8, "block_diffusion": None}) == {
        "window": 8}
    # blocks that do not divide L go to the dense path, with a warning
    with pytest.warns(UserWarning, match="Pallas kernel does not cover"):
        got = fa.flash_attention(q, k, v, block_q=128, block_k=128,
                                 block_diffusion=B)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_auto_block_size_knows_the_mask():
    # an edge divides L, not the 2 L rows: a tile lies in one copy
    assert fa.auto_block_size(16384, 128, jnp.bfloat16,
                              blockdiff=(8192, 4)) == 1024
    assert fa.auto_block_size(768, 128, jnp.bfloat16,
                              blockdiff=(384, 4)) == 384
    assert fa.auto_block_size(768, 128, jnp.bfloat16) == 768
    assert fa.auto_block_size(16384, 128, jnp.bfloat16, "bwd",
                              blockdiff=(8192, 4)) == 1024


@pytest.mark.parametrize("bad", [
    dict(causal=False), dict(window=8), dict(block_diffusion=0),
    dict(block_diffusion=5), dict(segments=True), dict(odd=True),
    dict(more_keys=True)])
def test_what_the_mask_refuses(bad):
    S = 63 if bad.pop("odd", False) else 64
    q = jnp.zeros((1, S, 2, 16))
    k = jnp.zeros((1, 2 * S if bad.pop("more_keys", False) else S, 2, 16))
    kw = dict(block_diffusion=4)
    if bad.pop("segments", False):
        ids = jnp.zeros((1, S), jnp.int32)
        kw.update(q_segment_ids=ids, kv_segment_ids=ids)
    kw.update(bad)
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(q, k, k, **kw)


def test_the_sequence_parallel_adapters_refuse_the_mask_by_name():
    from chainermn_tpu.parallel import ring_attention, ulysses

    for make in (ring_attention.make_ring_attention_fn,
                 ring_attention.make_zigzag_ring_attention_fn,
                 ulysses.make_ulysses_attention_fn):
        fn = make("sp")
        with pytest.raises(ValueError, match="block-diffusion"):
            fn(None, None, None, None, block_diffusion=4)


def test_the_mask_publishes_its_geometry():
    from chainermn_tpu.observability import reporter

    L, B = 128, 4
    x = jnp.zeros((1, 2 * L, 2, 16))
    rep = reporter.Reporter()
    with reporter.scope(rep):
        fa.flash_attention(x, x, x, block_q=64, block_k=64,
                           block_diffusion=B)
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()}
    live = int(brute_tiles(L, B, 64, 64).sum())
    assert gauges["blockdiff/L"] == L and gauges["blockdiff/B"] == B
    assert gauges["blockdiff/rows"] == 2 * L
    assert gauges["blockdiff/live_pairs"] == L * (L + B)
    # (the backward is one pass: its tiles are flash-bwd-dkv's)
    assert gauges["flash/bwd_fused"] == 1
    assert "flash/flash-bwd-dq/visited" not in gauges
    for kernel in ("flash-fwd", "flash-bwd-dkv"):
        assert gauges[f"blockdiff/{kernel}/live"] == live
        assert gauges[f"blockdiff/{kernel}/visited"] == live
        assert gauges[f"flash/{kernel}/visited"] == live
    # a call without the mask publishes none of it
    rep = reporter.Reporter()
    with reporter.scope(rep):
        fa.flash_attention(x, x, x, block_q=64, block_k=64)
    assert not [k for k in rep.summary()["gauges"] if "blockdiff" in k]


@pytest.mark.parametrize("which", ["dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_one_pass_backward_under_the_mask_is_the_two_kernels(case, which):
    """``_flash_bwd_fused`` against ``_flash_bwd_pair`` on the walk of the
    mask's live tiles, bfloat16 operands: ``dq`` sums over a q tile's K
    tiles in the walk's order on both sides and is EQUAL; so are ``dk``
    and ``dv`` where a KV row has one query head.  With a group the two
    kernels' dk/dv walk puts the heads INSIDE a tile's steps (``(q block,
    head)``) and the one pass has them outside (``(head, q block)``), so
    the float32 sums can differ in their last place: within 2e-3 of the
    largest gradient after the cast to bfloat16 (measured on these cases:
    ``gqa-q64-k128`` alone differs, 3 of 65,536 ``dk`` elements and 1
    ``dv`` by one bfloat16 step, 6.1e-5 at |dk| <= 3.6; the two MQA cases
    come out equal)."""
    L, B, bq, bk, H, Hk = CASES[case]
    key = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q = jax.random.normal(key[0], (2 * H, 2 * L, D), jnp.bfloat16)
    k = jax.random.normal(key[1], (2 * Hk, 2 * L, D), jnp.bfloat16)
    v = jax.random.normal(key[2], (2 * Hk, 2 * L, D), jnp.bfloat16)
    do = jax.random.normal(key[3], (2 * H, 2 * L, D), jnp.bfloat16)
    geometry = dict(scale=D ** -0.5, causal=True, block_q=bq, block_k=bk,
                    interpret=True, blockdiff=(L, B))
    o, lse = fa._flash_bh_fwd(q, k, v, **geometry)
    i = ["dq", "dk", "dv"].index(which)
    fused = np.asarray(fa._flash_bwd_fused(
        q, k, v, o, lse, do, **geometry)[i], np.float32)
    pair = np.asarray(fa._flash_bwd_pair(
        q, k, v, o, lse, do, **geometry)[i], np.float32)
    assert np.abs(pair).max() > 0
    if which == "dq" or H == Hk:
        np.testing.assert_array_equal(fused, pair)
    else:
        np.testing.assert_allclose(fused, pair, rtol=0,
                                   atol=2e-3 * np.abs(pair).max())
