"""Bucketed gradient packing tests (communicators/packing.py).

Reference lineage: the reference validated its flat-buffer fusion by
round-tripping ``pack_params``/``unpack_params`` against the original
arrays (REF:chainermn tests).  Here the same contract is stronger — the
pack/unpack pair must be BIT-exact (pure layout moves), and the bucketed
``allreduce_grad`` must match the unbucketed lowering numerically on
every communicator, because bucketing defaults ON.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.communicators import build_mesh, create_communicator
from chainermn_tpu.communicators.overlap import (
    ENV_OVERLAP,
    ENV_OVERLAP_GRANULARITY,
)
from chainermn_tpu.communicators.packing import (
    DEFAULT_BUCKET_BYTES,
    ENV_BUCKET_BYTES,
    LANE_ELEMS,
    GradPacker,
    pack_tree,
    synthetic_grad_tree,
)

ALL_NAMES = ["naive", "flat", "xla_ici", "hierarchical", "two_dimensional"]


@pytest.fixture(scope="module")
def mesh24(devices8):
    """One fixed (inter=2, intra=4) mesh — parity/census tests assert
    per-communicator structure, not mesh-shape coverage (the mesh sweep
    lives in test_communicator.py)."""
    return build_mesh(inter_size=2, intra_size=4, devices=devices8)


def _random_tree(seed: int, n_leaves: int) -> dict:
    """Pseudo-property input: random shapes (incl. scalars and 3-D),
    random dtypes, deterministic per seed."""
    rng = np.random.default_rng(seed)
    dts = [np.dtype("float32"), np.dtype("float16"),
           np.dtype(jnp.bfloat16)]
    tree = {}
    for i in range(n_leaves):
        kind = rng.integers(0, 4)
        if kind == 0:
            shape: tuple = ()
        elif kind == 1:
            shape = (int(rng.integers(1, 2000)),)
        elif kind == 2:
            shape = (int(rng.integers(1, 60)), int(rng.integers(1, 60)))
        else:
            shape = (int(rng.integers(1, 8)), int(rng.integers(1, 8)),
                     int(rng.integers(1, 8)))
        dt = dts[int(rng.integers(0, len(dts)))]
        vals = rng.integers(-128, 128, size=shape).astype(np.float32) / 32.0
        tree[f"leaf_{i:03d}"] = vals.astype(dt)
    return tree


TREES = {
    "mixed_synthetic": lambda: synthetic_grad_tree(16, 1 << 20),
    "all_scalars": lambda: {
        "a": np.float32(1.5),
        "b": np.asarray(2.0, np.dtype(jnp.bfloat16)),
        "c": np.float32(-3.25),
    },
    "single_giant_leaf": lambda: {
        "w": (np.arange(200_000, dtype=np.float32) % 97) / 32.0,
    },
    "bucket_straddle": lambda: {
        # cap 512 B = 128 f32 elems: l0+l1 fill a bucket EXACTLY, l2
        # opens the next, l3 straddles past the cap into a third.
        "l0": np.full((64,), 1.0, np.float32),
        "l1": np.full((64,), 2.0, np.float32),
        "l2": np.full((100,), 3.0, np.float32),
        "l3": np.full((100,), 4.0, np.float32),
    },
    "empty": lambda: {},
    "random_0": lambda: _random_tree(0, 13),
    "random_1": lambda: _random_tree(1, 21),
    "random_2": lambda: _random_tree(2, 7),
}


@pytest.mark.parametrize("tree_name", sorted(TREES))
@pytest.mark.parametrize("bucket_bytes", [512, 64 * 1024, DEFAULT_BUCKET_BYTES])
def test_pack_unpack_bit_exact(tree_name, bucket_bytes):
    tree = TREES[tree_name]()
    packer = GradPacker.for_tree(tree, bucket_bytes=bucket_bytes)
    out = packer.unpack(packer.pack(tree))

    in_leaves, in_def = jax.tree.flatten(tree)
    out_leaves, out_def = jax.tree.flatten(out)
    assert in_def == out_def
    assert len(in_leaves) == len(out_leaves)
    for a, b in zip(in_leaves, out_leaves):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(
            np.asarray(a, dtype=np.asarray(a).dtype).reshape(-1).view(np.uint8),
            np.asarray(b).reshape(-1).view(np.uint8),
        )


@pytest.mark.parametrize("tree_name", sorted(TREES))
@pytest.mark.parametrize("bucket_bytes", [512, 64 * 1024])
def test_plan_invariants(tree_name, bucket_bytes):
    tree = TREES[tree_name]()
    packer = GradPacker.for_tree(tree, bucket_bytes=bucket_bytes)

    # Buckets partition the leaves exactly (no loss, no duplication).
    covered = sorted(i for b in packer.buckets for i in b.leaf_indices)
    assert covered == list(range(packer.n_leaves))

    for b in packer.buckets:
        # Single dtype per bucket, matching its member leaves.
        assert all(packer.dtypes[i] == b.dtype for i in b.leaf_indices)
        assert b.elems == sum(packer.sizes[i] for i in b.leaf_indices)
        assert b.padded_elems >= b.elems
        # Padding rule: pow2, or lane-aligned when pow2 would overshoot.
        cap_elems = max(1, bucket_bytes // b.dtype.itemsize)
        p = 1 << max(0, b.elems - 1).bit_length()
        if p <= cap_elems:
            assert b.padded_elems == p
        else:
            assert b.padded_elems % LANE_ELEMS == 0
            assert b.padded_elems - b.elems < LANE_ELEMS
        # Cap respected unless the bucket is a single oversize leaf.
        if len(b.leaf_indices) > 1:
            assert b.payload_bytes <= bucket_bytes


def test_bucket_straddle_plan_shape():
    """The hand-built straddle case lands exactly as designed: a full
    bucket, then the cap forces two more."""
    packer = GradPacker.for_tree(TREES["bucket_straddle"](), bucket_bytes=512)
    assert [list(b.leaf_indices) for b in packer.buckets] == [[0, 1], [2], [3]]
    assert packer.buckets[0].elems == packer.buckets[0].padded_elems == 128


def test_empty_tree_plan():
    packer = GradPacker.for_tree({}, bucket_bytes=1024)
    assert packer.n_buckets == 0 and packer.n_leaves == 0
    assert packer.pack({}) == []
    assert packer.unpack([]) == {}


def test_gradpacker_rejects_nonpositive_cap():
    with pytest.raises(ValueError, match="bucket_bytes"):
        GradPacker.for_tree({"a": np.zeros(4, np.float32)}, bucket_bytes=0)


def test_gradpacker_rejects_mismatched_tree():
    packer = GradPacker.for_tree({"a": np.zeros(4, np.float32)})
    with pytest.raises(ValueError, match="leaf 0"):
        packer.pack({"a": np.zeros(5, np.float32)})
    with pytest.raises(ValueError, match="buffers"):
        packer.unpack([])


def test_pack_tree_roundtrip_and_padding():
    tree = synthetic_grad_tree(6, 1 << 14, dtypes=("float32",))
    flat, unpack = pack_tree(tree)
    size = sum(l.size for l in jax.tree.leaves(tree))
    assert flat.shape == (size,)
    out = unpack(flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    padded, unpack2 = pack_tree(tree, pad_to=size + 37)
    assert padded.shape == (size + 37,)
    assert np.all(np.asarray(padded)[size:] == 0)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(unpack2(padded))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    with pytest.raises(ValueError, match="pad_to"):
        pack_tree(tree, pad_to=size - 1)


def _stacked(tree, n):
    """Per-rank-distinct stacked input for eager_allreduce_grad."""
    return jax.tree.map(
        lambda l: jnp.stack(
            [jnp.asarray(l) + jnp.asarray(r, l.dtype) for r in range(n)]
        ),
        tree,
    )


@pytest.mark.parametrize("name", ALL_NAMES)
def test_bucketed_matches_unbucketed(mesh24, name):
    """The acceptance parity bound: bucketed vs bucket_bytes=0 on the
    same communicator agree to fp32 exactness (both lowerings psum the
    same values; only the layout differs)."""
    tree = synthetic_grad_tree(12, 256 * 1024)
    bucketed = create_communicator(name, mesh=mesh24, bucket_bytes=32 * 1024)
    unbucketed = create_communicator(name, mesh=mesh24, bucket_bytes=0)
    n = bucketed.device_size
    stacked = _stacked(tree, n)

    out_b = bucketed.eager_allreduce_grad(stacked)
    out_u = unbucketed.eager_allreduce_grad(stacked)

    for k in tree:
        a, b = np.asarray(out_b[k]), np.asarray(out_u[k])
        assert a.dtype == b.dtype
        if a.dtype == np.float32:
            np.testing.assert_allclose(
                a.astype(np.float32), b.astype(np.float32), rtol=1e-6,
                atol=1e-6, err_msg=k,
            )
        else:  # low-precision leaves: cast-dtype tolerance
            np.testing.assert_allclose(
                a.astype(np.float32), b.astype(np.float32), rtol=2e-2,
                atol=2e-2, err_msg=k,
            )


@pytest.mark.parametrize("name", ALL_NAMES)
def test_overlapped_matches_eager_bit_exact(mesh24, name):
    """The tentpole acceptance bound: the backward-overlapped schedule is
    BIT-exact against the eager bucketed path on every communicator —
    the same per-bucket collectives over the same operands, only the
    emission order differs, so the results are byte-identical (not
    merely allclose)."""
    tree = synthetic_grad_tree(12, 256 * 1024)
    overlapped = create_communicator(
        name, mesh=mesh24, bucket_bytes=32 * 1024, overlap=True,
        overlap_granularity=1,
    )
    eager = create_communicator(
        name, mesh=mesh24, bucket_bytes=32 * 1024, overlap=False,
    )
    stacked = _stacked(tree, overlapped.device_size)

    out_o = overlapped.eager_allreduce_grad(stacked)
    out_e = eager.eager_allreduce_grad(stacked)

    for k in tree:
        a, b = np.asarray(out_o[k]), np.asarray(out_e[k])
        assert a.dtype == b.dtype, k
        np.testing.assert_array_equal(
            a.reshape(-1).view(np.uint8),
            b.reshape(-1).view(np.uint8),
            err_msg=k,
        )


def test_overlapped_stays_bit_exact_where_a_ring_could_engage(
        mesh24, monkeypatch):
    """``xla_ici`` has a ring (``communicators/ring.py``) for buckets this
    large (from 64 KiB here), but a bucket rides it only where a train
    step pins its hops under the backward pass
    (``mean_grads_under``): ``allreduce_grad`` pins nothing, so its
    staged emission keeps ``psum`` and the contract above holds bit for
    bit — the same bits on every device, the eager program's bits."""
    from chainermn_tpu.communicators import overlap

    monkeypatch.setattr(overlap, "RING_MIN_BYTES", 64 * 1024)
    tree = synthetic_grad_tree(6, 2 << 20, dtypes=("float32",))
    make = lambda over: create_communicator(  # noqa: E731
        "xla_ici", mesh=mesh24, bucket_bytes=256 * 1024, overlap=over)
    stacked = _stacked(tree, 8)
    out_o = make(True).eager_allreduce_grad(stacked)
    out_e = make(False).eager_allreduce_grad(stacked)
    for k in tree:
        a, b = np.asarray(out_o[k]), np.asarray(out_e[k])
        for r in range(1, 8):
            np.testing.assert_array_equal(a[r], a[0], err_msg=k)
        np.testing.assert_array_equal(a, b, err_msg=k)


def test_overlap_granularity_bit_exact(mesh24):
    """Stage width changes the emission batching, never the values."""
    tree = synthetic_grad_tree(12, 256 * 1024)
    base = create_communicator(
        "xla_ici", mesh=mesh24, bucket_bytes=32 * 1024, overlap=False,
    )
    stacked = _stacked(tree, base.device_size)
    ref = base.eager_allreduce_grad(stacked)
    for g in (1, 3, 100):
        comm = create_communicator(
            "xla_ici", mesh=mesh24, bucket_bytes=32 * 1024, overlap=True,
            overlap_granularity=g,
        )
        out = comm.eager_allreduce_grad(stacked)
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(out[k]).reshape(-1).view(np.uint8),
                np.asarray(ref[k]).reshape(-1).view(np.uint8),
                err_msg=f"granularity={g} {k}",
            )


@pytest.mark.parametrize("name", ["xla_ici", "hierarchical"])
def test_bucketed_allreduce_grad_dtype_roundtrip(mesh24, name):
    """allreduce_grad_dtype cast composes with bucketing: leaves come
    back in their ORIGINAL dtypes and values stay ~mean."""
    comm = create_communicator(
        name, mesh=mesh24, allreduce_grad_dtype=jnp.bfloat16,
        bucket_bytes=16 * 1024,
    )
    tree = synthetic_grad_tree(8, 64 * 1024, dtypes=("float32",))
    n = comm.device_size
    stacked = _stacked(tree, n)
    out = comm.eager_allreduce_grad(stacked)
    for k in tree:
        assert out[k].dtype == stacked[k].dtype
        expected = np.mean(np.asarray(stacked[k], np.float32), axis=0)
        np.testing.assert_allclose(
            np.asarray(out[k])[0], expected, rtol=2e-2, atol=2e-2,
        )


def test_scatter_inter_hierarchical_parity(mesh24):
    """Satellite: the scatter-decomposed inter leg is numerically the
    same allreduce."""
    base = create_communicator("naive", mesh=mesh24, bucket_bytes=0)
    scat = create_communicator(
        "hierarchical", mesh=mesh24, scatter_inter=True, bucket_bytes=0,
    )
    tree = synthetic_grad_tree(6, 64 * 1024, dtypes=("float32",))
    stacked = _stacked(tree, base.device_size)
    out_b = base.eager_allreduce_grad(stacked)
    out_s = scat.eager_allreduce_grad(stacked)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(out_s[k]), np.asarray(out_b[k]), rtol=1e-6, atol=1e-6,
        )


def test_scatter_inter_rejected_elsewhere(mesh24):
    with pytest.raises(ValueError, match="scatter_inter"):
        create_communicator("flat", mesh=mesh24, scatter_inter=True)


def test_env_escape_hatch(mesh24, monkeypatch):
    comm = create_communicator("naive", mesh=mesh24)
    assert comm.resolve_bucket_bytes() == DEFAULT_BUCKET_BYTES

    monkeypatch.setenv(ENV_BUCKET_BYTES, "0")
    assert comm.resolve_bucket_bytes() == 0

    monkeypatch.setenv(ENV_BUCKET_BYTES, "65536")
    assert comm.resolve_bucket_bytes() == 65536

    # An explicit constructor value beats the environment.
    pinned = create_communicator("naive", mesh=mesh24, bucket_bytes=123)
    assert pinned.resolve_bucket_bytes() == 123

    with pytest.raises(ValueError, match="bucket_bytes"):
        create_communicator("naive", mesh=mesh24, bucket_bytes=-1)


def test_overlap_env_escape_hatch(mesh24, monkeypatch):
    comm = create_communicator("naive", mesh=mesh24)
    monkeypatch.delenv(ENV_OVERLAP, raising=False)
    assert comm.resolve_overlap() is True  # ON by default

    for off in ("0", "false", "off", "no"):
        monkeypatch.setenv(ENV_OVERLAP, off)
        assert comm.resolve_overlap() is False
    monkeypatch.setenv(ENV_OVERLAP, "1")
    assert comm.resolve_overlap() is True

    # Call-site pin beats ctor beats env.
    monkeypatch.setenv(ENV_OVERLAP, "0")
    pinned = create_communicator("naive", mesh=mesh24, overlap=True)
    assert pinned.resolve_overlap() is True
    assert pinned.resolve_overlap(overlap=False) is False
    assert comm.resolve_overlap(overlap=True) is True

    # Granularity: ctor → env → default 1.
    monkeypatch.delenv(ENV_OVERLAP_GRANULARITY, raising=False)
    assert comm.resolve_overlap_granularity() == 1
    monkeypatch.setenv(ENV_OVERLAP_GRANULARITY, "3")
    assert comm.resolve_overlap_granularity() == 3
    g2 = create_communicator("naive", mesh=mesh24, overlap_granularity=2)
    assert g2.resolve_overlap_granularity() == 2
    with pytest.raises(ValueError, match="overlap_granularity"):
        create_communicator("naive", mesh=mesh24, overlap_granularity=0)


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell_config_files():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return [c["file"] for c in json.load(f)["configs"]]


@pytest.mark.parametrize("config_file", _cell_config_files())
def test_default_communicator_is_the_measured_one(config_file, monkeypatch):
    """A communicator built with no arguments and no environment is the
    program the ledger measured: every value a cell's configuration file
    pins under ``program.communicator`` is what the defaults resolve to."""
    from chainermn_tpu.communicators.quant import ENV_COMM_DTYPE

    for env in (ENV_BUCKET_BYTES, ENV_OVERLAP, ENV_OVERLAP_GRANULARITY,
                ENV_COMM_DTYPE):
        monkeypatch.delenv(env, raising=False)
    with open(os.path.join(REPO_ROOT, config_file)) as f:
        pinned = json.load(f)["program"]["communicator"]
    comm = create_communicator()
    assert {
        "name": comm.name,
        "bucket_bytes": comm.resolve_bucket_bytes(),
        "overlap": comm.resolve_overlap(),
        "overlap_granularity": comm.resolve_overlap_granularity(),
        "comm_dtype": comm.resolve_comm_dtype() or "none",
    } == pinned


#: reduction collectives each variant lowers PER BUCKET: one fused psum
#: for the single-collective backends, psum(intra)+psum(inter) for
#: hierarchical, psum_scatter+psum for two_dimensional.  The ISSUE
#: acceptance bound is <= 2 per dtype bucket.
REDUCTIONS_PER_BUCKET = {
    "naive": 1,
    "flat": 1,
    "xla_ici": 1,
    "hierarchical": 2,
    "two_dimensional": 2,
}


@pytest.mark.parametrize("name", ALL_NAMES)
def test_census_independent_of_leaf_count(mesh24, name):
    """The tentpole's point, asserted at the jaxpr level: reduction
    collectives scale with n_buckets, not n_leaves."""
    from chainermn_tpu.observability import audit_allreduce_tree

    tree = synthetic_grad_tree(24, 512 * 1024)
    comm = create_communicator(name, mesh=mesh24, bucket_bytes=64 * 1024)
    plan = GradPacker.for_tree(tree, bucket_bytes=64 * 1024)
    assert plan.n_buckets < plan.n_leaves

    audit = audit_allreduce_tree(comm, tree)
    per_bucket = REDUCTIONS_PER_BUCKET[name]
    assert audit.reduction_collectives() == per_bucket * plan.n_buckets
    assert per_bucket <= 2

    # Per-axis operand bytes are conserved: the intra leg always carries
    # the full payload; the inter leg carries at least its 1/intra_size
    # shard (scatter-decomposed algorithms charge exactly that — the
    # whole point of two_dimensional).
    assert audit.bytes_per_axis.get("intra", 0) >= plan.payload_bytes
    assert (audit.bytes_per_axis.get("inter", 0)
            >= plan.payload_bytes // comm.intra_size)


def test_unbucketed_census_scales_with_leaves(mesh24):
    from chainermn_tpu.observability import audit_allreduce_tree

    tree = synthetic_grad_tree(24, 512 * 1024)
    comm = create_communicator("naive", mesh=mesh24, bucket_bytes=0)
    audit = audit_allreduce_tree(comm, tree)
    assert audit.reduction_collectives() == 24


def test_single_leaf_tree_skips_bucketing(mesh24):
    """One leaf → the direct path, regardless of bucket_bytes: the
    single-buffer census must not change."""
    from chainermn_tpu.observability import audit_allreduce_tree

    comm = create_communicator("xla_ici", mesh=mesh24)
    tree = {"g": np.zeros((1000,), np.float32)}
    audit = audit_allreduce_tree(comm, tree)
    assert audit.reduction_collectives() == 1
    assert audit.op_bytes["psum"] == [4000]


def test_synthetic_grad_tree_deterministic():
    a = synthetic_grad_tree(16, 1 << 20)
    b = synthetic_grad_tree(16, 1 << 20)
    assert list(a) == list(b)
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
        np.testing.assert_array_equal(
            np.asarray(a[k]).reshape(-1).view(np.uint8),
            np.asarray(b[k]).reshape(-1).view(np.uint8),
        )
    # leaf 0 is the scalar edge case, and 2-D leaves exist
    assert a["leaf_000"].shape == ()
    assert any(np.asarray(v).ndim == 2 for v in a.values())
