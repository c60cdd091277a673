"""Backward-overlapped allreduce schedule tests (communicators/overlap.py
+ the hlo_audit async-pair census it is observed through).

The numerical contract (overlapped == eager, bit-exact, on every
communicator) lives in tests/test_packing.py and the schedule's census
in tests/test_overlap_census_golden.py; this module covers the schedule
builder itself, the env/flag plumbing, the compiled-HLO async-pair
parser (seeded text — CPU compiles never emit start/done pairs, so the
parser cannot be exercised through a live lowering here), and the
recompile-count guard on the staged train step.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.communicators import build_mesh, create_communicator
from chainermn_tpu.communicators.overlap import (
    ENV_OVERLAP,
    OverlapSchedule,
    build_overlap_schedule,
    overlap_enabled,
    resolve_granularity,
)
from chainermn_tpu.communicators.packing import (
    GradPacker,
    synthetic_grad_tree,
)


@pytest.fixture(scope="module")
def mesh24(devices8):
    return build_mesh(inter_size=2, intra_size=4, devices=devices8)


# ----------------------------------------------------------------------
# Schedule builder
# ----------------------------------------------------------------------
def test_schedule_reverse_leaf_production_order():
    tree = synthetic_grad_tree(12, 256 * 1024)
    packer = GradPacker.for_tree(tree, bucket_bytes=32 * 1024)
    sched = build_overlap_schedule(packer, granularity=1)

    assert sorted(sched.order) == list(range(packer.n_buckets))
    last = [max(packer.buckets[i].leaf_indices) for i in sched.order]
    assert last == sorted(last, reverse=True)
    assert sched.n_buckets == packer.n_buckets
    assert sched.n_stages == packer.n_buckets  # granularity 1
    assert all(len(s) == 1 for s in sched.stages)


@pytest.mark.parametrize("granularity", [1, 2, 3, 7, 100])
def test_schedule_stage_grouping(granularity):
    tree = synthetic_grad_tree(16, 512 * 1024)
    packer = GradPacker.for_tree(tree, bucket_bytes=64 * 1024)
    sched = build_overlap_schedule(packer, granularity=granularity)

    # Stages partition the same order the granularity-1 schedule emits.
    flat = build_overlap_schedule(packer, granularity=1).order
    assert sched.order == flat
    assert all(len(s) <= granularity for s in sched.stages)
    assert all(len(s) == granularity for s in sched.stages[:-1])
    d = sched.describe()
    assert d["n_buckets"] == packer.n_buckets
    assert d["granularity"] == max(1, granularity)


def test_schedule_empty_and_single_bucket():
    empty = build_overlap_schedule(
        GradPacker.for_tree({}, bucket_bytes=1024)
    )
    assert empty.stages == () and empty.order == ()

    one = build_overlap_schedule(GradPacker.for_tree(
        {"w": np.zeros((64,), np.float32)}, bucket_bytes=1024
    ))
    assert one.order == (0,)


def test_schedule_is_frozen():
    s = OverlapSchedule(stages=((0,),), granularity=1)
    with pytest.raises(Exception):
        s.granularity = 2


# ----------------------------------------------------------------------
# Env gate + XLA flag plumbing
# ----------------------------------------------------------------------
def test_overlap_enabled_gate(monkeypatch):
    monkeypatch.delenv(ENV_OVERLAP, raising=False)
    assert overlap_enabled() is True
    assert overlap_enabled(default=False) is False
    for off in ("0", "false", "OFF", "No", " off "):
        monkeypatch.setenv(ENV_OVERLAP, off)
        assert overlap_enabled() is False
    for on in ("1", "true", "yes", "anything"):
        monkeypatch.setenv(ENV_OVERLAP, on)
        assert overlap_enabled() is True


def test_resolve_granularity_env(monkeypatch):
    monkeypatch.delenv(
        "CHAINERMN_TPU_OVERLAP_GRANULARITY", raising=False
    )
    assert resolve_granularity() == 1
    assert resolve_granularity(default=5) == 5
    monkeypatch.setenv("CHAINERMN_TPU_OVERLAP_GRANULARITY", "4")
    assert resolve_granularity() == 4
    monkeypatch.setenv("CHAINERMN_TPU_OVERLAP_GRANULARITY", "-3")
    assert resolve_granularity() == 1  # clamped
    monkeypatch.setenv("CHAINERMN_TPU_OVERLAP_GRANULARITY", "junk")
    assert resolve_granularity(default=2) == 2


@pytest.mark.parametrize(
    "name", ["naive", "flat", "xla_ici", "hierarchical", "two_dimensional"])
def test_communicator_construction_leaves_xla_flags_alone(
        monkeypatch, devices8, name):
    """No compiler flag is written on anyone's behalf: jaxlib aborts the
    process at backend init on a flag it does not know, and bench's CPU
    children inherit the variable."""
    monkeypatch.delenv(ENV_OVERLAP, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # the chip machine's
    before = "--xla_force_host_platform_device_count=8  --xla_dummy=1 "
    monkeypatch.setenv("XLA_FLAGS", before)
    create_communicator(name, overlap=True)
    assert os.environ["XLA_FLAGS"] == before


# ----------------------------------------------------------------------
# Compiled-HLO async-pair census (seeded text: only TPU compiles split
# collectives into start/done pairs, so the parser is fed the HLO shape
# the latency-hiding scheduler produces)
# ----------------------------------------------------------------------
_SEEDED_HLO = """\
HloModule overlapped_bwd

ENTRY %main (p0: f32[65536], p1: f32[65536]) -> f32[65536] {
  %p0 = f32[65536]{0} parameter(0)
  %p1 = f32[65536]{0} parameter(1)
  %ars0 = f32[65536]{0} all-reduce-start(%p0), replica_groups={}, to_apply=%sum
  %bwd0 = f32[65536]{0} multiply(%p1, %p1)
  %ard0 = f32[65536]{0} all-reduce-done(%ars0)
  %ars1 = f32[65536]{0} all-reduce-start(%bwd0), replica_groups={}, to_apply=%sum
  %ard1 = f32[65536]{0} all-reduce-done(%ars1)
  %cps = (f32[65536]{0}, f32[65536]{0}) collective-permute-start(%ard0), source_target_pairs={{0,1},{1,0}}
  %bwd1 = f32[65536]{0} add(%ard0, %ard1)
  %cpd = f32[65536]{0} collective-permute-done(%cps)
  ROOT %out = f32[65536]{0} add(%bwd1, %cpd)
}
"""


def test_audit_hlo_text_folds_async_pairs():
    from chainermn_tpu.observability import audit_hlo_text

    audit = audit_hlo_text(_SEEDED_HLO)
    # 2 all-reduce pairs + 1 collective-permute pair = 3 logical
    # collectives, 2 of them reductions; never 6.  (census() is the
    # fixed-key zero-including view — compare the nonzero slice.)
    nonzero = {k: v for k, v in audit.census().items() if v}
    assert nonzero == {"psum": 2, "ppermute": 1}
    assert audit.reduction_collectives() == 2
    assert audit.async_pairs == 3
    # pairs with real compute strictly between start and done: ars0
    # (multiply) and cps (add); ars1 completes immediately -> 2/3.
    assert audit.overlap_fraction == pytest.approx(2 / 3)
    assert audit.op_bytes["psum"] == [65536 * 4, 65536 * 4]
    s = audit.summary()
    assert s["async_pairs"] == 3
    assert s["overlap_fraction"] == pytest.approx(2 / 3)


def test_fold_async_counts():
    from chainermn_tpu.observability import fold_async_counts

    assert fold_async_counts({
        "all-reduce-start": 4, "all-reduce-done": 4, "psum": 1,
    }) == {"psum": 5}
    assert fold_async_counts({
        "reduce-scatter-start": 2, "reduce-scatter-done": 2,
        "all-gather-start": 1, "all-gather-done": 1,
        "collective-permute-start": 3, "collective-permute-done": 3,
    }) == {"reduce_scatter": 2, "all_gather": 1, "ppermute": 3}
    # unmatched done never counts; unmatched start counts once
    assert fold_async_counts({"all-reduce-done": 2}) == {}
    assert fold_async_counts({"all-reduce-start": 2}) == {"psum": 2}


def test_audit_hlo_text_sync_collectives():
    """Plain (unsplit) HLO collectives still census under the jaxpr
    primitive names, with zero pairs."""
    from chainermn_tpu.observability import audit_hlo_text

    hlo = """\
ENTRY %main (p0: f32[128]) -> f32[128] {
  %p0 = f32[128]{0} parameter(0)
  %ar = f32[128]{0} all-reduce(%p0), replica_groups={}, to_apply=%sum
  ROOT %ag = f32[128]{0} all-gather(%ar), dimensions={0}
}
"""
    audit = audit_hlo_text(hlo)
    nonzero = {k: v for k, v in audit.census().items() if v}
    assert nonzero == {"psum": 1, "all_gather": 1}
    assert audit.async_pairs == 0
    assert audit.overlap_fraction == 0.0


def test_audit_compiled_on_cpu_lowering(mesh24):
    """The audit of a REAL compiled module; on CPU no async pairs
    exist.  XLA:CPU's all-reduce combiner may merge the bucket
    psums into fewer ops (one, on jaxlib 0.9.0), so what is asserted is
    what survives it: between 1 and n_buckets all-reduces whose operands
    are the buckets, one for one.  One psum per bucket BEFORE the
    compiler is held by the jaxpr census tests and the goldens."""
    import re

    from chainermn_tpu.observability import audit_hlo_text

    comm = create_communicator(
        "xla_ici", mesh=mesh24, bucket_bytes=32 * 1024
    )
    tree = synthetic_grad_tree(12, 256 * 1024)
    packer = GradPacker.for_tree(tree, bucket_bytes=32 * 1024)
    n = comm.device_size
    stacked = jax.tree.map(
        lambda l: jnp.stack([jnp.asarray(l)] * n), tree
    )

    def fn(t):
        def body(tt):
            sq = jax.tree.map(lambda x: jnp.squeeze(x, 0), tt)
            out = comm.allreduce_grad(sq)
            return jax.tree.map(lambda x: x[None], out)
        spec = jax.tree.map(lambda _: comm._world_spec, t)
        return comm.shard_map(body, in_specs=(spec,), out_specs=spec)(t)

    hlo = jax.jit(fn).lower(stacked).compile().as_text()
    audit = audit_hlo_text(hlo)
    assert 1 <= audit.census().get("psum", 0) <= packer.n_buckets
    assert audit.async_pairs == 0  # CPU backend: no start/done pairs
    # Elements, not bytes: XLA:CPU widens the bf16 buckets to f32.
    wire = [
        int(elems)
        for line in hlo.splitlines() if " all-reduce(" in line
        for elems in re.findall(
            r"\[(\d+)\]", line.split(" all-reduce(")[0])
    ]
    assert sorted(wire) == sorted(b.padded_elems for b in packer.buckets)


def test_r004_async_fixture_would_flag_unfolded():
    """The regression the fixture pins, shown directly: the raw
    start/done tally (8) crosses R004's >= n_leaves (6) threshold, the
    folded census (4) does not."""
    from chainermn_tpu.analysis.fixtures import (
        _ASYNC_PAIR_HLO,
        fixture_overlap_async_pairs,
    )
    from chainermn_tpu.observability import audit_hlo_text

    t = fixture_overlap_async_pairs()
    audit = t["audit"]
    assert audit.reduction_collectives() == 4 < t["n_leaves"]
    raw = audit_hlo_text(_ASYNC_PAIR_HLO)
    assert raw.async_pairs == 4
    # a double-counting census would have seen start + done = 2 per
    # pair, crossing R004's >= n_leaves threshold
    assert 2 * raw.async_pairs >= t["n_leaves"]


# ----------------------------------------------------------------------
# Staged train step: recompile-count guard
# ----------------------------------------------------------------------
def _leafy_loss(params, batch):
    scale = jnp.mean(batch.astype(jnp.float32) ** 2)
    return scale * sum(
        jnp.vdot(w, w) for w in jax.tree_util.tree_leaves(params)
    )


@pytest.mark.parametrize("overlap", [None, True, False])
def test_staged_step_compiles_once(mesh24, overlap):
    """The staged pipeline must not cost recompiles: after the first
    step establishes the device-resident arg shardings, repeated calls
    reuse one executable (cache size stabilizes, never grows per call)."""
    from chainermn_tpu.optimizers import create_multi_node_optimizer

    comm = create_communicator(
        "xla_ici", mesh=mesh24, bucket_bytes=16 * 1024
    )
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    params = {f"w{i}": jnp.ones((32, 32), jnp.float32) for i in range(6)}
    state = opt.init(params)
    step = opt.make_train_step(_leafy_loss, donate=False, overlap=overlap)
    assert hasattr(step, "_cache_size")
    batch = jnp.ones((comm.device_size * 2, 8), jnp.float32)

    params, state, _ = step(params, state, batch)
    warm = step._cache_size()
    for _ in range(3):
        params, state, loss = step(params, state, batch)
        # the first jax-array-input call may add ONE entry over the
        # numpy-input warmup; after that the count must be flat
        assert step._cache_size() <= warm + 1
    assert jnp.isfinite(loss)
    assert step._cache_size() == warm + 1 or step._cache_size() == warm


def test_staged_step_with_state_exposes_cache_size(mesh24):
    from chainermn_tpu.optimizers import create_multi_node_optimizer

    comm = create_communicator("xla_ici", mesh=mesh24)
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)

    def loss_fn(params, mstate, batch):
        return _leafy_loss(params, batch), {"n": mstate["n"] + 1.0}

    step = opt.make_train_step_with_state(loss_fn, donate=False)
    assert hasattr(step, "_cache_size")
    params = {"w": jnp.ones((16, 16), jnp.float32)}
    state = opt.init(params)
    mstate = {"n": jnp.zeros(())}
    out = step(params, state, mstate, jnp.ones((8, 8), jnp.float32))
    params, state, mstate, _ = out
    c1 = step._cache_size()
    step(params, state, mstate, jnp.ones((8, 8), jnp.float32))
    assert step._cache_size() <= c1 + 1


def test_train_step_overlap_pin_is_bit_exact(mesh24):
    """End-to-end: a full train step with overlap pinned ON vs OFF gives
    byte-identical params (the optimizer sees identical averaged
    grads)."""
    from chainermn_tpu.optimizers import create_multi_node_optimizer

    comm = create_communicator(
        "xla_ici", mesh=mesh24, bucket_bytes=16 * 1024
    )
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)

    def run(overlap):
        params = {
            f"w{i}": jnp.full((32, 32), 0.5 + i, jnp.float32)
            for i in range(6)
        }
        state = opt.init(params)
        step = opt.make_train_step(
            _leafy_loss, donate=False, overlap=overlap
        )
        batch = jnp.arange(
            comm.device_size * 2 * 8, dtype=jnp.float32
        ).reshape(comm.device_size * 2, 8) / 100.0
        params, state, loss = step(params, state, batch)
        return params, loss

    p_on, l_on = run(True)
    p_off, l_off = run(False)
    np.testing.assert_array_equal(np.asarray(l_on), np.asarray(l_off))
    for k in p_on:
        np.testing.assert_array_equal(
            np.asarray(p_on[k]).reshape(-1).view(np.uint8),
            np.asarray(p_off[k]).reshape(-1).view(np.uint8),
            err_msg=k,
        )
