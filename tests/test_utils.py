"""Native host-buffer library, collective-order debug mode, profiling."""

import os

import numpy as np
import pytest

from chainermn_tpu.utils import debug, native, profiling


def test_native_lib_builds():
    lib = native.get_lib()
    assert lib is not None, "g++ build of csrc/hostbuf.cpp failed"


def test_crc32c_known_vector():
    # RFC 3720 test vector: crc32c of 32 zero bytes.
    if native.get_lib() is None:
        pytest.skip("native lib unavailable")
    assert native.crc32c(b"\x00" * 32) == 0x8A9136AA
    assert native.crc32c(b"123456789") == 0xE3069283


def test_parallel_gather_matches_stack():
    items = [np.random.RandomState(i).randn(16, 16).astype(np.float32) for i in range(32)]
    out = native.parallel_gather(items)
    np.testing.assert_array_equal(out, np.stack(items))


def test_parallel_gather_rejects_mismatch():
    with pytest.raises(ValueError, match="equal-shaped"):
        native.parallel_gather([np.zeros((2, 2)), np.zeros((2, 3))])


def test_pack_unpack_ragged_roundtrip():
    """gatherv/scatterv over ragged shapes+dtypes (the checkpoint payload
    shape): bytes concatenate exactly and scatter back bit-identical."""
    rng = np.random.RandomState(0)
    arrays = [
        rng.randn(3, 5).astype(np.float32),
        rng.randint(0, 100, size=(7,)).astype(np.int64),
        np.float64(rng.randn()) * np.ones(()),
        rng.randn(2, 2, 2).astype(np.float16),
    ]
    buf = native.pack_buffers(arrays)
    assert buf.nbytes == sum(a.nbytes for a in arrays)
    # Byte-exact layout: manual concatenation agrees.
    manual = np.concatenate(
        [np.ascontiguousarray(a).view(np.uint8).ravel() for a in arrays]
    )
    np.testing.assert_array_equal(buf, manual)
    outs = [np.empty_like(a) for a in arrays]
    native.unpack_buffers(buf, outs)
    for a, o in zip(arrays, outs):
        np.testing.assert_array_equal(a, o)


def test_fallback_paths_handle_0d_and_match_native(monkeypatch):
    """The no-toolchain fallbacks must handle everything the native path
    does — including 0-d arrays (scalar labels, step counters), which
    ndarray.view(uint8) rejects."""
    arrays = [
        np.asarray(np.float32(7.0)),  # 0-d
        np.arange(6.0, dtype=np.float32).reshape(2, 3),
        np.arange(5).astype(np.int64),
    ]
    native_buf = native.pack_buffers(arrays)
    native_crc = native.crc32c(native_buf)

    monkeypatch.setattr(native, "get_lib", lambda: None)
    buf = native.pack_buffers(arrays)
    np.testing.assert_array_equal(buf, native_buf)
    outs = [np.empty_like(a) for a in arrays]
    native.unpack_buffers(buf, outs)
    for a, o in zip(arrays, outs):
        np.testing.assert_array_equal(a, o)
    assert native.crc32c(buf) == native_crc
    # 0-d ndarray checksums its 4 raw bytes, same as the equivalent bytes.
    scalar = np.asarray(np.float32(1.5))
    assert native.crc32c(scalar) == native.crc32c(scalar.tobytes())
    # parallel_gather fallback with scalar items (label batches).
    labels = [np.int32(i) for i in range(5)]
    np.testing.assert_array_equal(
        native.parallel_gather(labels), np.arange(5, dtype=np.int32)
    )


def test_crc32c_incremental_chaining():
    """Streaming crc (seed chaining) equals one-shot crc — the checkpoint
    writer relies on this across payload chunks."""
    data = np.random.RandomState(1).bytes(100_000)
    one = native.crc32c(data)
    acc = 0
    for i in range(0, len(data), 33_333):
        acc = native.crc32c(data[i : i + 33_333], acc)
    assert acc == one


def test_native_queue_roundtrip():
    q = native.NativeQueue(capacity=2)
    assert q.push(b"hello")
    assert q.push(b"world")
    assert q.size() == 2
    assert q.pop(16) == b"hello"
    assert q.pop(16) == b"world"
    q.close()


def test_collective_trace_records_and_fingerprints(mesh):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.communicators import create_communicator

    comm = create_communicator("naive", mesh=mesh)
    dbg = debug.CollectiveTrace(comm)

    def body(x):
        v = dbg.allreduce(x[0], "sum")
        v = dbg.bcast(v, 0)
        return v[None]

    f = jax.jit(
        comm.shard_map(body, in_specs=(comm._world_spec,), out_specs=comm._world_spec)
    )
    f(jnp.arange(float(comm.device_size)))
    assert len(dbg.log) == 2
    assert "allreduce" in dbg.log[0] and "bcast" in dbg.log[1]
    fp1 = dbg.fingerprint()
    assert dbg.verify_across_hosts() == fp1  # single host: trivially equal
    dbg.reset()
    assert dbg.fingerprint() != fp1 or not dbg.log


def test_bus_bandwidth_formula():
    # 8 devices, 1 GB buffer, 0.1 s → 2*(7/8) GB moved per chip / 0.1 s.
    got = profiling.allreduce_bus_bandwidth_gbs(1e9, 8, 0.1)
    assert abs(got - 17.5) < 1e-6


# ---------------------------------------------------------------------------
# corpus BLEU (reference seq2seq reported BLEU; in-repo implementation)
# ---------------------------------------------------------------------------


def test_bleu_perfect_match_is_one():
    from chainermn_tpu.utils.metrics import corpus_bleu

    seqs = [[1, 2, 3, 4, 5], [6, 7, 8, 9]]
    assert abs(corpus_bleu(seqs, seqs, smooth=False) - 1.0) < 1e-9


def test_bleu_disjoint_is_zero():
    from chainermn_tpu.utils.metrics import corpus_bleu

    assert corpus_bleu([[1, 2, 3, 4]], [[5, 6, 7, 8]]) == 0.0


def test_bleu_known_value():
    """Hand-checked: hyp shares 3/4 unigrams, 2/3 bigrams, 1/2 trigrams,
    0+1/1+1 smoothed 4-grams with the reference; lengths equal (BP=1)."""
    from chainermn_tpu.utils.metrics import corpus_bleu

    ref = [[1, 2, 3, 4]]
    hyp = [[1, 2, 3, 9]]
    import math

    expect = math.exp(
        (math.log(3 / 4) + math.log((2 + 1) / (3 + 1))
         + math.log((1 + 1) / (2 + 1)) + math.log((0 + 1) / (1 + 1))) / 4
    )
    got = corpus_bleu(ref, hyp, smooth=True)
    assert abs(got - expect) < 1e-9


def test_bleu_brevity_penalty():
    from chainermn_tpu.utils.metrics import corpus_bleu

    ref = [[1, 2, 3, 4, 5, 6, 7, 8]]
    short = [[1, 2, 3, 4]]
    full = corpus_bleu(ref, ref, smooth=False)
    clipped = corpus_bleu(ref, short, smooth=True)
    assert clipped < full  # BP punishes the short hypothesis


def test_strip_special():
    from chainermn_tpu.utils.metrics import strip_special

    assert strip_special([5, 6, 2, 9, 9]) == [5, 6]      # cut at EOS
    assert strip_special([0, 5, 0, 6]) == [5, 6]         # drop PAD


def test_facade_exposes_every_lazy_attribute():
    """Regression: every name the lazy facade claims must resolve (a
    from-import inside __getattr__ once recursed forever)."""
    import chainermn_tpu as c

    for name in [
        "create_communicator", "CommunicatorBase", "build_mesh",
        "create_multi_node_optimizer", "MultiNodeOptimizer",
        "scatter_dataset", "create_empty_dataset",
        "create_multi_node_evaluator", "create_multi_node_checkpointer",
        "MultiNodeChainList", "functions",
        "create_multi_node_iterator", "create_synchronized_iterator",
        "create_prefetch_iterator", "global_except_hook",
    ]:
        assert getattr(c, name) is not None, name
    import pytest as _pytest

    with _pytest.raises(AttributeError):
        c.definitely_not_an_attribute


def test_collective_trace_records_object_plane(mesh):
    """Host/object-plane ops enter the order log; asymmetric p2p ops are
    logged for the diagnostic trail but excluded from the verified
    (cross-host-compared) sequence."""
    from chainermn_tpu.communicators import create_communicator

    comm = create_communicator("naive", mesh=mesh)
    dbg = debug.CollectiveTrace(comm)
    dbg.bcast_obj({"k": 1}, root=0)   # single host: returns obj, still logged
    dbg.gather_obj("x")
    dbg.allreduce_obj(2)
    dbg.barrier()
    assert len(dbg.log) >= 4
    assert "bcast_obj" in dbg.log[0] and "plane" in dbg.log[0]
    sym_before = len(dbg._sym)
    # p2p is rank-asymmetric by design: recorded, not verified.
    try:
        dbg.send_obj("p", dest=1)
    except Exception:
        pass  # single-process: send_obj itself rejects; recording happened first
    assert any("send_obj" in e for e in dbg.log)
    assert len(dbg._sym) == sym_before
    dbg.verify_across_hosts()  # single host: trivially consistent


def test_typed_array_path_excludes_ndarray_subclasses():
    """The raw-buffer wire path must only take PLAIN ndarrays: subclasses
    (np.matrix, MaskedArray) carry state a raw buffer drops, so they must
    round-trip via pickle (ADVICE r3 #1)."""
    import numpy as np

    from chainermn_tpu.communicators.kvtransport import _is_typed_array

    assert _is_typed_array(np.zeros((2, 2)))
    assert _is_typed_array(np.zeros((), np.float32))  # 0-d plain
    assert not _is_typed_array(np.matrix([[1.0]]))
    assert not _is_typed_array(np.ma.masked_array([1, 2], mask=[0, 1]))
    assert not _is_typed_array(np.array([object()]))  # object dtype
    assert not _is_typed_array([1, 2, 3])


@pytest.mark.slow
def test_wheel_builds_and_loads_packaged_native_lib(tmp_path):
    """VERDICT r4 item 7: ``pip wheel .`` must compile csrc/hostbuf.cpp
    into the package (setup.py build hook) so an INSTALLED tree — no
    csrc/, no toolchain assumption — loads the native path, not the
    silent Python fallback.  Round-trip: build the wheel, unpack it far
    from the repo, and ask utils.native which source it loaded."""
    import subprocess
    import sys
    import zipfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    wheel_dir = tmp_path / "wheels"
    proc = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", ".", "--no-deps",
         "--no-build-isolation", "-w", str(wheel_dir)],
        cwd=repo, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    wheels = list(wheel_dir.glob("chainermn_tpu-*.whl"))
    assert len(wheels) == 1, list(wheel_dir.iterdir())

    unpacked = tmp_path / "site"
    with zipfile.ZipFile(wheels[0]) as zf:
        names = zf.namelist()
        assert "chainermn_tpu/_native/libhostbuf.so" in names, names
        zf.extractall(unpacked)

    check = subprocess.run(
        [sys.executable, "-c",
         "from chainermn_tpu.utils import native; "
         "print('IMPL=' + str(native.native_impl())); "
         "print('CRC=%08x' % native.crc32c(b'hello world'))"],
        cwd=str(tmp_path),  # away from the repo: csrc/ not reachable
        env={**os.environ, "PYTHONPATH": str(unpacked)},
        capture_output=True, text=True, timeout=120,
    )
    assert check.returncode == 0, check.stderr[-2000:]
    assert "IMPL=packaged" in check.stdout, check.stdout
    assert "CRC=c99465aa" in check.stdout, check.stdout


def test_native_impl_reports_source_checkout():
    """In this source tree the chain loads the on-demand csrc build (or
    the packaged lib if one was installed); never silently None while the
    library is actually available."""
    from chainermn_tpu.utils import native

    impl = native.native_impl()
    if native.get_lib() is not None:
        assert impl in ("packaged", "csrc")
    else:  # toolchain-less host: fallbacks active, impl honest about it
        assert impl is None
