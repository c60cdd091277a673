"""Worker for the 2-process jax.distributed harness test.

Run as: python _mp_worker.py <process_id> <num_processes> <coordinator_port>
Prints "MP_WORKER_OK <rank>" on success; any assertion kills the worker.
"""

import os
import sys


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    os.environ["JAX_PLATFORMS"] = "cpu"
    # Force the per-process virtual device count (default 4 → the
    # (inter=2, intra=4) deployment shape of SURVEY §2.6: a mesh whose
    # inter leg crosses a REAL process boundary while each process owns
    # several local devices), replacing any inherited
    # host_platform_device_count (pytest's conftest sets 8).
    ndev = int(os.environ.get("CHAINERMN_TPU_TEST_LOCAL_DEVICES", "4"))
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={ndev}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    assert jax.process_index() == pid
    assert jax.device_count() == ndev * nproc

    import numpy as np

    from chainermn_tpu.communicators import create_communicator
    from chainermn_tpu.datasets import scatter_dataset
    from chainermn_tpu.optimizers import create_multi_node_optimizer

    comm = create_communicator("naive")
    # Host-plane topology: one process per "node" (inter row).
    assert comm.rank == pid and comm.size == nproc
    assert comm.device_size == ndev * nproc
    assert comm.inter_size == nproc and comm.intra_size == ndev

    # Object plane across REAL process boundaries (the reference's pickled
    # MPI transport, here over the jax.distributed DCN analogue).
    got = comm.bcast_obj({"payload": [1, 2, 3], "from": "rank0"}, root=0)
    assert got["from"] == "rank0", got

    gathered = comm.gather_obj(("rank", pid))
    assert gathered == [("rank", i) for i in range(nproc)], gathered

    total = comm.allreduce_obj(pid + 1)
    assert total == sum(range(1, nproc + 1)), total

    comm.barrier()

    # scatter_dataset: per-process contiguous shards covering everything.
    shard = scatter_dataset(list(range(10)), comm, shuffle=True, seed=3,
                            force_equal_length=False)
    all_idx = comm.gather_obj(sorted(shard.indices.tolist()))
    merged = sorted(sum(all_idx, []))
    assert merged == list(range(10)), merged

    # broadcast_params: rank-divergent params replicated from process 0.
    import jax.numpy as jnp

    opt = create_multi_node_optimizer(__import__("optax").sgd(0.1), comm)
    params = {"w": jnp.full((3,), float(pid))}
    params = opt.broadcast_params(params)
    np.testing.assert_allclose(np.asarray(params["w"]), 0.0)

    # Full multi-host train step: per-host batches (different data per
    # process, as scatter_dataset produces) assembled into the global batch
    # via comm.global_batch, gradients psum-averaged across ALL processes'
    # devices inside the jitted step.
    params = {"w": jnp.zeros((3,))}

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    step = opt.make_train_step(loss_fn)
    state = opt.init(params)
    rng = np.random.RandomState(100 + pid)  # data differs per host
    local = {
        "x": rng.randn(4, 3).astype(np.float32),
        "y": rng.randn(4).astype(np.float32),
    }
    gbatch = comm.global_batch(local)
    assert gbatch["x"].shape == (4 * nproc, 3), gbatch["x"].shape
    params, state, loss = step(params, state, gbatch)
    assert np.isfinite(float(loss)), loss
    # The averaged gradient is identical everywhere → so are the params.
    w_everywhere = comm.gather_obj(np.asarray(params["w"]).tolist())
    for w in w_everywhere[1:]:
        np.testing.assert_allclose(w, w_everywhere[0], rtol=1e-6)

    # Traced binomial-tree gather/scatter whose point-to-root tree spans
    # the REAL process boundary (root on process 1; sources on process 0
    # must relay through the inter leg).  shard_map runs SPMD over the
    # global mesh, so each process verifies its own addressable shards.
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = comm.device_size
    root_rank = n_dev - 1  # last device: owned by the LAST process
    wsharding = NamedSharding(comm.mesh, comm._world_spec)
    src = np.arange(float(n_dev), dtype=np.float32)
    xs_in = jax.make_array_from_callback(
        (n_dev,), wsharding, lambda idx: src[idx]
    )

    def gather_body(xs):
        return comm.gather(xs[0] * 10.0, root=root_rank)[None]

    gout = jax.jit(comm.shard_map(
        gather_body, in_specs=(comm._world_spec,),
        out_specs=comm._world_spec,
    ))(xs_in)
    for shard in gout.addressable_shards:
        r = shard.index[0].start or 0
        if r == root_rank:
            np.testing.assert_allclose(
                np.asarray(shard.data).reshape(-1),
                10.0 * np.arange(n_dev),
            )
    # The root row is addressable exactly on the last process.
    has_root = any(
        (s.index[0].start or 0) == root_rank
        for s in gout.addressable_shards
    )
    assert has_root == (pid == nproc - 1), (pid, has_root)

    full = np.arange(float(2 * n_dev), dtype=np.float32)
    rep = jax.make_array_from_callback(
        (2 * n_dev,), NamedSharding(comm.mesh, P()), lambda idx: full[idx]
    )

    def scatter_body(xs):
        return comm.scatter(xs, root=root_rank)[None]

    sout = jax.jit(comm.shard_map(
        scatter_body, in_specs=(P(),), out_specs=comm._world_spec,
    ))(rep)
    for shard in sout.addressable_shards:
        r = shard.index[0].start or 0
        np.testing.assert_allclose(
            np.asarray(shard.data).reshape(-1), full[2 * r : 2 * r + 2],
        )

    # Multi-host checkpointer: leaves spanning non-addressable devices are
    # saved as per-process shard lists and re-assembled against the
    # template's sharding on load — untestable single-host, the whole
    # point of this harness.
    ckpt_dir = os.environ.get("CHAINERMN_TPU_TEST_CKPT_DIR")
    if ckpt_dir:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from chainermn_tpu.extensions import create_multi_node_checkpointer

        n_dev = comm.device_size
        sh = NamedSharding(comm.mesh, P(("inter", "intra")))
        full = np.arange(n_dev * 3, dtype=np.float32)
        garr = jax.make_array_from_callback(
            (n_dev * 3,), sh, lambda idx: full[idx]
        )
        assert not garr.is_fully_addressable
        cp = create_multi_node_checkpointer("mh", comm, path=ckpt_dir)
        cp.save({"g": garr, "s": jnp.float32(7.0)}, 11)
        loaded, it = cp.maybe_load(
            {"g": garr, "s": jnp.float32(0.0)}
        )
        assert it == 11, it
        assert loaded["g"].sharding == sh
        for s_l, s_o in zip(
            loaded["g"].addressable_shards, garr.addressable_shards
        ):
            np.testing.assert_array_equal(
                np.asarray(s_l.data), np.asarray(s_o.data)
            )
        assert float(loaded["s"]) == 7.0

    # Host-plane point-to-point (reference MpiCommunicatorBase.send/recv):
    # an object moves rank0 → rank1 over the coordination-service KV store
    # with NO world collective — ranks outside the pair do not participate.
    # The second payload spans multiple kvtransport chunks.
    from chainermn_tpu.communicators import kvtransport

    big = np.random.RandomState(7).bytes(2 * kvtransport.CHUNK_BYTES + 12345)
    if pid == 0:
        comm.send_obj({"msg": "hello", "n": 42}, dest=1)
        comm.send_obj(big, dest=1, tag=7)
        assert comm.recv_obj(source=1) == "ack"
    elif pid == 1:
        assert comm.recv_obj(source=0) == {"msg": "hello", "n": 42}
        assert comm.recv_obj(source=0, tag=7) == big
        comm.send_obj("ack", dest=0)

    # Typed ndarray fast path (reference MpiCommunicatorBase moves ndarrays
    # as first-class typed buffers): multi-chunk float32, a 0-d scalar, a
    # non-contiguous view (contiguified on send), and an empty array must
    # all round-trip with exact dtype/shape/values — and arrive as
    # ndarrays, not pickles of them.
    typed = np.random.RandomState(11).randn(
        3 * ((2 * kvtransport.CHUNK_BYTES) // 24) + 3
    ).astype(np.float64)
    if pid == 0:
        comm.send_obj(typed, dest=1, tag=9)
        comm.send_obj(np.array(2.5, np.float32), dest=1, tag=9)
        comm.send_obj(typed.reshape(-1, 3)[:, 1], dest=1, tag=9)  # strided
        comm.send_obj(np.empty((0, 4), np.int16), dest=1, tag=9)
    elif pid == 1:
        got = comm.recv_obj(source=0, tag=9)
        assert isinstance(got, np.ndarray) and got.dtype == np.float64
        np.testing.assert_array_equal(got, typed)
        got = comm.recv_obj(source=0, tag=9)
        assert isinstance(got, np.ndarray)
        assert got.shape == () and got.dtype == np.float32
        assert float(got) == 2.5
        got = comm.recv_obj(source=0, tag=9)
        np.testing.assert_array_equal(got, typed.reshape(-1, 3)[:, 1])
        got = comm.recv_obj(source=0, tag=9)
        assert got.shape == (0, 4) and got.dtype == np.int16

    # Same matrix over the KV chunk fallback plane (the path used where
    # direct TCP is unavailable): flip the plane on BOTH processes in SPMD
    # order, round-trip typed + pickled payloads, flip back.
    kvtransport.ObjectPlane._use_sockets = False
    try:
        if pid == 0:
            comm.send_obj(typed, dest=1, tag=13)
            comm.send_obj({"via": "kv"}, dest=1, tag=13)
        elif pid == 1:
            got = comm.recv_obj(source=0, tag=13)
            assert isinstance(got, np.ndarray)
            np.testing.assert_array_equal(got, typed)
            assert comm.recv_obj(source=0, tag=13) == {"via": "kv"}
    finally:
        kvtransport.ObjectPlane._use_sockets = True

    # scatter_obj is point-to-point under the KV plane: each rank receives
    # exactly its own element from root.
    items = [f"item{r}" for r in range(nproc)] if pid == 0 else None
    assert comm.scatter_obj(items, root=0) == f"item{pid}"

    # Communicator matrix across REAL process boundaries: every variant's
    # inter (DCN) collective leg, with fp32 and bf16 wire dtypes, must
    # reproduce the naive oracle's trajectory.
    import optax

    def run_steps(comm2, nsteps=2):
        opt2 = create_multi_node_optimizer(optax.sgd(0.1), comm2)
        p = {"w": jnp.zeros((3,))}
        st = opt2.init(p)
        stp = opt2.make_train_step(loss_fn, donate=False)
        gb = comm2.global_batch(local)
        for _ in range(nsteps):
            p, st, _ = stp(p, st, gb)
        return np.asarray(p["w"].addressable_shards[0].data).reshape(-1)

    ref_w = run_steps(comm)
    for name in ("xla_ici", "hierarchical", "two_dimensional"):
        for wire in (None, "bfloat16"):
            c2 = create_communicator(name, allreduce_grad_dtype=wire)
            w = run_steps(c2)
            tol = 1e-6 if wire is None else 6e-2
            np.testing.assert_allclose(
                w, ref_w, rtol=tol, atol=tol, err_msg=f"{name} wire={wire}"
            )

    # ZeRO-3 across a real process boundary: master params sharded over all
    # devices of both processes (w has 3 elements over 4 devices → the
    # padded-shard path), trajectory must match the replicated optimizer.
    zcomm = create_communicator("xla_ici")
    zopt = create_multi_node_optimizer(optax.sgd(0.1), zcomm, zero_stage=3)
    p0 = {"w": jnp.zeros((3,))}
    zstate = zopt.init(p0)
    flat = zopt.shard_params(p0)
    zstep = zopt.make_train_step(loss_fn, donate=False)
    zgb = zcomm.global_batch(local)
    for _ in range(2):
        flat, zstate, zloss = zstep(flat, zstate, zgb)
    zw = np.asarray(
        zopt.materialize(flat)["w"].addressable_shards[0].data
    ).reshape(-1)
    np.testing.assert_allclose(zw, ref_w, rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(zloss))

    from jax import lax

    # MPI_Comm_split(color, key) across REAL process boundaries
    # (REF:chainermn/communicators/mpi_communicator_base.py split).
    # Disjoint colors: every process its own singleton subgroup whose
    # mesh holds ONLY its local devices.
    solo = comm.split(pid)
    assert solo.size == 1 and solo.rank == 0
    assert solo.device_size == ndev
    assert all(
        d.process_index == pid for d in solo.mesh.devices.flat
    )
    # Same color, reversed keys: subgroup rank order flips.
    rev = comm.split(0, key=nproc - pid)
    assert rev.size == nproc
    assert rev.rank == nproc - 1 - pid, (rev.rank, pid)
    # Subgroup object plane: root is the subgroup's rank 0 = global
    # LAST process; payload visible to all members.
    got = rev.bcast_obj(("from", pid) if rev.rank == 0 else None, root=0)
    assert got == ("from", nproc - 1), got
    # Subgroup allgather is ordered by subgroup rank (key order).
    ag = rev.allgather_obj(pid)
    assert ag == list(range(nproc))[::-1], ag
    # Point-to-root gather_obj: list at root only, None elsewhere.
    g = rev.gather_obj(f"p{pid}", root=0)
    if rev.rank == 0:
        assert g == [f"p{r}" for r in reversed(range(nproc))], g
    else:
        assert g is None
    rev.barrier()
    # Subgroup DEVICE plane: the sub-mesh's inter rows follow key order
    # (last process first); a psum over it must still see every device.
    tot = jax.jit(rev.shard_map(
        lambda x: lax.psum(x, rev.axes),
        in_specs=(rev._world_spec,), out_specs=jax.sharding.PartitionSpec(),
    ))(jax.make_array_from_callback(
        (rev.device_size,),
        NamedSharding(rev.mesh, rev._world_spec),
        lambda idx: np.arange(float(rev.device_size), dtype=np.float32)[idx],
    ))
    np.testing.assert_allclose(
        float(tot.addressable_shards[0].data.reshape(-1)[0]),
        sum(range(rev.device_size)),
    )
    # MPI_UNDEFINED on one process only: plane ordinals stay in lockstep,
    # so a later world communicator still lines up across processes.
    maybe = comm.split(0 if pid == 0 else None)
    if pid == 0:
        assert maybe.size == 1
    else:
        assert maybe is None
    after = create_communicator("naive")
    assert after.bcast_obj({"post": "split"}, root=0)["post"] == "split"

    # Reporter cross-host aggregation over the REAL multi-process object
    # plane: rank-dependent observations must merge to the same
    # observation-weighted totals on every rank.
    from chainermn_tpu.observability import Reporter

    rep = Reporter()
    rep.observe("loss", float(pid))       # one observation per rank
    rep.observe("loss", float(pid) + 1.0)
    rep.count("steps", pid + 1)
    rep.histogram_observe("lat", 2.0 ** pid)
    agg = rep.aggregate(after)
    n = after.size
    loss = agg["scalars"]["loss"]
    assert loss["count"] == 2 * n, loss
    # sum over ranks of (pid + pid+1) = 2*sum(pid) + n
    assert loss["sum"] == float(n * (n - 1) + n), loss
    assert loss["min"] == 0.0 and loss["max"] == float(n), loss
    assert agg["counters"]["steps"] == n * (n + 1) // 2, agg["counters"]
    # 2^pid lands in bucket pid (ceil(log2) with 2^0=1 -> bucket 0).
    assert sum(agg["histograms"]["lat"].values()) == n, agg["histograms"]

    print(f"MP_WORKER_OK {pid}", flush=True)


if __name__ == "__main__":
    main()
