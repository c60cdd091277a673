"""Worker for the cross-process MODEL-parallel harness test (VERDICT r4
item 3): the reference's CI ran EVERY distributed feature under
``mpiexec -n 2`` (SURVEY §4); here the pipeline schedules, the
heterogeneous links chain, zigzag sequence parallelism, and the MoE
all-to-all each run their collective leg over the ``inter`` mesh axis —
the one that crosses a REAL jax.distributed process boundary — not just
a single-process virtual mesh.

Run as: python _mp_modelpar_worker.py <pid> <nproc> <port>
Prints "MP_MODELPAR_OK <rank>" on success.
"""

import os
import sys


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    os.environ["JAX_PLATFORMS"] = "cpu"
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

    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chainermn_tpu.communicators import create_communicator

    comm = create_communicator("naive")
    n_dev = comm.device_size
    assert comm.inter_size == nproc and comm.intra_size == ndev

    def put(spec, arr):
        """Host array -> global jax.Array under this mesh (each process
        materializes only its addressable shards)."""
        arr = np.asarray(arr, np.float32)
        return jax.make_array_from_callback(
            arr.shape, NamedSharding(comm.mesh, spec), lambda idx: arr[idx]
        )

    def first_local(garr):
        return np.asarray(garr.addressable_shards[0].data)

    D = 8

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    # ---- 1. SPMD 1F1B pipeline with stages across the process boundary
    # (one stage per inter row: process 0 holds stage 0, process 1 stage
    # 1, ...), explicit-vjp backward.  Oracle: sequential stages.
    from chainermn_tpu.parallel.pipeline import (
        pipeline_1f1b_loss_and_grads,
        pipeline_circular_1f1b_loss_and_grads,
    )

    rng = np.random.RandomState(0)
    stage_w = rng.randn(nproc, D, D).astype(np.float32) * 0.5
    xb = rng.randn(2 * nproc, D).astype(np.float32)
    tb = rng.randn(2 * nproc, D).astype(np.float32)

    def pp_body(stacked, x, t):
        mine = jnp.squeeze(stacked, 0)
        loss, g = pipeline_1f1b_loss_and_grads(
            stage_fn, lambda o, tt: jnp.mean((o - tt) ** 2),
            mine, x, t, "inter", nproc,
        )
        return loss, jnp.expand_dims(g, 0)

    loss, grads = jax.jit(comm.shard_map(
        pp_body, in_specs=(P("inter"), P(), P()),
        out_specs=(P(), P("inter")),
    ))(put(P("inter"), stage_w), put(P(), xb), put(P(), tb))

    def oracle_loss(ws):
        h = jnp.asarray(xb)
        for s in range(nproc):
            h = stage_fn(ws[s], h)
        return jnp.mean((h - jnp.asarray(tb)) ** 2)

    ref_l, ref_g = jax.value_and_grad(oracle_loss)(jnp.asarray(stage_w))
    np.testing.assert_allclose(
        float(first_local(loss).reshape(-1)[0]), float(ref_l), rtol=1e-5
    )
    np.testing.assert_allclose(
        first_local(grads)[0], np.asarray(ref_g)[pid], rtol=1e-4, atol=1e-5
    )

    # ---- 1b. Circular (Megatron-tight) schedule, v=2 chunks/process.
    v = 2
    chunk_w = rng.randn(nproc, v, D, D).astype(np.float32) * 0.5

    def circ_body(chunked, x, t):
        mine = jnp.squeeze(chunked, 0)
        loss, g = pipeline_circular_1f1b_loss_and_grads(
            stage_fn, lambda o, tt: jnp.mean((o - tt) ** 2),
            mine, x, t, "inter", nproc, v,
        )
        return loss, jnp.expand_dims(g, 0)

    closs, cg = jax.jit(comm.shard_map(
        circ_body, in_specs=(P("inter"), P(), P()),
        out_specs=(P(), P("inter")),
    ))(put(P("inter"), chunk_w), put(P(), xb), put(P(), tb))

    def oracle_circ(ws):
        # global stage s = l*n + d  ->  ws[d, l]
        h = jnp.asarray(xb)
        for s in range(nproc * v):
            h = stage_fn(ws[s % nproc, s // nproc], h)
        return jnp.mean((h - jnp.asarray(tb)) ** 2)

    cref_l, cref_g = jax.value_and_grad(oracle_circ)(jnp.asarray(chunk_w))
    np.testing.assert_allclose(
        float(first_local(closs).reshape(-1)[0]), float(cref_l), rtol=1e-5
    )
    np.testing.assert_allclose(
        first_local(cg)[0], np.asarray(cref_g)[pid], rtol=1e-4, atol=1e-5
    )

    # ---- 2. Heterogeneous links chain (MultiNodeChainList): encoder on
    # the FIRST device, decoder on the LAST — the activation send/recv
    # crosses the process boundary.
    from chainermn_tpu.links import MultiNodeChainList

    def enc_fn(p, b):
        return jnp.tanh(b["x"] @ p["w"])

    def dec_fn(p, h):
        return h @ p["w"]

    chain = MultiNodeChainList(comm)
    chain.add_link(enc_fn, rank=0, rank_in=None, rank_out=n_dev - 1)
    chain.add_link(dec_fn, rank=n_dev - 1, rank_in=0, rank_out=None)
    ch_params = [
        {"w": jnp.full((6, 10), 0.1)},
        {"w": jnp.full((10, 2), 0.1)},
    ]
    import optax

    ch_flat = chain.shard_params(ch_params)
    ch_opt = optax.sgd(0.1)
    ch_state = chain.init_sharded_opt_state(ch_opt, ch_flat)
    ch_step = chain.make_sharded_train_step(
        ch_opt, lambda out, b: jnp.mean((out - b["y"]) ** 2), donate=False
    )
    ch_batch = {"x": jnp.ones((4, 6)), "y": jnp.zeros((4, 2))}
    prev = None
    for _ in range(2):
        ch_flat, ch_state, ch_loss = ch_step(ch_flat, ch_state, ch_batch)
        l = float(first_local(ch_loss).reshape(-1)[0])
        assert np.isfinite(l)
        if prev is not None:
            assert l < prev, (l, prev)  # it actually trains
        prev = l

    # ---- 3. Zigzag sequence parallelism over the process boundary:
    # 2(n)-way zigzag ring on the inter axis, vs full attention.
    from chainermn_tpu.parallel.ring_attention import (
        inverse_zigzag_indices,
        zigzag_indices,
        zigzag_ring_attention,
    )

    B, S, H, Dh = 2, 8 * nproc, 2, 4

    def dense_causal_ref(q, k, v):
        logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(Dh)
        mask = np.tril(np.ones((q.shape[1],) * 2, bool))
        logits = np.where(mask[None, None], logits, -np.inf)
        w = np.exp(logits - logits.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        return np.einsum("bhqk,bkhd->bqhd", w, v)

    q = rng.randn(B, S, H, Dh).astype(np.float32)
    k = rng.randn(B, S, H, Dh).astype(np.float32)
    vv = rng.randn(B, S, H, Dh).astype(np.float32)
    idx = zigzag_indices(S, nproc)
    inv = inverse_zigzag_indices(S, nproc)

    def sp_body(q, k, v):
        return zigzag_ring_attention(q, k, v, "inter")

    out = jax.jit(comm.shard_map(
        sp_body, in_specs=(P(None, "inter"),) * 3,
        out_specs=P(None, "inter"),
    ))(put(P(None, "inter"), q[:, idx]), put(P(None, "inter"), k[:, idx]),
       put(P(None, "inter"), vv[:, idx]))

    ref = dense_causal_ref(q, k, vv)
    got = np.zeros_like(ref)
    # Reassemble only the shards THIS process holds; verify those rows.
    for shard in out.addressable_shards:
        sl = shard.index[1]
        got[:, sl] = np.asarray(shard.data)
        zz_rows = np.arange(S)[idx][sl]
        np.testing.assert_allclose(
            np.asarray(shard.data), ref[:, zz_rows], rtol=2e-4, atol=2e-4
        )
    del got, inv

    # ---- 3b. Ulysses SP over the process boundary: the head<->sequence
    # all-to-all crosses processes; GQA deals the reduced kv heads too.
    from chainermn_tpu.parallel.ulysses import ulysses_attention

    Hq, Hkv = 2 * nproc, nproc  # both divisible by the axis size
    uq = rng.randn(B, S, Hq, Dh).astype(np.float32)
    uk = rng.randn(B, S, Hkv, Dh).astype(np.float32)
    uv = rng.randn(B, S, Hkv, Dh).astype(np.float32)

    u_out = jax.jit(comm.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "inter", causal=True),
        in_specs=(P(None, "inter"),) * 3, out_specs=P(None, "inter"),
    ))(put(P(None, "inter"), uq), put(P(None, "inter"), uk),
       put(P(None, "inter"), uv))

    G = Hq // Hkv
    uref = dense_causal_ref(
        uq, np.repeat(uk, G, axis=2), np.repeat(uv, G, axis=2)
    )
    for shard in u_out.addressable_shards:
        sl = shard.index[1]
        np.testing.assert_allclose(
            np.asarray(shard.data), uref[:, sl], rtol=2e-4, atol=2e-4
        )

    # ---- 4. MoE with the token all-to-all over the process boundary:
    # one expert per inter row, shard-wise oracle per device row.
    from chainermn_tpu.parallel.moe import dense_moe_oracle, moe_layer

    E = nproc
    T_loc, Dm = 8, 8
    moe_x = rng.randn(E * T_loc, Dm).astype(np.float32)
    gate_w = (rng.randn(Dm, E) * 0.5).astype(np.float32)
    experts = {"w": (rng.randn(E, Dm, Dm) * 0.3).astype(np.float32)}

    def moe_fn(p, t):
        return jnp.tanh(t @ p["w"])

    def moe_body(x, gw, ex):
        mine = jax.tree.map(lambda p: jnp.squeeze(p, 0), ex)
        y, aux = moe_layer(
            x, gw, moe_fn, mine, "inter", capacity_factor=4.0,
            return_aux=True,
        )
        return y, jax.lax.pmean(aux, comm.axes)

    y, aux = jax.jit(comm.shard_map(
        moe_body, in_specs=(P("inter"), P(), {"w": P("inter")}),
        out_specs=(P("inter"), P()),
    ))(put(P("inter"), moe_x), put(P(), gate_w),
       {"w": put(P("inter"), experts["w"])})
    drop = float(first_local(aux["dropped_fraction"]).reshape(-1)[0])
    assert 0.0 <= drop <= 1.0, drop
    for shard in y.addressable_shards:
        r = (shard.index[0].start or 0) // T_loc
        ref_shard = dense_moe_oracle(
            jnp.asarray(moe_x[r * T_loc:(r + 1) * T_loc]),
            jnp.asarray(gate_w), moe_fn, experts, capacity_factor=4.0,
        )
        np.testing.assert_allclose(
            np.asarray(shard.data), np.asarray(ref_shard),
            rtol=2e-4, atol=2e-5,
        )

    # ---- 5. Interleaved (coupled) 1F1B across the boundary, v=2.
    from chainermn_tpu.parallel.pipeline import (
        pipeline_interleaved_1f1b_loss_and_grads,
    )

    def il_body(chunked, x, t):
        mine = jnp.squeeze(chunked, 0)
        loss, g = pipeline_interleaved_1f1b_loss_and_grads(
            stage_fn, lambda o, tt: jnp.mean((o - tt) ** 2),
            mine, x, t, "inter", nproc, v,
        )
        return loss, jnp.expand_dims(g, 0)

    il_loss, il_g = jax.jit(comm.shard_map(
        il_body, in_specs=(P("inter"), P(), P()),
        out_specs=(P(), P("inter")),
    ))(put(P("inter"), chunk_w), put(P(), xb), put(P(), tb))
    np.testing.assert_allclose(
        float(first_local(il_loss).reshape(-1)[0]), float(cref_l),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        first_local(il_g)[0], np.asarray(cref_g)[pid], rtol=1e-4,
        atol=1e-5,
    )

    print(f"MP_MODELPAR_OK {pid}", flush=True)


if __name__ == "__main__":
    main()
