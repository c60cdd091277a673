"""Supervised training worker for the elastic soak tests.

Launched by ``python -m chainermn_tpu.tools.elastic`` (never directly):
joins the supervisor's ``jax.distributed`` world via
``elastic.init_from_env``, then runs a small but REAL data-parallel
training loop — jitted per-rank forward/grad on the local device,
gradient combination over the cross-process host plane
(``allreduce_obj``), coordinated checkpointing through the multi-node
checkpointer — with heartbeats, chaos faults, preemption handling, and
plan-validated resharding on resume.

The host plane carries the gradients (the naive communicator's
reference wire profile) so the loop runs over REAL process boundaries
on the CPU backend, where cross-process *device* computations are
unavailable.  The math is world-size-decomposable: each step's global
batch is generated from the step index, each rank reduces its slice to
a SUM, and the host-plane allreduce totals the sums before the /B —
so an N-rank run and its respawned twin are bit-identical, and an
N→M rescale stays on the same loss curve up to summation order.

Markers the supervisor/tests scrape::

    resumed from iteration <it>
    elastic_reshard plan=dp ok=True ...
    step <g> loss <float>
    final gstep <g> params_digest <8 hex>
    ELASTIC_TRAIN_OK <rank>
"""

import argparse
import os
import sys


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ckpt", required=True)
    p.add_argument("--steps", type=int, default=16)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--dim", type=int, default=8)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from chainermn_tpu import elastic

    ctx = elastic.init_from_env()
    assert ctx is not None, "must run under the elastic supervisor"

    import jax
    import jax.numpy as jnp

    import chainermn_tpu
    from chainermn_tpu.extensions import create_multi_node_checkpointer
    from chainermn_tpu.utils.native import tree_digest

    comm = chainermn_tpu.create_communicator("naive")
    rank, world = comm.rank, comm.size
    assert args.batch % world == 0
    local = args.batch // world

    f32 = np.float32
    params = {"b": np.zeros((), f32), "w": np.zeros(args.dim, f32)}
    moments = {"b": np.zeros((), f32), "w": np.zeros(args.dim, f32)}
    rs = np.random.RandomState(7)
    w_true = rs.randn(args.dim).astype(f32)

    def sse(w, b, x, y):
        r = x @ w + b - y
        return jnp.sum(r * r)

    grad_fn = jax.jit(jax.value_and_grad(sse, argnums=(0, 1)))

    def global_batch(g):
        bs = np.random.RandomState(4242 + g)
        x = bs.randn(args.batch, args.dim).astype(f32)
        y = (x @ w_true + 0.1 * bs.randn(args.batch).astype(f32)).astype(f32)
        return x, y

    ckpt = create_multi_node_checkpointer(
        "soak", comm, path=args.ckpt, keep_last_n=4
    )
    ctx.attach_checkpointer(ckpt)
    state = {"params": params, "opt": moments, "gstep": 0}
    loaded, it = ckpt.maybe_load(state)
    gstep = 0
    if it is not None:
        params, moments = loaded["params"], loaded["opt"]
        gstep = it
        if rank == 0:
            print(f"resumed from iteration {it}", flush=True)
        # Plan-validated layout for the CURRENT mesh (the N→M proof).
        # Placement is committed only where the backend can hold a
        # multi-process array in a local computation (world == 1 here:
        # the CPU backend has no cross-process device plane).
        params, moments, rep = ctx.reshard(
            params, moments, comm, plan="dp", place=(world == 1)
        )
        if rank == 0:
            print(
                f"elastic_reshard plan=dp ok={rep.ok} "
                f"leaves={rep.n_leaves} world={world}",
                flush=True,
            )
        params = jax.tree.map(lambda a: np.asarray(a, f32), params)
        moments = jax.tree.map(lambda a: np.asarray(a, f32), moments)

    lr, mu = f32(args.lr), f32(0.9)
    for g in range(gstep, args.steps):
        ctx.beat(g)
        if ctx.check_preemption(comm):
            ckpt.save(
                {"params": params, "opt": moments, "gstep": g},
                g, block=True,
            )
            if rank == 0:
                print(f"preempted: checkpoint saved at iteration {g}",
                      flush=True)
            ctx.exit_preempted()
        x, y = global_batch(g)
        xs, ys = x[rank * local:(rank + 1) * local], \
            y[rank * local:(rank + 1) * local]
        sse_local, (gw, gb) = grad_fn(params["w"], params["b"], xs, ys)
        flat = np.concatenate(
            [np.asarray(gw, f32).ravel(),
             [np.asarray(gb, f32)], [np.asarray(sse_local, f32)]]
        ).astype(f32)
        if world > 1:
            flat = comm.allreduce_obj(flat)
        gw = flat[:args.dim] / f32(args.batch)
        gb = flat[args.dim] / f32(args.batch)
        loss = flat[args.dim + 1] / f32(args.batch)
        moments["w"] = mu * moments["w"] + gw
        moments["b"] = mu * moments["b"] + gb
        params["w"] = params["w"] - lr * moments["w"]
        params["b"] = params["b"] - lr * moments["b"]
        gstep = g + 1
        if rank == 0:
            print(f"step {g} loss {float(loss):.6f}", flush=True)
        ckpt.save(
            {"params": params, "opt": moments, "gstep": gstep},
            gstep, block=False,
        )
    ckpt.wait()
    if rank == 0:
        print(
            f"final gstep {gstep} params_digest {tree_digest(params):08x}",
            flush=True,
        )
    print(f"ELASTIC_TRAIN_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
