#!/usr/bin/env python
"""Channel-parallel convolution — the reference's proto-tensor-parallelism.

Reference: REF:examples/parallel_convolution/ — each rank computes a
1/size shard of every conv layer's output channels and the ranks
``allgather`` activations between layers (differentiable allgather from
REF:chainermn/functions/collective_communication.py).

TPU-native: the same algorithm inside one ``shard_map`` — each device owns
``C/n`` output channels of each conv, activations are re-assembled with
``chainermn_tpu.functions.allgather`` (backward = reduce-scatter, inserted
by AD), and the data-parallel gradient mean runs over the same mesh.  This
is the explicit-collective spelling of what GSPMD does from sharding
annotations (chainermn_tpu.parallel.sharding); both styles are supported on
purpose, as in the reference where this example existed alongside the
communicator-driven DP stack.
"""

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu import functions as F
from chainermn_tpu.datasets.toy import SyntheticImageDataset, batch_iterator


class ShardedConvNet(nn.Module):
    """A CNN whose conv layers will be instantiated with C/n channels on
    each device; activations are allgathered between layers."""

    channels: int  # per-device channels (global // n)
    n_classes: int = 10

    @nn.compact
    def __call__(self, x, comm=None):
        for i, stride in enumerate([1, 2, 2]):
            x = nn.Conv(
                self.channels, (3, 3), strides=(stride, stride), name=f"conv_{i}"
            )(x)
            x = nn.relu(x)
            if comm is not None:
                # Reassemble the full channel dimension from all devices —
                # the reference's differentiable allgather, riding ICI.
                x = F.allgather(comm, x, axis=0, tiled=False)
                # (n, B, H, W, C/n) → (B, H, W, C)
                x = jnp.concatenate([x[j] for j in range(x.shape[0])], axis=-1)
        x = x.mean(axis=(1, 2))
        return nn.Dense(self.n_classes, name="head")(x)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="xla_ici")
    p.add_argument("--batchsize", type=int, default=128)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--channels", type=int, default=64, help="global channels")
    p.add_argument("--train-size", type=int, default=1024)
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    n = comm.device_size
    if args.channels % n:
        raise SystemExit(f"--channels must be divisible by {n} devices")
    model = ShardedConvNet(channels=args.channels // n)

    train = SyntheticImageDataset(n=args.train_size, shape=(16, 16, 3), seed=0)
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=1)

    x0 = jnp.zeros((2, 16, 16, 3))

    # Each device holds the SAME parameter structure (its channel shard);
    # different init per device comes from folding the device rank into
    # the rng inside the mapped init.  Init runs inside shard_map with the
    # communicator so the traced allgathers give every layer its true
    # (gathered) input channel count.
    def device_init():
        def body():
            seed = chainermn_tpu.communicators.mesh_utils.flat_rank(comm.axes)
            params = model.init(
                jax.random.fold_in(jax.random.PRNGKey(0), seed), x0, comm=comm
            )
            return jax.tree.map(lambda x: x[None], params)

        return jax.jit(
            comm.shard_map(body, in_specs=(), out_specs=comm._world_spec)
        )()

    stacked_params = device_init()  # leading axis = device (each a real shard)

    opt = optax.adam(1e-3)
    opt_state = opt.init(stacked_params)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x, comm=comm)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    def step(stacked_params, opt_state, batch):
        def body(params, batch):
            params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            # Channel-parallel ranks must see the SAME batch (the invariant
            # the reference's create_multi_node_iterator protects), so the
            # batch is replicated and each device's channel-shard params
            # get their own exact gradients — no averaging needed.
            return jax.tree.map(lambda g: g[None], grads), loss[None]

        batch_spec = P()  # replicated: model-parallel ranks share the batch
        grads, loss = jax.jit(
            comm.shard_map(
                body,
                in_specs=(comm._world_spec, batch_spec),
                out_specs=(comm._world_spec, comm._world_spec),
            )
        )(stacked_params, batch)
        updates, opt_state = opt.update(grads, opt_state, stacked_params)
        stacked_params = optax.apply_updates(stacked_params, updates)
        return stacked_params, opt_state, float(loss[0])

    for epoch in range(args.epochs):
        t0, last = time.perf_counter(), float("nan")
        for batch in batch_iterator(train, args.batchsize, seed=epoch):
            stacked_params, opt_state, last = step(
                stacked_params, opt_state, (batch[0], batch[1])
            )
        if comm.rank == 0:
            print(f"epoch {epoch}: loss {last:.4f} ({time.perf_counter()-t0:.1f}s)")
    return last


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
