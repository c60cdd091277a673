#!/usr/bin/env python
"""ViT-B/16-style training with mixed data+pipeline parallelism and
double-buffered allreduce — BASELINE config #5.

Layout: the mesh's ``inter`` axis is DATA parallel, the ``intra`` axis is
the PIPELINE.  Patchify runs replicated (cheap), the transformer blocks run
through ``parallel.pipeline.spmd_pipeline`` with each pipeline rank holding
only ITS stages' parameters (genuinely sharded — the memory win the
reference's MultiNodeChainList never had), and the classifier head runs on
the pipeline output.  Gradients are combined per-role:

* stage params   → mean over the DATA axis only (each pipeline rank owns
  different weights — averaging across ``intra`` would mix stages);
* patchify/head  → summed over the pipeline axis (only one pipeline rank
  produces nonzero grads) then averaged over data — exercised via a
  ``comm.split(('inter',))`` sub-communicator, the reference's
  sub-communicator pattern for hybrid parallelism (SURVEY §2.5).

Double buffering applies the PREVIOUS step's averaged gradients
(one-step-stale, first step reduce-only) — the semantics of the
reference's _DoubleBufferingOptimizer, letting XLA overlap the DP
allreduce across the step boundary.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.utils.profiling import sync
from chainermn_tpu.datasets.toy import SyntheticImageDataset, batch_iterator
from chainermn_tpu.models.transformer import EncoderLayer
from chainermn_tpu.parallel.pipeline import (
    pipeline_1f1b_loss_and_grads,
    spmd_pipeline,
)

import flax.linen as nn


class Patchify(nn.Module):
    d_model: int
    patch: int

    @nn.compact
    def __call__(self, x):
        B = x.shape[0]
        x = nn.Conv(
            self.d_model, (self.patch, self.patch),
            strides=(self.patch, self.patch), name="proj",
        )(x)
        x = x.reshape(B, -1, self.d_model)
        pos = self.param(
            "pos", nn.initializers.normal(0.02), (1, x.shape[1], self.d_model)
        )
        return x + pos


class Blocks(nn.Module):
    """The per-pipeline-rank stage: `layers_per_stage` encoder blocks."""

    d_model: int
    n_heads: int
    d_ff: int
    layers_per_stage: int

    @nn.compact
    def __call__(self, x):
        for i in range(self.layers_per_stage):
            x = EncoderLayer(
                self.d_model, self.n_heads, self.d_ff, jnp.float32,
                name=f"block_{i}",
            )(x)
        return x


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batchsize", type=int, default=64, help="global batch")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--patch", type=int, default=8)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=256)
    p.add_argument("--layers-per-stage", type=int, default=1)
    p.add_argument("--n-classes", type=int, default=10)
    p.add_argument("--microbatches", type=int, default=2)
    p.add_argument("--train-size", type=int, default=1024)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--no-double-buffering", action="store_true")
    p.add_argument("--schedule", choices=["gpipe", "1f1b"], default="gpipe",
                   help="pipeline schedule: GPipe (AD backward) or the "
                   "memory-bounded 1F1B (explicit backward)")
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="model chunks PER pipeline device (interleaved "
                   "1F1B; total depth = pp * v, bubble cut ~(v+1)/2v of "
                   "the non-interleaved schedule's; requires --schedule "
                   "1f1b and microbatches divisible by the pipeline size)")
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel ways (inter axis); rest is pipeline")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator("xla_ici", inter_size=args.dp)
    dp = comm.inter_size
    pp = comm.intra_size
    dp_comm = comm.split(("inter",))  # data-parallel sub-communicator
    # Arbitrary-subgroup split (MPI_Comm_split(color, key) shape): one
    # data-parallel subgroup PER PIPELINE STAGE — the devices at intra
    # position s across all inter rows.  Stage s's grads could be
    # averaged on stage_dp[s] alone; here they sanity-check the topology.
    stage_dp = comm.split_devices([r % pp for r in range(comm.device_size)])
    # A color whose devices all live on other processes maps to None
    # (MPI_COMM_NULL) — skip those rather than AttributeError on None.
    assert all(
        sub is None or sub.device_size == dp for sub in stage_dp.values()
    )
    if comm.rank == 0:
        print(f"mesh: data={dp} x pipeline={pp} "
              f"(+{len(stage_dp)} per-stage DP subgroups); "
              f"double_buffering={not args.no_double_buffering}")

    shape = (args.image_size, args.image_size, 3)
    train = SyntheticImageDataset(
        n=args.train_size, shape=shape, n_classes=args.n_classes, seed=0
    )
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=1)

    patchify = Patchify(args.d_model, args.patch)
    stage = Blocks(args.d_model, args.n_heads, args.d_ff, args.layers_per_stage)
    head = nn.Dense(args.n_classes)

    v = args.virtual_stages
    if v < 1:
        raise SystemExit("--virtual-stages must be >= 1")
    if v > 1 and args.schedule != "1f1b":
        raise SystemExit("--virtual-stages > 1 requires --schedule 1f1b")

    x0 = jnp.zeros((2, *shape))
    embed_params = patchify.init(jax.random.PRNGKey(0), x0)
    tok0 = patchify.apply(embed_params, x0)
    if v == 1:
        # One stage per pipeline rank, stacked on a leading axis sharded
        # over 'intra' — each device holds only its own stage's weights.
        stage_params = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[stage.init(jax.random.PRNGKey(10 + i), tok0) for i in range(pp)],
        )
    else:
        # Interleaved assignment: device d's chunk l is GLOBAL stage
        # l*pp + d; stacked (pp, v, ...), still sharded over 'intra'.
        inits = [
            stage.init(jax.random.PRNGKey(10 + i), tok0)
            for i in range(pp * v)
        ]
        stage_params = jax.tree.map(
            lambda *xs: jnp.stack(xs).reshape(v, pp, *xs[0].shape)
            .swapaxes(0, 1),
            *inits,
        )
    head_params = head.init(jax.random.PRNGKey(1), tok0.mean(axis=1))

    opt = optax.adamw(args.lr, weight_decay=0.01)
    params = {"embed": embed_params, "stages": stage_params, "head": head_params}
    opt_state = opt.init(params)
    double_buffering = not args.no_double_buffering

    def head_loss(hp, out, tgt):
        # Shared by both schedules — edit the head/loss here only.
        logits = head.apply(hp, out.mean(axis=1))
        return optax.softmax_cross_entropy_with_integer_labels(logits, tgt).mean()

    def forward_loss(params, batch):
        x, y = batch
        tokens = patchify.apply(params["embed"], x)
        mine = jax.tree.map(lambda p: jnp.squeeze(p, 0), params["stages"])
        out = spmd_pipeline(
            stage.apply, mine, tokens, "intra", args.microbatches
        )
        # Pipeline output is valid on the last pipeline rank; broadcast it
        # along 'intra' so the (replicated) head computes the loss everywhere.
        out = jax.lax.psum(out, "intra")
        return head_loss(params["head"], out, y)

    def reduce_grads(grads):
        # Stage grads: DP-mean only. Embed/head grads: collect over the
        # pipeline axis (one owner each) then DP-mean.
        stages = dp_comm.allreduce_grad(grads["stages"])
        embed = jax.tree.map(lambda g: jax.lax.psum(g, "intra"), grads["embed"])
        head_g = jax.tree.map(lambda g: jax.lax.psum(g, "intra"), grads["head"])
        embed = dp_comm.allreduce_grad(embed)
        head_g = dp_comm.allreduce_grad(head_g)
        return {"embed": embed, "stages": stages, "head": head_g}

    def forward_loss_1f1b(params, batch):
        # 1F1B: the head rides inside the schedule (loss_params), the
        # patchify embedding hangs off it via jax.vjp on the input
        # cotangents — each microbatch's backward starts the tick its
        # forward ends, bounding live activations to O(pipeline depth).
        x, y = batch
        tokens, embed_vjp = jax.vjp(
            lambda ep: patchify.apply(ep, x), params["embed"]
        )
        mine = jax.tree.map(lambda p: jnp.squeeze(p, 0), params["stages"])
        if v > 1:
            from chainermn_tpu.parallel.pipeline import (
                pipeline_interleaved_1f1b_loss_and_grads,
            )

            loss, sg, hg, gtok = pipeline_interleaved_1f1b_loss_and_grads(
                stage.apply, head_loss, mine, tokens, y, "intra",
                args.microbatches, v, loss_params=params["head"],
                with_input_grads=True,
            )
        else:
            loss, sg, hg, gtok = pipeline_1f1b_loss_and_grads(
                stage.apply, head_loss, mine, tokens, y, "intra",
                args.microbatches, loss_params=params["head"],
                with_input_grads=True,
            )
        gtok = jax.lax.psum(gtok, "intra")   # stage-0 owner
        hg = jax.lax.psum(hg, "intra")       # last-stage owner
        (eg,) = embed_vjp(gtok)
        sg = jax.tree.map(lambda a: jnp.expand_dims(a, 0), sg)
        return loss, {"embed": eg, "stages": sg, "head": hg}

    def step(params, opt_state, prev_grads, step_idx, batch):
        def body(params, prev_grads, batch):
            if args.schedule == "1f1b":
                loss, grads = forward_loss_1f1b(params, batch)
                loss = jax.lax.pmean(loss, "inter")
                # embed/head grads are already psum-collected over the
                # pipeline axis inside forward_loss_1f1b; DP-mean the rest.
                grads = {
                    "embed": dp_comm.allreduce_grad(grads["embed"]),
                    "stages": dp_comm.allreduce_grad(grads["stages"]),
                    "head": dp_comm.allreduce_grad(grads["head"]),
                }
                return loss, grads
            loss, grads = jax.value_and_grad(forward_loss)(params, batch)
            loss = jax.lax.pmean(loss, comm.axes)
            grads = reduce_grads(grads)
            return loss, grads

        spec = {"embed": P(), "stages": P("intra"), "head": P()}
        loss, grads = comm.shard_map(
            body,
            in_specs=(spec, spec, P("inter")),
            out_specs=(P(), spec),
        )(params, prev_grads, batch)

        apply_grads = grads
        if double_buffering:
            apply_grads, keep = prev_grads, grads
        else:
            keep = grads
        updates, opt_state = opt.update(apply_grads, opt_state, params)
        # Double buffering: step 0 has no previous grads — reduce only.
        scale = jnp.where(step_idx == 0, 0.0, 1.0) if double_buffering else 1.0
        updates = jax.tree.map(lambda u: u * scale, updates)
        params = optax.apply_updates(params, updates)
        return params, opt_state, keep, loss

    step = jax.jit(step, static_argnames=())

    prev_grads = jax.tree.map(jnp.zeros_like, params)
    step_idx = 0
    for epoch in range(args.epochs):
        t0, n_seen, last = time.perf_counter(), 0, float("nan")
        for batch in batch_iterator(train, args.batchsize, seed=epoch):
            params, opt_state, prev_grads, last = step(
                params, opt_state, prev_grads, step_idx, batch
            )
            step_idx += 1
            n_seen += batch[0].shape[0]
        sync(last)  # host readback: honest timing on all backends
        if comm.rank == 0:
            print(
                f"epoch {epoch}: loss {float(last):.4f} "
                f"({n_seen/(time.perf_counter()-t0):,.0f} img/s)"
            )
    return float(last)


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
