#!/usr/bin/env python
"""Transformer enc-dec (WMT-shape) with hierarchical 2D allreduce —
BASELINE config #4.

The configuration the reference ran on multi-node GPU pods with its
``two_dimensional`` communicator (intra-node reduce-scatter → inter-node
allreduce → intra-node all-gather, REF:chainermn/communicators/
two_dimensional_communicator.py): here the same collective pattern rides
the ICI (``intra``) and DCN (``inter``) mesh axes, traced into the jitted
step by the multi-node optimizer.

Data: zero-egress → synthetic reversal "translation" corpus of WMT-like
shape; point --data-npz at {src,tgt} int32 arrays for real text.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import optax

import chainermn_tpu
from chainermn_tpu.utils.profiling import sync
from chainermn_tpu.datasets.toy import SyntheticSeqDataset, batch_iterator
from chainermn_tpu.models.transformer import Transformer


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="two_dimensional")
    p.add_argument("--batchsize", type=int, default=128, help="global batch")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--train-size", type=int, default=4096)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--comm-dtype", default="bfloat16",
                   help="allreduce_grad dtype (the fp16-comm analogue)")
    p.add_argument("--steps", type=int, default=None)
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(
        args.communicator,
        allreduce_grad_dtype=args.comm_dtype if args.comm_dtype != "none" else None,
    )
    if comm.rank == 0:
        print(f"communicator: {comm!r} comm-dtype={args.comm_dtype}")

    train = SyntheticSeqDataset(
        n=args.train_size, src_len=args.seq_len, tgt_len=args.seq_len,
        vocab=args.vocab,
    )
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=0)

    model = Transformer(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        d_ff=args.d_ff, n_enc_layers=args.layers, n_dec_layers=args.layers,
        max_len=args.seq_len,
    )
    src0 = jnp.zeros((2, args.seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), src0, src0)

    def loss_fn(params, batch):
        src, tgt = batch
        tgt_in = jnp.concatenate(
            [jnp.ones((tgt.shape[0], 1), tgt.dtype), tgt[:, :-1]], axis=1
        )
        logits = model.apply(params, src, tgt_in)
        mask = (tgt != 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
        return (ce * mask).sum() / mask.sum()

    sched = optax.warmup_cosine_decay_schedule(
        0.0, args.lr, 50, max(200, args.epochs * len(train) // args.batchsize)
    )
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adamw(sched, weight_decay=0.01), comm
    )
    state = opt.init(params)
    step = opt.make_train_step(loss_fn)

    n_steps = 0
    for epoch in range(args.epochs):
        t0, n_tok, last = time.perf_counter(), 0, float("nan")
        for batch in batch_iterator(train, args.batchsize, seed=epoch):
            params, state, last = step(params, state, batch)
            n_tok += batch[0].size + batch[1].size
            n_steps += 1
            if args.steps and n_steps >= args.steps:
                break
        sync(last)  # host readback: honest timing on all backends
        dt = time.perf_counter() - t0
        if comm.rank == 0:
            print(
                f"epoch {epoch}: loss {float(last):.4f} "
                f"({n_tok/dt:,.0f} tok/s over {comm.device_size} devices)"
            )
    return float(last)


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
