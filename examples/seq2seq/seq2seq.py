#!/usr/bin/env python
"""Model-parallel seq2seq — encoder and decoder on different device ranks.

Reference: REF:examples/seq2seq/seq2seq.py — the ChainerMN model-parallel
showcase: encoder on rank 0, decoder on rank 1, wired with
``MultiNodeChainList`` ``send``/``recv`` (BASELINE config #3).

TPU-native: both stages live in ONE traced SPMD program; the encoder's
hidden state crosses ranks as a single ``lax.ppermute`` and gradients ride
its transpose back.  Trained here on the synthetic reversal task (target =
reversed source) so convergence is a real acceptance signal.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.utils.profiling import sync
from chainermn_tpu.datasets.toy import SyntheticSeqDataset, batch_iterator
from chainermn_tpu.links import MultiNodeChainList
from chainermn_tpu.models.seq2seq import Decoder, Encoder, shift_right


def main(argv=None):
    p = argparse.ArgumentParser(description="chainermn_tpu seq2seq example")
    p.add_argument("--communicator", default="xla_ici")
    p.add_argument("--batchsize", type=int, default=64)
    p.add_argument("--epochs", type=int, default=8)
    p.add_argument("--unit", type=int, default=128)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--seq-len", type=int, default=12)
    p.add_argument("--train-size", type=int, default=2048)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--sharded-params", action="store_true",
                   help="stage-sharded parameter storage: each device "
                        "holds only its own component (encoder XOR "
                        "decoder), not the whole model")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    n = comm.device_size
    enc_rank, dec_rank = 0, n - 1
    if comm.rank == 0:
        print(f"communicator: {comm!r}; encoder on rank {enc_rank}, "
              f"decoder on rank {dec_rank}")

    train = SyntheticSeqDataset(
        n=args.train_size, src_len=args.seq_len, tgt_len=args.seq_len,
        vocab=args.vocab,
    )
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=0)

    encoder = Encoder(args.vocab, args.unit)
    decoder = Decoder(args.vocab, args.unit)
    src0 = jnp.zeros((2, args.seq_len), jnp.int32)
    tgt0 = jnp.zeros((2, args.seq_len), jnp.int32)
    enc_params = encoder.init(jax.random.PRNGKey(0), src0)
    dec_params = decoder.init(
        jax.random.PRNGKey(1), encoder.apply(enc_params, src0), tgt0
    )

    # The split model: encoder owned by rank 0, decoder by the last rank,
    # hidden state transferred between them.
    chain = MultiNodeChainList(comm)
    chain.add_link(
        lambda p, batch: encoder.apply(p, batch[0]),
        rank=enc_rank, rank_in=None, rank_out=dec_rank,
    )
    chain.add_link(
        lambda p, inp: decoder.apply(p, inp[0], shift_right(inp[1][1])),
        rank=dec_rank, rank_in=enc_rank, rank_out=None, needs_input=True,
    )

    def ce_loss(logits, batch):
        tgt = batch[1]
        mask = (tgt != 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
        return (ce * mask).sum() / mask.sum()

    def loss_fn(params_list, batch):
        return ce_loss(chain.apply(params_list, batch), batch)

    opt = optax.adam(args.lr)
    params = (enc_params, dec_params)

    if args.sharded_params:
        # Stage-sharded tier: each device persistently holds only its own
        # component's parameters (encoder XOR decoder), as one flat row of
        # the sharded buffer — the per-process memory profile the
        # reference's one-rank-one-submodel processes had.
        flat = chain.shard_params(params)
        opt_state = chain.init_sharded_opt_state(opt, flat)
        train_step = chain.make_sharded_train_step(opt, ce_loss)
        params = flat
    else:
        opt_state = opt.init(params)

        def train_step_fn(params, opt_state, batch):
            def mapped(params, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                # Model-parallel ranks hold the full (replicated) params;
                # grads are summed so every rank applies identical updates.
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, comm.axes), grads
                )
                return loss, grads

            loss, grads = comm.shard_map(
                mapped, in_specs=(P(), P()), out_specs=(P(), P())
            )(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        train_step = jax.jit(train_step_fn)

    for epoch in range(args.epochs):
        t0, last = time.perf_counter(), float("nan")
        for batch in batch_iterator(train, args.batchsize, seed=epoch):
            params, opt_state, last = train_step(params, opt_state, batch)
        sync(last)  # host readback: honest timing on all backends
        if comm.rank == 0:
            print(
                f"epoch {epoch}: loss {float(last):.4f} "
                f"({time.perf_counter() - t0:.1f}s)"
            )
    if args.sharded_params:
        params = chain.materialize_params(params)

    # Evaluation on a fresh batch: teacher-forced token accuracy AND
    # greedy-decode BLEU (the reference's seq2seq reported BLEU).
    test = SyntheticSeqDataset(n=256, src_len=args.seq_len, vocab=args.vocab, seed=9)
    src = jnp.asarray(test.src)
    tgt = jnp.asarray(test.tgt)
    fwd = chain.make_forward(batch_spec=P())
    logits = fwd(params, (src, tgt))
    acc = float((logits.argmax(-1) == tgt).mean())

    # Autoregressive greedy decode (params are replicated, so this runs
    # identically on every rank; static unroll over the short target).
    from chainermn_tpu.models.seq2seq import BOS
    from chainermn_tpu.utils.metrics import corpus_bleu, strip_special

    @jax.jit
    def greedy(params, src):
        enc_p, dec_p = params
        h = encoder.apply(enc_p, src)
        toks = jnp.full((src.shape[0], 1), BOS, jnp.int32)
        for _ in range(args.seq_len):
            step_logits = decoder.apply(dec_p, h, toks)
            nxt = step_logits[:, -1].argmax(-1).astype(jnp.int32)
            toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
        return toks[:, 1:]

    hyp = np.asarray(greedy(params, src))
    refs = [strip_special(r) for r in np.asarray(tgt)]
    hyps = [strip_special(h) for h in hyp]
    bleu = corpus_bleu(refs, hyps)
    if comm.rank == 0:
        print(f"token accuracy (teacher-forced): {acc:.4f}  "
              f"BLEU (greedy): {bleu * 100:.2f}")
    return acc


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
