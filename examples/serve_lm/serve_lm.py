#!/usr/bin/env python
"""Minimal serving demo: train a toy LM briefly, then serve it.

Two halves, deliberately end-to-end:

1. **Train** a small :class:`TransformerLM` on the synthetic successor
   task (next token = current + 1 mod vocab) for a handful of steps —
   enough that greedy decoding visibly continues the pattern, so the
   served output is checkable by eye.
2. **Serve** it through the full stack: requests with different prompt
   lengths enter the :class:`ServeFrontend` queue, the continuous-
   batching scheduler interleaves their prefill and decode iterations,
   tokens stream back through callbacks as they are sampled, and the
   Reporter's gauges/counters show queue depth and KV-cache occupancy.

Runs on anything (CPU included): the decode data plane is plain jnp.

Usage::

    python examples/serve_lm/serve_lm.py                 # defaults
    python examples/serve_lm/serve_lm.py --requests 8 --new-tokens 24
"""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.observability import Reporter
from chainermn_tpu.serving import (
    ContinuousBatchingScheduler,
    EngineConfig,
    InferenceEngine,
    SamplingParams,
    ServeFrontend,
)


def train_successor_lm(model, vocab, steps, batch, seq_len, lr=1e-2):
    rng = np.random.RandomState(0)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32)
    )
    opt = optax.adam(lr)
    state = opt.init(params)

    @jax.jit
    def step(params, state, tok, tgt):
        def loss_fn(p):
            logits = model.apply(p, tok)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, state = opt.update(grads, state)
        return optax.apply_updates(params, updates), state, loss

    loss = float("nan")
    for _ in range(steps):
        start = rng.randint(0, vocab, size=(batch, 1))
        tok = (start + np.arange(seq_len)[None, :]) % vocab
        tok = jnp.asarray(tok, jnp.int32)
        tgt = (tok + 1) % vocab
        params, state, loss = step(params, state, tok, tgt)
    return params, float(loss)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--train-steps", type=int, default=200)
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--new-tokens", type=int, default=12)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--block-size", type=int, default=8,
                   help="KV page size in tokens")
    p.add_argument("--n-blocks", type=int, default=128,
                   help="KV pages in the pool (shrink to watch "
                        "preemption-by-eviction kick in)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; >0 samples (seeded per request)")
    args = p.parse_args(argv)

    max_len = 128
    model = TransformerLM(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        d_ff=args.d_ff, n_layers=args.layers, max_len=max_len,
    )
    params, loss = train_successor_lm(
        model, args.vocab, args.train_steps, batch=16, seq_len=32
    )
    print(f"trained {args.train_steps} steps, final loss {loss:.3f}")

    reporter = Reporter()
    engine = InferenceEngine(model, params, EngineConfig(
        block_size=args.block_size, n_blocks=args.n_blocks,
        max_len=max_len, max_batch=args.max_batch,
    ))
    sched = ContinuousBatchingScheduler(engine, reporter=reporter)
    frontend = ServeFrontend(sched, max_queue=args.requests + 1)

    rng = np.random.RandomState(1)
    streams = {}

    def on_token(rid, tok):
        streams.setdefault(rid, []).append(tok)

    handles = []
    for i in range(args.requests):
        start = int(rng.randint(0, args.vocab))
        plen = int(rng.randint(3, 9))
        prompt = [(start + j) % args.vocab for j in range(plen)]
        h = frontend.submit(
            prompt, args.new_tokens,
            sampling=SamplingParams(temperature=args.temperature,
                                    seed=i),
            on_token=on_token,
        )
        handles.append((prompt, h))
    frontend.run_until_idle()

    for prompt, h in handles:
        want = [(prompt[-1] + 1 + j) % args.vocab
                for j in range(len(h.tokens))]
        tag = "" if args.temperature else (
            " <- successor" if h.tokens == want else " (off-pattern)"
        )
        print(f"req {h.request_id}: prompt {prompt} -> {h.tokens}{tag}")
        assert streams[h.request_id] == h.tokens  # streaming == final

    summary = reporter.summary()
    print("engine:", json.dumps({
        k: v for k, v in engine.stats().items()
        if k in ("prefill_compiles", "decode_compiles",
                 "tokens_prefilled", "tokens_decoded")
    }))
    print("gauges:", json.dumps(
        {k: d["value"] for k, d in summary["gauges"].items()}
    ))
    print("counters:", json.dumps(summary["counters"]))


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
