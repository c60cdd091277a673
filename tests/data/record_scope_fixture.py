#!/usr/bin/env python3
"""Record the fixture ``tests/test_device_trace.py`` holds the join on: a
tiny data-parallel LM train step (flash attention + fused CE + AdamW
through ``make_train_step``), three steps captured on the chip by
``device_trace.capture``, together with the ``as_text()`` of the compiled
step.

    chiprun -- python tests/data/record_scope_fixture.py chiprun_out/scope_fixture
    cp chiprun_out/scope_fixture/tiny_step.* tests/data/

``build_tiny_step`` is also what the vocabulary test compiles on the CPU.
"""

import glob
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

VOCAB, SEQ, BATCH, STEPS = 211, 128, 4, 3


def build_tiny_step(devices):
    """``(step, params, state, feed)``: the step ``make_train_step``
    builds over ``devices``, its seeded parameters and optimizer state,
    and ``feed(i)`` placing the i-th global batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu
    from chainermn_tpu.communicators import build_mesh
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.ops import make_flash_attention_fn
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    mesh = build_mesh(inter_size=1, intra_size=len(devices),
                      devices=devices)
    comm = chainermn_tpu.create_communicator("xla_ici", mesh=mesh)
    model = TransformerLM(
        vocab=VOCAB, d_model=64, n_heads=2, d_ff=128, n_layers=2,
        max_len=SEQ, attention_fn=make_flash_attention_fn(
            causal=True, block_q=128, block_k=128))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adamw(3e-4, weight_decay=0.1), comm)

    def loss_fn(p, batch):
        tokens, labels = batch
        h = model.apply({"params": p}, tokens, return_hidden=True)
        return fused_cross_entropy(
            h, p["embed"]["embedding"], labels, chunk=64)

    step = opt.make_train_step(loss_fn)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, SEQ), jnp.int32))["params"]
    state = opt.init(params)

    def feed(i):
        rows = np.random.default_rng(i).integers(
            0, VOCAB, size=(BATCH * len(devices), SEQ + 1)).astype(np.int32)
        return comm.global_batch((rows[:, :-1].copy(), rows[:, 1:].copy()))

    return step, params, state, feed


def main():
    import jax

    from chainermn_tpu.observability import device_trace

    out_dir = os.path.abspath(sys.argv[1])
    trace_dir = os.path.join(out_dir, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    step, params, state, feed = build_tiny_step(jax.devices()[:1])
    for i in range(2):
        params, state, loss = step(params, state, feed(i))
    jax.block_until_ready(loss)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding),
        (params, state, feed(0)))
    with device_trace.capture({"train_step": step}, logdir=trace_dir) as cap:
        for i in range(STEPS):
            params, state, loss = cap["train_step"](
                params, state, feed(2 + i))
        jax.block_until_ready(loss)
    print(json.dumps(cap.report))
    (found,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    with open(found, "rb") as src, gzip.open(
            os.path.join(out_dir, "tiny_step.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    text = step.lower(*abstract).compile().as_text()
    with gzip.open(os.path.join(out_dir, "tiny_step.hlo.txt.gz"),
                   "wt") as dst:
        dst.write(text)
    shutil.rmtree(trace_dir)


if __name__ == "__main__":
    main()
