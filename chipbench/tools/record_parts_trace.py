#!/usr/bin/env python3
"""Record the fixture ``chipbench/tests/test_parts_reduce.py`` reads the
owner readers on: a tiny ``train_hybrid`` run (two Mamba-2 layers, an
attention layer, a third Mamba-2 layer, SwiGLU, ``remat``), traced, on
the chip, together with the ``as_text()`` of its compiled step.

    chiprun -- python3 chipbench/tools/record_parts_trace.py chiprun_out/parts_fixture
    cp chiprun_out/parts_fixture/tiny_hybrid.* chipbench/data/

``CONFIG``, ``MIX`` and ``STEPS`` are what the test rebuilds its context
from.
"""

import copy
import glob
import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.tests import tiny, tiny_hybrid  # noqa: E402

STEPS = 3
#: The kernels' least shapes on the chip: a scan block of 128 tokens.
CONFIG = dict(tiny_hybrid.CONFIG, mamba_chunk_size=128)
MIX = dict(tiny_hybrid.MIX, seq_len=256, trace_steps=STEPS)
READERS = ("part.ffn_ms", "part.ffn_roofline", "part.mixer_proj_ms",
           "part.mixer_gate_ms", "part.norm_ms", "part.residual_ms",
           "part.embed_ms", "part.recompute_ms", "parts.unowned_pct",
           "parts.shared_pct")


def main():
    import jax
    import jax.numpy as jnp

    from chipbench import harness, weights_hybrid
    from chipbench.runners.train_hybrid import HybridJob

    out_dir = os.path.abspath(sys.argv[1])
    os.makedirs(out_dir, exist_ok=True)
    harness.ProfilerSlice.keep_dir = out_dir
    cell = {"name": "tiny-hybrid", "config": "tiny", "traffic": "hybrid",
            "chips": 1}
    per_layer = [
        {"name": n, "unit": "x", "better": "lower",
         "source": "device_trace", "layer": "x", "moves": "train_step_ms"}
        for n in READERS]
    devices = list(jax.devices()[:1])
    run = harness.Run(
        manifest=tiny.manifest(cell, per_layer), cell=cell,
        config=copy.deepcopy(CONFIG), mix=dict(MIX),
        limits=dict(tiny_hybrid.LIMITS), seed=1, seconds=1.0, trace=True,
        t_start=time.perf_counter(), devices=devices)
    print(json.dumps(harness.execute(run)))
    (found,) = glob.glob(os.path.join(out_dir, "*.xplane.pb"))
    with open(found, "rb") as src, gzip.open(
            os.path.join(out_dir, "tiny_hybrid.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    os.remove(found)
    # The step once more, from abstract arguments: the program the run
    # traced (its instruction names are the capture's).
    job = HybridJob(CONFIG, MIX, devices)
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=job.replicated), tree)
    params = placed(jax.eval_shape(lambda: weights_hybrid.make(CONFIG, 0)))
    state = placed(jax.eval_shape(job.opt.init, params))
    tokens = jax.ShapeDtypeStruct(
        (MIX["global_batch"], MIX["seq_len"]), jnp.int32, sharding=job.rows)
    text = job.step_fn.lower(params, state, (tokens, tokens)).compile(
        ).as_text()
    with gzip.open(os.path.join(out_dir, "tiny_hybrid.hlo.txt.gz"),
                   "wt") as dst:
        dst.write(text)


if __name__ == "__main__":
    main()
