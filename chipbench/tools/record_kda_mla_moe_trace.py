#!/usr/bin/env python3
"""Record the fixture ``chipbench/tests/test_ling3_files.py`` reads the
``ling.*`` readers on: a tiny ``train_kda_mla_moe`` run (a dense KDA row,
a sparse KDA row and a sparse latent-attention row over 1,024 tokens, four
held of sixteen experts in four groups, ``remat``), traced, on the chip,
together with the ``as_text()`` of its compiled step, as
``record_swa_moe_trace.py`` records the ``mellum.*`` readers' fixture.

    chiprun -- python3 chipbench/tools/record_kda_mla_moe_trace.py chiprun_out/kda_mla_moe_fixture
    cp chiprun_out/kda_mla_moe_fixture/tiny_kda_mla_moe.* chipbench/data/

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

from chipbench.tests import tiny, tiny_kda_mla_moe  # noqa: E402

STEPS = 3
CELL = "ling3flash-train-1chip"
#: The kernels' least shapes on the chip: heads of 128, tiles of 256.
CONFIG = dict(
    tiny_kda_mla_moe.CONFIG, hidden_size=256, head_dim=128,
    num_attention_heads=2, num_key_value_heads=2, kv_lora_rank=128,
    qk_head_dim=192, qk_nope_head_dim=128, qk_rope_head_dim=64,
    rotary_dim=64, v_head_dim=128, intermediate_size=512,
    moe_intermediate_size=128, moe_shared_expert_intermediate_size=128,
    vocab_size=512)
MIX = dict(tiny_kda_mla_moe.MIX, global_batch=1, seq_len=1024,
           trace_steps=STEPS)


def readers():
    from chipbench import harness

    return [m for m in harness.load_manifest()["per_layer"]
            if m.get("workloads") == [CELL]]


def main():
    import jax
    import jax.numpy as jnp

    from chipbench import harness, weights_ling3
    from chipbench.runners.train_kda_mla_moe import KdaMlaMoeJob

    out_dir = os.path.abspath(sys.argv[1])
    os.makedirs(out_dir, exist_ok=True)
    harness.ProfilerSlice.keep_dir = out_dir
    cell = {"name": CELL, "config": "tiny", "traffic": "kdamlamoe",
            "chips": 1}
    devices = list(jax.devices()[:1])
    run = harness.Run(
        manifest=tiny.manifest(cell, readers()), cell=cell,
        config=copy.deepcopy(CONFIG), mix=dict(MIX),
        limits=dict(tiny_kda_mla_moe.LIMITS), seed=1, seconds=1.0,
        trace=True, t_start=time.perf_counter(), devices=devices)
    print(json.dumps(harness.execute(run)))
    (found,) = glob.glob(os.path.join(out_dir, "*.xplane.pb"))
    with open(found, "rb") as src, gzip.open(os.path.join(
            out_dir, "tiny_kda_mla_moe.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    os.remove(found)
    # The step once more, from abstract arguments: the program the run
    # traced (its instruction names are the capture's).
    job = KdaMlaMoeJob(CONFIG, MIX, devices)
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=job.replicated), tree)
    params = placed(jax.eval_shape(lambda: weights_ling3.make(CONFIG, 0)))
    state = placed(jax.eval_shape(job.opt.init, params))
    tokens = jax.ShapeDtypeStruct(
        (MIX["global_batch"], MIX["seq_len"]), jnp.int32, sharding=job.rows)
    text = job.step_fn.lower(params, state, (tokens, tokens)).compile(
        ).as_text()
    with gzip.open(os.path.join(out_dir, "tiny_kda_mla_moe.hlo.txt.gz"),
                   "wt") as dst:
        dst.write(text)


if __name__ == "__main__":
    main()
