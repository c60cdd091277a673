#!/usr/bin/env python3
"""Record a capture ``chipbench/tests`` read the per-layer readers on: a
tiny run of one kind of step, traced, on the chip, together with the
``as_text()`` of the very step it compiled (the program whose instruction
names the capture's events carry).

    chiprun -- python3 chipbench/tools/record_trace.py chiprun_out/captures <kind>...
    cp chiprun_out/captures/<name>.* chipbench/data/

No kind named: every kind.  ``KINDS`` is what
``chipbench/tests/captures.py`` rebuilds a capture's context from: the
cell it stands for (its entries of the manifest are the readers asked
for), the tiny module under ``chipbench/tests`` whose configuration, mix
and limits it starts from, and what it changes of them to reach the
kernels' least shapes on the chip.
"""

import copy
import glob
import gzip
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 3
#: name of the files -> (cell, tiny module, configuration changes, mix
#: changes).  A scan block of 128 tokens; heads of 128 and tiles of 256
#: where a kind's kernels ask for them.
KINDS = {
    "tiny_hybrid": (
        "granite4hm-train-1chip", "tiny_hybrid",
        dict(mamba_chunk_size=128), dict(seq_len=256)),
    "tiny_swa_moe": (
        "mellum2-train-1chip", "tiny_swa_moe",
        dict(hidden_size=256, head_dim=128, num_attention_heads=4,
             num_key_value_heads=2, moe_intermediate_size=128,
             sliding_window=256, vocab_size=512,
             rope_parameters={
                 "full_attention": {
                     "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                     "original_max_position_embeddings": 256,
                     "beta_fast": 32, "beta_slow": 1,
                     "attention_factor": 1.1386294361119891},
                 "sliding_attention": {"rope_type": "default",
                                       "rope_theta": 10000}}),
        dict(global_batch=1, seq_len=1024)),
    "tiny_gpt2": (
        "cgpt-train-1chip", "tiny", dict(), dict(global_batch=4)),
    "tiny_moe_hybrid": (
        "nemo3nano-train-1chip", "tiny_moe_hybrid",
        dict(chunk_size=128, hidden_size=256, head_dim=128,
             num_attention_heads=2, num_key_value_heads=2,
             mamba_head_dim=64, mamba_num_heads=8, intermediate_size=512,
             moe_intermediate_size=128,
             moe_shared_expert_intermediate_size=256, vocab_size=512),
        dict(global_batch=1, seq_len=1024)),
    "tiny_cca_moe": (
        "zaya1-train-1chip", "tiny_cca_moe",
        dict(hidden_size=256, head_dim=128, num_attention_heads=2,
             num_key_value_heads=2, moe_intermediate_size=128,
             vocab_size=512),
        dict(global_batch=1, seq_len=1024)),
    "tiny_gdn_moe": (
        "qwen3next-train-1chip", "tiny_gdn_moe",
        dict(hidden_size=256, head_dim=128, num_attention_heads=2,
             num_key_value_heads=2, linear_key_head_dim=128,
             linear_value_head_dim=128, linear_num_key_heads=1,
             linear_num_value_heads=2, intermediate_size=512,
             moe_intermediate_size=128,
             shared_expert_intermediate_size=128, vocab_size=512),
        dict(global_batch=1, seq_len=1024)),
    "tiny_kda_mla_moe": (
        "ling3flash-train-1chip", "tiny_kda_mla_moe",
        dict(hidden_size=256, head_dim=128, num_attention_heads=2,
             num_key_value_heads=2, kv_lora_rank=128, qk_head_dim=192,
             qk_nope_head_dim=128, qk_rope_head_dim=64, rotary_dim=64,
             v_head_dim=128, intermediate_size=512,
             moe_intermediate_size=128,
             moe_shared_expert_intermediate_size=128, vocab_size=512),
        dict(global_batch=1, seq_len=1024)),
}


def context(name):
    """``(cell, configuration, mix, limits)`` of the kind ``name``."""
    cell, module, config, mix = KINDS[name]
    tiny = importlib.import_module("chipbench.tests." + module)
    base_config = getattr(tiny, "CONFIG", None) or tiny.TRAIN_CONFIG
    base_mix = getattr(tiny, "MIX", None) or tiny.TRAIN_MIX
    return (cell, dict(base_config, **config),
            dict(base_mix, trace_steps=STEPS, **mix), dict(tiny.LIMITS))


def record(name, out_dir):
    import jax

    from chainermn_tpu.observability import device_trace
    from chipbench import harness

    cell_name, config, mix, limits = context(name)
    cell = {"name": cell_name, "config": "tiny", "traffic": "tiny",
            "chips": 1}
    manifest = harness.load_manifest()
    manifest = {"workloads": [cell], "end_to_end": manifest["end_to_end"],
                "per_layer": harness.cell_metrics(
                    manifest, cell_name, "per_layer")}
    texts = []
    scope_table = device_trace.scope_table

    def keeping(compiled):
        texts.append(compiled.as_text())
        return scope_table(compiled)

    device_trace.scope_table = keeping
    harness.ProfilerSlice.keep_dir = os.path.join(out_dir, name)
    run = harness.Run(
        manifest=manifest, cell=cell, config=copy.deepcopy(config),
        mix=dict(mix), limits=limits, seed=1, seconds=1.0, trace=True,
        t_start=time.perf_counter(), devices=list(jax.devices()[:1]))
    try:
        print(json.dumps(harness.execute(run)))
    finally:
        device_trace.scope_table = scope_table
    (found,) = glob.glob(os.path.join(out_dir, name, "*.xplane.pb"))
    with open(found, "rb") as src, gzip.open(
            os.path.join(out_dir, name + ".xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(os.path.join(out_dir, name))
    (text,) = set(texts)
    with gzip.open(os.path.join(out_dir, name + ".hlo.txt.gz"), "wt") as dst:
        dst.write(text)


def main():
    out_dir = os.path.abspath(sys.argv[1])
    os.makedirs(out_dir, exist_ok=True)
    for name in sys.argv[2:] or KINDS:
        record(name, out_dir)


if __name__ == "__main__":
    main()
