"""Test-only entry: the rest of a run at a tiny size, without the
harness's look for a chip (``chipbench/run.py`` itself keeps refusing to
run off the TPU).  Builds the :class:`harness.Run` that ``run.py`` would
and hands it to the same ``harness.execute``."""

import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

TINY_MODEL = {
    "vocab_size": 211, "n_embd": 64, "n_head": 2, "n_inner": 128,
    "n_layer": 2, "n_positions": 128,
}
TRAIN_CONFIG = dict(
    TINY_MODEL, name="tiny-train",
    optimizer={"name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1,
               "b1": 0.9, "b2": 0.999, "eps": 1e-8},
    program={"attention": "flash", "flash_block_q": 128,
             "flash_block_k": 128, "loss": "fused_ce", "ce_chunk": 64,
             "remat": False, "donate": True,
             "communicator": {"name": "xla_ici", "bucket_bytes": 4194304,
                              "overlap": True, "overlap_granularity": 1,
                              "comm_dtype": "none"}})
TRAIN_MIX = {"kind": "train", "global_batch": 2, "seq_len": 128,
             "token_dist": {"name": "zipf", "s": 1.0},
             "reference_steps": 3, "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-2, "grad_norm_gap": 5e-2,
          "delta_norm_gap": 5e-2}


def manifest(cell, per_layer=()):
    e2e = [{"name": "setup_s", "unit": "s", "better": "lower",
            "bound": 0.1, "source": "host_clock"},
           {"name": "train_step_ms", "unit": "ms", "better": "lower",
            "bound": 0.05, "source": "host_clock"}]
    return {"workloads": [cell], "end_to_end": e2e,
            "per_layer": list(per_layer)}


def tiny_run(seed=1, seconds=1.0, trace=False, chips=1, mix=None,
             config=None, limits=None, per_layer=()):
    """One tiny training run through ``harness.execute``; returns
    (line, run)."""
    import jax

    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    cell = {"name": "tiny-train", "config": "tiny", "traffic": "train",
            "chips": chips}
    base_mix = dict(TRAIN_MIX, global_batch=2 * chips)
    base_mix.update(mix or {})
    run = harness.Run(
        manifest=manifest(cell, per_layer), cell=cell,
        config=copy.deepcopy(config or TRAIN_CONFIG),
        mix=base_mix, limits=dict(LIMITS, **(limits or {})), seed=seed,
        seconds=seconds, trace=trace, t_start=time.perf_counter(),
        devices=list(jax.devices()[:chips]))
    return harness.execute(run), run
