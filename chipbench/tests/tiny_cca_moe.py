"""Test-only entry for the ``train_cca_moe`` runner: the rest of a run at
a tiny size on the CPU, as ``tiny_moe_hybrid.py`` is for
``train_moe_hybrid`` (same manifest, same ``harness.execute``)."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-cca-moe", "model_type": "zaya", "attention_bias": False,
    "cca_time0": 2, "cca_time1": 2, "head_dim": 32, "hidden_act": "silu",
    "hidden_size": 64, "layer_types": ["hybrid"] * 6,
    "lm_head_bias": False, "max_position_embeddings": 1024,
    "moe_intermediate_size": 48, "num_attention_heads": 4,
    "num_experts": 4, "num_experts_published": 8, "experts_held_first": 2,
    "num_experts_per_tok": 1, "num_hidden_layers": 6,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5,
                           "rope_theta": 10000, "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 16, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 211, "n_layer": 3,
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "balancing": {"rate": 0.05},
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_cca_moe", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "reference_steps": 3,
       "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-2,
          "delta_norm_gap": 1.5e-2, "router_pair_diff_share": 1.2e-2}


def make_run(seed, seconds=0.0, limits=None, config=None, mix=None):
    import jax

    cell = {"name": "tiny-cca-moe", "config": "tiny", "traffic": "ccamoe",
            "chips": 1}
    return harness.Run(
        manifest=tiny.manifest(cell), cell=cell,
        config=copy.deepcopy(config or CONFIG), mix=dict(MIX, **(mix or {})),
        limits=dict(LIMITS, **(limits or {})), seed=seed, seconds=seconds,
        trace=False, t_start=time.perf_counter(),
        devices=list(jax.devices()[:1]))


def tiny_run(seed=1, seconds=0.6, limits=None, config=None, mix=None):
    """One tiny run through ``harness.execute``; returns (line, run)."""
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    run = make_run(seed, seconds, limits, config, mix)
    return harness.execute(run), run
