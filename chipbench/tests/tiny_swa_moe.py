"""Test-only entry for the ``train_swa_moe`` runner: the rest of a run at a
tiny size on the CPU, as ``tiny_gdn_moe.py`` is for ``train_gdn_moe``
(same manifest, same ``harness.execute``)."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-swa-moe", "model_type": "mellum",
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 8, "max_position_embeddings": 2048,
    "max_window_layers": 0, "moe_intermediate_size": 48,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_published": 16, "experts_held_first": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 64, "beta_fast": 8,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "sliding_window": 48, "use_sliding_window": True,
    "tie_word_embeddings": False, "vocab_size": 211, "n_layer": 4,
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True,
                    flash_block_q=None, flash_block_k=None),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_swa_moe", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "reference_steps": 3,
       "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-2,
          "delta_norm_gap": 1.5e-2, "router_pair_diff_share": 1.2e-2}


def make_run(seed, seconds=0.0, limits=None, config=None, mix=None):
    import jax

    cell = {"name": "tiny-swa-moe", "config": "tiny", "traffic": "swamoe",
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
