"""Test-only entry for the ``train_gswa_moe`` runner: the rest of a run at
a tiny size on the CPU, as ``tiny_swa_moe.py`` is for ``train_swa_moe``
(same manifest, same ``harness.execute``).  The tiny config keeps what
the family forces: query heads a layer that differ (groups of 2 and 3
over 2 key/value heads, neither 1), a full row that rotates half its
head under YaRN, a leading dense layer, a shared expert."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-gswa-moe", "model_type": "laguna",
    "attention_bias": False, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 128,
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 2,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7, "mlp_only_layers": [0],
    "gating": "per-head", "gating_types": ["per_head"] * 8,
    "decoder_sparse_step": 1, "max_position_embeddings": 2048,
    "moe_intermediate_size": 48, "shared_expert_intermediate_size": 48,
    "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False,
    "moe_router_logit_softcapping": 0, "num_attention_heads": 4,
    "num_experts": 4, "num_experts_published": 16, "experts_held_first": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 128,
            "original_max_position_embeddings": 64, "beta_fast": 8,
            "beta_slow": 1, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1000,
                              "partial_rotary_factor": 1}},
    "sliding_window": 48, "tie_word_embeddings": False, "vocab_size": 211,
    "n_layer": 5, "attention_rows_compared": ["layer_1", "layer_4"],
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True,
                    flash_block_q=None, flash_block_k=None),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_gswa_moe", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "reference_steps": 3,
       "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-2,
          "delta_norm_gap": 1.5e-2, "router_pair_diff_share": 1.2e-2,
          "attention_row_gap": 2e-2}


def make_run(seed, seconds=0.0, limits=None, config=None, mix=None):
    import jax

    cell = {"name": "tiny-gswa-moe", "config": "tiny", "traffic": "gswamoe",
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
