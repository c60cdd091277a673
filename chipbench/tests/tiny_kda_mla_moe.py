"""Test-only entry for the ``train_kda_mla_moe`` runner: the rest of a run
at a tiny size on the CPU, as ``tiny_gdn_moe.py`` is for ``train_gdn_moe``
(same manifest, same ``harness.execute``)."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-kda-mla-moe", "model_type": "bailing_hybrid",
    "num_hidden_layers": 6, "hidden_size": 64, "vocab_size": 211,
    "intermediate_size": 96, "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "tie_word_embeddings": False, "layer_group_size": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 16, "kv_lora_rank": 24,
    "q_lora_rank": None, "qk_head_dim": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
    "rope_interleave": True, "rope_scaling": None, "rotary_dim": 8,
    "partial_rotary_factor": 0.5, "use_qk_norm": True,
    "use_mla_nope": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "short_conv_kernel_size": 4, "linear_silu": True, "kda_safe_gate": True,
    "kda_lower_bound": -5, "no_kda_lora": True, "use_kda_lora": False,
    "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
    "num_experts": 4, "num_experts_published": 16, "experts_held_first": 2,
    "num_experts_per_tok": 3, "num_shared_experts": 1,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 48,
    "moe_router_enable_expert_bias": True, "n_group": 4, "topk_group": 2,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "scale_router_input": False,
    "expert_swiglu_limit_list": [0] * 6,
    "share_expert_swiglu_limit_list": [0] * 6, "use_nGPT": False,
    "value_norm": False, "up_proj_norm": False, "use_bias": False,
    "use_qkv_bias": False, "mtp_use_kda": False,
    "mtp_loss_scaling_factor": 0, "num_nextn_predict_layers": 1,
    "max_position_embeddings": 1024, "max_window_layers": 20,
    "seq_aux": True, "n_layer": 3,
    "balancing": {"rate": 0.05},
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_kda_mla_moe", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "reference_steps": 3,
       "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 4e-2,
          "delta_norm_gap": 1.5e-2, "router_pair_diff_share": 2.5e-2}


def make_run(seed, seconds=0.0, limits=None, config=None, mix=None):
    import jax

    cell = {"name": "tiny-kda-mla-moe", "config": "tiny",
            "traffic": "kdamlamoe", "chips": 1}
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
