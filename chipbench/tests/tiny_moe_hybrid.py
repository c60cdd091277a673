"""Test-only entry for the ``train_moe_hybrid`` runner: the rest of a run
at a tiny size on the CPU, as ``tiny_hybrid.py`` is for ``train_hybrid``
(same manifest, same ``harness.execute``)."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-moe-hybrid", "model_type": "nemotron_h",
    "attention_bias": False, "chunk_size": 32, "conv_kernel": 4,
    "expand": 2, "head_dim": 32, "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EME", "intermediate_size": 48,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 16,
    "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "n_group": 1, "n_groups": 2, "n_routed_experts": 2,
    "n_routed_experts_published": 8, "experts_held_first": 2,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 7, "num_key_value_heads": 2,
    "routed_scaling_factor": 2.5, "ssm_state_size": 16,
    "tie_word_embeddings": False, "time_step_floor": 1e-4,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "vocab_size": 211,
    "n_layer": 5,
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_moe_hybrid", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "reference_steps": 3,
       "dispatch_ahead": 2, "trace_steps": 2}
# Between what five seeds of the program read against the reference that
# took its experts (loss 3.3e-5, grad 4.6e-3, delta 5.1e-3, routers
# 4.6e-3) and the fp8 control's smallest (1.3e-4, 2.5e-2, 1.05e-2,
# 3.1e-2), on the CPU at this size.
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-2,
          "delta_norm_gap": 1.5e-2, "router_pair_diff_share": 1.2e-2}


def make_run(seed, seconds=0.0, limits=None, config=None, mix=None):
    import jax

    cell = {"name": "tiny-moe-hybrid", "config": "tiny",
            "traffic": "moehybrid", "chips": 1}
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
