"""Test-only entry for the ``train_gdn_moe`` runner: the rest of a run at a
tiny size on the CPU, as ``tiny_moe_hybrid.py`` is for
``train_moe_hybrid`` (same manifest, same ``harness.execute``)."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-gdn-moe", "model_type": "qwen3_next",
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 32,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 16, "max_position_embeddings": 1024,
    "mlp_only_layers": [], "moe_intermediate_size": 48,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_published": 16, "experts_held_first": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 48, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 211, "n_layer": 4,
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_gdn_moe", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "reference_steps": 3,
       "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-2,
          "delta_norm_gap": 1.5e-2, "router_pair_diff_share": 1.2e-2}


def make_run(seed, seconds=0.0, limits=None, config=None, mix=None):
    import jax

    cell = {"name": "tiny-gdn-moe", "config": "tiny", "traffic": "gdnmoe",
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
