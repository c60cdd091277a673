"""Test-only entry for the ``train_bd_moe`` runner: the rest of a run at a
tiny size on the CPU, as ``tiny_swa_moe.py`` is for ``train_swa_moe``
(same manifest, same ``harness.execute``)."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-bd-moe", "model_type": "sdar_moe",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 2048, "max_window_layers": 8,
    "mlp_only_layers": [], "moe_intermediate_size": 48,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_published": 16, "experts_held_first": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "vocab_size": 211, "n_layer": 3, "block_length": 4,
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True,
                    flash_block_q=None, flash_block_k=None),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_bd_moe", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "sampling_eps": 1e-3,
       "reference_steps": 3, "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-2,
          "delta_norm_gap": 1.5e-2, "attention_row_gap": 3e-2}


def make_run(seed, seconds=0.0, limits=None, config=None, mix=None):
    import jax

    cell = {"name": "tiny-bd-moe", "config": "tiny", "traffic": "bdmoe",
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
