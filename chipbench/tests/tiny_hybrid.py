"""Test-only entry for the ``train_hybrid`` runner: the rest of a run at a
tiny size on the CPU, as ``tiny.py`` is for ``train`` (same manifest,
same ``harness.execute``)."""

import copy
import time

from chipbench import harness
from chipbench.tests import tiny

CONFIG = {
    "name": "tiny-hybrid", "model_type": "granitemoehybrid",
    "attention_bias": False, "attention_multiplier": 0.125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "logits_scaling": 8, "mamba_chunk_size": 32, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 5, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
    "vocab_size": 211, "n_layer": 4,
    "optimizer": tiny.TRAIN_CONFIG["optimizer"],
    "program": dict(tiny.TRAIN_CONFIG["program"], remat=True),
    "precision": {"compute": "bfloat16", "control": "fp8_e4m3"},
}
MIX = {"kind": "train_hybrid", "global_batch": 2, "seq_len": 128,
       "token_dist": {"name": "zipf", "s": 1.0}, "reference_steps": 3,
       "dispatch_ahead": 2, "trace_steps": 2}
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 8e-3,
          "delta_norm_gap": 8e-3}


def make_run(seed, seconds=0.0, limits=None):
    import jax

    cell = {"name": "tiny-hybrid", "config": "tiny", "traffic": "hybrid",
            "chips": 1}
    return harness.Run(
        manifest=tiny.manifest(cell), cell=cell,
        config=copy.deepcopy(CONFIG), mix=dict(MIX),
        limits=dict(LIMITS, **(limits or {})), seed=seed, seconds=seconds,
        trace=False, t_start=time.perf_counter(),
        devices=list(jax.devices()[:1]))


def tiny_run(seed=1, seconds=0.6, limits=None):
    """One tiny hybrid training run through ``harness.execute``; returns
    (line, run)."""
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    run = make_run(seed, seconds, limits)
    return harness.execute(run), run
