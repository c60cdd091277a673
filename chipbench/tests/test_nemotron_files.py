"""The NVIDIA-Nemotron-3-Nano-30B-A3B configuration, its cell and its
arithmetic."""

import json
import math
import os

import pytest

from chipbench import flops_nemotron, harness, weights_nemotron

CELL = "nemo3nano-train-1chip"

#: The source's ``config.json`` as the model catalog carries it
#: (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16),
#: without the three keys the cut changes.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True,
}


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_holds_every_published_key_unchanged(cell):
    _, config, _, _ = cell
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert len(config["hybrid_override_pattern"]) == 52
    assert (config["n_routed_experts"], config["vocab_size"],
            config["n_layer"]) == (8, 16384, 9)
    assert (config["n_routed_experts_published"],
            config["experts_held_first"]) == (128, 0)
    reduced = config["reduced"]
    assert set(reduced) == {"n_layer", "n_routed_experts", "vocab_size"}
    assert reduced["n_layer"]["source_key"] == "num_hidden_layers"
    assert reduced["n_layer"]["source"] == config["num_hidden_layers"]
    assert reduced["n_routed_experts"]["source"] == 128
    assert reduced["vocab_size"]["source"] == 8 * config["vocab_size"]
    for key, entry in reduced.items():
        assert entry["here"] == config[key] and entry["why"]
    entry = [c for c in harness.load_manifest()["configs"]
             if c["name"] == "nemotron3nano-train"][0]
    assert entry["reduced"] == sorted(reduced)
    assert entry["source"] == config["source"]
    for key in ("rotary", "gated_norm", "d_inner", "router",
                "correction_bias", "weights", "optimizer", "chunk_size"):
        assert config["assumed"][key]
    assert "16 chips" in config["deployment"]
    assert "vocabulary-parallel" in config["deployment"]
    assert "six pipeline stages" in config["deployment"]
    assert "768 tokens" in config["deployment"]


def test_the_config_is_the_catalog_rows_where_the_catalog_is_here(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    _, config, _, _ = cell
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows
           if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"][0]
    assert config["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differing == {"n_routed_experts", "vocab_size"}


def test_the_cut_counts_what_the_issue_counted(cell):
    _, config, _, _ = cell
    z = weights_nemotron.sizes(config)
    assert "".join(k[0] for k in z["kinds"]) == "memema" "eme"
    shapes = weights_nemotron.shapes(config)

    def count(prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    assert count(("layer_0",)) == 38_744_896            # a Mamba layer
    assert count(("layer_5",)) == 23_399_040            # the attention layer
    assert count(("layer_1", "ExpertLayer_0", "experts_up")) == (
        8 * 2688 * 1856)
    assert count(("layer_1",)) == 20_302_592 + 8 * 9_977_856
    assert count(("embed",)) == count(("lm_head",)) == 16384 * 2688
    total = weights_nemotron.n_params(config)
    assert total == 666_963_456
    assert 0.25 * 16e9 < 16 * total < 0.70 * 16e9        # 10.67 GB


def test_cell_traffic_and_limits(cell):
    entry, config, mix, limits = cell
    assert entry["chips"] == 1
    assert (mix["kind"], mix["global_batch"], mix["seq_len"]) == (
        "train_moe_hybrid", 2, 8192)
    assert (mix["reference_steps"], mix["dispatch_ahead"],
            mix["trace_steps"]) == (2, 2, 4)
    assert mix["token_dist"] == {"name": "zipf", "s": 1.0}
    assert {"loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
            "router_pair_diff_share", "set_from"} <= set(limits)
    assert "24,576" in config["program"]["moe_rows_bound_note"]


def test_flop_and_byte_arithmetic(cell):
    _, config, mix, _ = cell
    z = weights_nemotron.sizes(config)
    assert flops_nemotron.expected_held_pairs(config, mix) == 6144
    assert flops_nemotron.gmm_flops([6144], z) == (
        12 * 2688 * 1856 * 6144)
    weights = 2 * 8 * 2688 * 1856
    assert flops_nemotron.gmm_bytes([6144], z) == (
        5 * 6144 * 2688 * 2 + weights * 8)
    total = flops_nemotron.train_flops_per_step(config, mix)
    routed = flops_nemotron.gmm_flops([6144] * 4, z)
    # every token passes 347.7 M parameters (34.2 T), attention 3.3 T, the
    # recurrence 0.5 T, the held experts at their expected load 1.5 T
    assert total == pytest.approx(39.46e12, rel=1e-3)
    assert 0.03 < routed / total < 0.05
    least, bound = flops_nemotron.gmm_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute" and 0.005 < least < 0.01
    more, _ = flops_nemotron.gmm_roofline_seconds(
        config, mix, "TPU v5 lite", [12288] * 4)
    assert more == pytest.approx(2 * least, rel=1e-6)
    # the recurrence at 64 heads of 64 x 128 state, four layers: 0.5 TFLOP
    # and 2.1 GB a step, memory-bound; one causal GQA 32/2 layer of D=128
    # at S=8192: 3.3 TFLOP against 0.9 GB, compute-bound
    least, bound = flops_nemotron.ssd_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "memory" and least == pytest.approx(
        (5 * 8192 + 3 * 4096 + 3 * 256) * 16384 * 4 / 819e9, rel=1e-6)
    assert flops_nemotron.flash_bytes(2, 8192, z, 1) == (
        6 * (32 + 2) * 128 * 2 * 16384)
    least, bound = flops_nemotron.flash_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(
        12 * 4096.5 * 32 * 128 * 16384 / 197e12, rel=1e-6)

