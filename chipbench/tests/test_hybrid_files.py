"""The granite-4.0-h-micro configuration, its cell and its arithmetic."""

import math

import pytest

from chipbench import flops_hybrid, harness, weights_hybrid

CELL = "granite4hm-train-1chip"

#: The source's ``config.json`` as the model catalog carries it
#: (https://huggingface.co/ibm-granite/granite-4.0-h-micro), numbers and
#: switches; ``layer_types`` is checked apart.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
}
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_holds_every_published_key_unchanged(cell):
    _, config, _, _ = cell
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["layer_types"] == PERIOD * 4
    assert (config["vocab_size"], config["n_layer"]) == (25088, 10)
    reduced = config["reduced"]
    assert set(reduced) == {"n_layer", "vocab_size"}
    assert reduced["n_layer"]["source_key"] == "num_hidden_layers"
    assert reduced["n_layer"]["here"] == config["n_layer"]
    assert reduced["vocab_size"]["source"] == 4 * config["vocab_size"]
    entry = [c for c in harness.load_manifest()["configs"]
             if c["name"] == "granite4hmicro-train"][0]
    assert entry["reduced"] == sorted(reduced)
    for key in ("assumed", "precision", "deployment"):
        assert config[key]
    assert "vocabulary-parallel" in config["deployment"]
    assert "four pipeline stages" in config["deployment"]


def test_the_cut_is_one_period_and_counts_what_the_issue_counted(cell):
    _, config, _, _ = cell
    z = weights_hybrid.sizes(config)
    assert list(z["kinds"]) == PERIOD
    shapes = weights_hybrid.shapes(config)

    def count(prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    assert count(("layer_0",)) == 76_182_976          # a mamba layer
    assert count(("layer_5",)) == 60_821_504          # the attention layer
    assert count(("embed",)) == 25088 * 2048
    assert weights_hybrid.n_params(config) == 797_850_560


def test_cell_traffic_and_limits(cell):
    entry, _, mix, limits = cell
    assert entry["chips"] == 1
    assert (mix["kind"], mix["global_batch"], mix["seq_len"]) == (
        "train_hybrid", 2, 8192)
    assert (mix["dispatch_ahead"], mix["trace_steps"]) == (2, 4)
    assert {"loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
            "set_from"} <= set(limits)


def test_flop_and_byte_arithmetic(cell):
    _, config, mix, _ = cell
    z = weights_hybrid.sizes(config)
    total = flops_hybrid.train_flops_per_step(config, mix)
    scan = flops_hybrid.ssd_flops(2, 8192, z)
    assert scan == 15 * 64 * 128 * 64 * 16384 * 9
    assert total == pytest.approx(81.24e12, rel=1e-3)
    assert scan / total < 0.02
    least, bound = flops_hybrid.ssd_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "memory" and 0.005 < least < 0.01

