"""The ZAYA1-8B configuration, its cell and its arithmetic."""

import json
import math
import os

import pytest

from chipbench import flops_zaya, harness, weights_zaya

CELL = "zaya1-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: The source's ``config.json`` as the model catalog carries it
#: (https://huggingface.co/Zyphra/ZAYA1-8B), without the two keys the cut
#: changes and without ``layer_types`` (40 x "hybrid", checked apart).
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5,
                           "rope_theta": 10000, "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True,
}


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_holds_every_published_key_unchanged(cell):
    _, config, _, _ = cell
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["layer_types"] == ["hybrid"] * 40
    assert (config["num_experts"], config["vocab_size"],
            config["n_layer"]) == (8, 32784, 5)
    assert (config["num_experts_published"],
            config["experts_held_first"]) == (16, 0)
    reduced = config["reduced"]
    assert set(reduced) == {"n_layer", "num_experts", "vocab_size"}
    assert reduced["n_layer"]["source_key"] == "num_hidden_layers"
    assert reduced["n_layer"]["source"] == config["num_hidden_layers"]
    assert reduced["num_experts"]["source"] == 16
    assert reduced["vocab_size"]["source"] == 8 * config["vocab_size"]
    for key, entry in reduced.items():
        assert entry["here"] == config[key] and entry["why"]
    entry = [c for c in harness.load_manifest()["configs"]
             if c["name"] == "zaya1-8b-train"][0]
    assert entry["reduced"] == sorted(reduced)
    assert entry["source"] == config["source"]
    for key in ("cca", "value_heads", "qk_norm", "rotary", "router",
                "balancing_bias", "residual_scaling", "experts", "weights",
                "optimizer", "remat"):
        assert config["assumed"][key]
    assert config["optimizer"]["learning_rate"] == 1e-06
    assert config["balancing"]["rate"] == 0.05 and config["balancing"]["form"]
    assert "PENDING" not in json.dumps(config)
    for words in ("16 chips", "8 pipeline stages", "vocabulary-parallel",
                  "1,024 tokens"):
        assert words in config["deployment"]


def test_the_config_is_the_catalog_rows_where_the_catalog_is_here(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    _, config, _, _ = cell
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "ZAYA1-8B"][0]
    assert config["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differing == {"num_experts", "vocab_size"}


def test_the_cut_counts_what_the_issue_counted(cell):
    _, config, _, _ = cell
    shapes = weights_zaya.shapes(config)

    def count(*prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    mixer = count("layer_0", "CCAMixer_0")
    conv1 = count("layer_0", "CCAMixer_0", "conv1_kernel")
    assert conv1 == 2 * 10 * 128 * 128                      # 0.33 M
    projections = 2048 * (1024 + 256 + 128 + 128) + 1024 * 2048
    assert projections == 5_242_880                          # 5.24 M
    # + the depthwise taps, both biases and tau
    assert mixer == projections + conv1 + 2 * 1280 + 2 * 1280 + 2
    router = count("layer_0", "ExpertLayer_0") - 3 * 8 * 2048 * 2048
    assert router == 2048 * 256 + 2 * 256 + 2 * 256 * 256 + 256 * 16 + 16
    assert count("layer_0", "ExpertLayer_0", "experts_gate") == (
        8 * 2048 * 2048)                             # 12.58 M an expert
    assert count("layer_3") == count("layer_0") == 106_903_058
    assert count("embed") == 32784 * 2048 and ("lm_head",) not in shapes
    total = weights_zaya.n_params(config)
    assert total == 5 * 106_903_058 + 32784 * 2048 + 2048 == 601_658_970
    assert 0.25 * 16e9 < 16 * total < 0.70 * 16e9            # 9.63 GB


def test_cell_traffic_and_limits(cell):
    entry, config, mix, limits = cell
    assert entry["chips"] == 1 and entry["traffic"] == "ccamoe8k-b2"
    assert (mix["kind"], mix["global_batch"], mix["seq_len"]) == (
        "train_cca_moe", 2, 8192)
    assert (mix["reference_steps"], mix["dispatch_ahead"],
            mix["trace_steps"]) == (2, 2, 4)
    assert mix["token_dist"] == {"name": "zipf", "s": 1.0}
    assert {"loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
            "router_pair_diff_share", "set_from"} <= set(limits)
    assert "PENDING" not in limits["set_from"]
    assert "18,432" in config["program"]["moe_rows_bound_note"]


def test_flop_and_byte_arithmetic(cell):
    _, config, mix, _ = cell
    z = weights_zaya.sizes(config)
    assert (z["rotary_dim"], z["rope_theta"], z["conv_dim"]) == (
        64, 5e6, 1280)
    assert flops_zaya.expected_held_pairs(config, mix) == 8192
    assert flops_zaya.gmm_flops([8192], z) == 18 * 2048 * 2048 * 8192
    weights = 3 * 8 * 2048 * 2048
    assert flops_zaya.gmm_bytes([8192], z) == (
        5 * 8192 * 2048 * 2 + weights * 8)
    total = flops_zaya.train_flops_per_step(config, mix)
    routed = flops_zaya.gmm_flops([8192] * 5, z)
    # every token passes 98.3 M parameters (9.7 T), five latent attention
    # layers 4.1 T, the held experts at their expected load 3.1 T
    assert total == pytest.approx(16.88e12, rel=2e-3)
    assert 0.17 < routed / total < 0.20
    least, bound = flops_zaya.gmm_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute" and 0.012 < least < 0.02
    more, _ = flops_zaya.gmm_roofline_seconds(
        config, mix, "TPU v5 lite", [16384] * 5)
    assert more == pytest.approx(2 * least, rel=1e-6)
    # five causal GQA 8/2 layers of D=128 at S=8192 in the latent
    assert flops_zaya.flash_bytes(2, 8192, z) == (
        6 * (8 + 2) * 128 * 2 * 16384 * 5)
    least, bound = flops_zaya.flash_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(
        12 * 4096.5 * 8 * 128 * 16384 * 5 / 197e12, rel=1e-6)


def test_balanced_biases_spread_the_first_batch_over_all_the_experts():
    """What the cell's weights add to the seeded tree: every layer's
    balancing bias where a balancing controller would hold it for the
    batch — the reference's own routers then load all experts alike, held
    or not — and nothing else moved."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import traffic, weights
    from chipbench.refs import zaya1 as reference
    from chipbench.tests import tiny_cca_moe

    config, mix = tiny_cca_moe.CONFIG, dict(tiny_cca_moe.MIX, seq_len=512)
    tokens, _ = traffic.train_batches(mix, config["vocab_size"], 7)(0)
    before = weights_zaya.make(config, 7)
    biases = weights_zaya.balanced_biases(before, jnp.asarray(tokens),
                                          config)
    assert sorted(biases) == ["layer_0", "layer_1", "layer_2"]
    even = weights_zaya.with_biases(before, biases)

    def loads(params):
        masks = reference.chosen_experts(params, jnp.asarray(tokens), config)
        return {name: np.asarray(m).reshape(-1, 8).sum(0)
                for name, m in masks.items()}

    assert max(load.max() for load in loads(before).values()) > 2 * 128
    for name, load in loads(even).items():
        assert load.sum() == 1024 and abs(load - 128).max() <= 4, (
            name, load)
    for path, leaf in weights.flatten(jax.device_get(even)).items():
        same = np.array_equal(leaf, weights.flatten(before)[path])
        assert same != (path[-1] == "router_bias"), path
    p = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (4096, 16)))
    b = weights_zaya.spread_evenly(p)
    load = np.bincount(np.asarray(jnp.argmax(p + b, -1)), minlength=16)
    assert abs(load - 256).max() <= 8
