"""The Qwen3-Next-80B-A3B configuration, its cell and its arithmetic."""

import json
import math
import os

import pytest

from chipbench import flops_qwen3next, harness, weights_qwen3next

CELL = "qwen3next-train-1chip"
CONFIG = "qwen3next-80b-a3b-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: The source's ``config.json`` as the model catalog carries it
#: (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), without the
#: two keys the cut changes.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False,
}


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_holds_every_published_key_unchanged(cell):
    _, config, _, _ = cell
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert (config["num_experts"], config["vocab_size"],
            config["n_layer"]) == (32, 18992, 4)
    assert (config["num_experts_published"],
            config["experts_held_first"]) == (512, 0)
    reduced = config["reduced"]
    assert set(reduced) == {"n_layer", "num_experts", "vocab_size"}
    assert reduced["n_layer"]["source_key"] == "num_hidden_layers"
    assert reduced["n_layer"]["source"] == config["num_hidden_layers"]
    assert reduced["num_experts"]["source"] == 512
    assert reduced["vocab_size"]["source"] == 8 * config["vocab_size"]
    for key, entry in reduced.items():
        assert entry["here"] == config[key] and entry["why"]
    entry = [c for c in harness.load_manifest()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == sorted(reduced)
    assert entry["source"] == config["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    for key in ("head_order", "norms", "delta_rule_chunk", "decay",
                "rotary", "router", "load_balancing_loss",
                "multi_token_prediction", "shared_expert", "weights",
                "optimizer", "remat", "documents"):
        assert config["assumed"][key]
    assert config["optimizer"]["learning_rate"] == 1e-06
    assert config["precision"]["control"] == "fp8_e4m3"
    for key in ("router", "delta_rule", "attention", "experts"):
        assert config["precision"][key]
    assert "PENDING" not in json.dumps(config)
    for words in ("16 chips", "12 pipeline stages", "vocabulary-parallel",
                  "320 tokens"):
        assert words in config["deployment"]


def test_the_config_is_the_catalog_rows_where_the_catalog_is_here(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    _, config, _, _ = cell
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"][0]
    assert config["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differing == {"num_experts", "vocab_size"}


def test_the_cut_counts_what_the_issue_counted(cell):
    _, config, _, _ = cell
    shapes = weights_qwen3next.shapes(config)
    r = config["reckoning"]

    def count(*prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    # [q | k | v | z] 2048 -> 12288, [b | a] -> 64, 4 taps x 8192 channels,
    # A_log and dt_bias, the gated norm's 128, 4096 -> 2048
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    assert count("layer_0", "GatedDeltaNetMixer_0") == gdn == r["gdn_mixer"]
    assert gdn == 33_718_464                                  # 33.72 M
    # [q | gate] 2048 -> 16 x 512, k and v 2048 -> 2 x 256, two QK-norms,
    # 4096 -> 2048
    att = 2048 * 16 * 512 + 2 * 2048 * 512 + 2 * 256 + 4096 * 2048
    assert count("layer_3", "MultiHeadAttention_0") == att
    assert att == r["attention_mixer"] == 27_263_488          # 27.26 M
    expert = 3 * 2048 * 512
    assert expert == r["routed_expert"] == 3_145_728          # 3.146 M
    rest = 2048 * 512 + expert + 2048      # router, shared expert, gate
    assert count("layer_0", "ExpertLayer_0") == 32 * expert + rest
    assert rest == r["router_shared_gate"] == 4_196_352       # 4.20 M
    assert count("layer_0") == r["gdn_layer"] == 138_582_208
    assert count("layer_1") == count("layer_2") == count("layer_0")
    assert count("layer_3") == r["attention_layer"] == 132_127_232
    assert count("embed") == count("lm_head") == 18992 * 2048
    total = weights_qwen3next.n_params(config)
    assert total == r["total"] == (
        3 * 138_582_208 + 132_127_232 + 2 * 18992 * 2048 + 2048)
    assert total == 625_667_136
    assert r["state_bytes"] == 16 * total
    assert 0.25 * 16e9 < 16 * total < 0.70 * 16e9            # 10.01 GB
    assert not any("router_bias" in p for path in shapes for p in path)


def test_cell_traffic_and_limits(cell):
    entry, config, mix, limits = cell
    assert entry["chips"] == 1 and entry["traffic"] == "gdnmoe8k-b2"
    assert entry["config"] == CONFIG
    assert (mix["kind"], mix["global_batch"], mix["seq_len"]) == (
        "train_gdn_moe", 2, 8192)
    assert (mix["reference_steps"], mix["dispatch_ahead"],
            mix["trace_steps"]) == (2, 2, 4)
    assert mix["token_dist"] == {"name": "zipf", "s": 1.0}
    assert {"loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
            "router_pair_diff_share", "set_from"} <= set(limits)
    assert "PENDING" not in limits["set_from"]
    assert "49,152" in config["program"]["moe_rows_bound_note"]


def test_rows_bound_and_tiles_of_the_cells_shape():
    from chainermn_tpu.parallel import moe_dropless as moe

    rows = moe.rows_bound(16384 * 10, 32, 512)
    assert rows == 40_960
    assert moe.buffer_tiles(rows, 32) == 192


def test_gdn_scan_arithmetic_against_a_hand_count(cell):
    """One chunk of one value head at C = 64, d_k = d_v = 128, forward,
    product by product at 2 a multiply-add."""
    _, config, mix, _ = cell
    z = weights_qwen3next.sizes(config)
    C, d = 64, 128
    by_hand = (2 * C * C * d            # k k^T
               + 2 * C * C * d          # q k^T
               + 2 * C * C * (d + d)    # T (beta e^G k | beta v)
               + 2 * C * C * d          # tril(q k^T ..) v_new
               + 3 * 2 * C * d * d)     # W S, (q e^G) S, (k e^..)^T v_new
    assert by_hand == 11_534_336
    assert flops_qwen3next.gdn_scan_flops_per_chunk_head(d, d) == by_hand
    # a token a layer forward: 32 heads / 64 tokens a chunk: 5.77 M
    assert by_hand * 32 / 64 == 5_767_168
    step = flops_qwen3next.gdn_scan_flops(2, 8192, z)
    assert step == 3 * by_hand * (16384 / 64) * 32 * 3      # fwd + 2 bwd
    assert step == pytest.approx(0.850e12, rel=1e-3)
    # bytes a token a layer: q, k 16 x 128 x 2 B each, v and o 32 x 128 x
    # 2 B, g and beta 32 x 4 B; backward reads those and do, writes dq, dk,
    # dv, dg, dbeta
    qk, v, gb = 2 * 4096, 8192, 256
    per_token = (qk + 2 * v + gb) + (2 * qk + 3 * v + 2 * gb)
    assert per_token == 66_304
    assert flops_qwen3next.gdn_scan_bytes(2, 8192, z) == (
        per_token * 16384 * 3)
    least, bound = flops_qwen3next.gdn_scan_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute"
    assert least == pytest.approx(step / 197e12, rel=1e-6)   # 4.3 ms


def test_flop_and_byte_arithmetic(cell):
    _, config, mix, _ = cell
    z = weights_qwen3next.sizes(config)
    assert (z["rotary_dim"], z["rope_theta"], z["conv_dim"]) == (
        64, 1e7, 8192)
    assert z["kinds"] == ("gdn", "gdn", "gdn", "attention")
    assert flops_qwen3next.expected_held_pairs(config, mix) == 10_240
    assert flops_qwen3next.gmm_flops([10240], z) == 18 * 2048 * 512 * 10240
    weights = 3 * 32 * 2048 * 512
    assert flops_qwen3next.gmm_bytes([10240], z) == (
        5 * 10240 * 2048 * 2 + weights * 8)
    # every token multiplies: three gdn mixers, the attention mixer, four
    # routers + shared experts + gates, the norms, the head — not the
    # embedding table, not the held experts' stacks
    matrices = flops_qwen3next.matrix_params(config)
    assert matrices == (3 * 33_718_464 + 27_263_488 + 4 * 4_196_352
                        + 4 * 4096 + 2048 + 18992 * 2048)
    total = flops_qwen3next.train_flops_per_step(config, mix)
    routed = flops_qwen3next.gmm_flops([10240] * 4, z)
    attention = 12 * 4096.5 * 16 * 256 * 16384
    scan = flops_qwen3next.gdn_scan_flops(2, 8192, z)
    assert total == pytest.approx(
        6 * matrices * 16384 + routed + attention + scan, rel=1e-9)
    # 18.1 T of matrices, 3.3 T of attention, 0.85 T of scan, 0.77 T of
    # routed experts: 23.0 TFLOP a step
    assert total == pytest.approx(23.03e12, rel=2e-3)
    assert 0.03 < routed / total < 0.04
    least, bound = flops_qwen3next.gmm_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "memory"       # 320 rows an expert: the weights' bytes
    assert least == pytest.approx(
        4 * (5 * 10240 * 2048 * 2 + weights * 8) / 819e9, rel=1e-2)
    more, _ = flops_qwen3next.gmm_roofline_seconds(
        config, mix, "TPU v5 lite", [20480] * 4)
    assert least < more < 2 * least
    # one causal GQA 16/2 layer of D=256 at S=8192
    assert flops_qwen3next.flash_bytes(2, 8192, z, 1) == (
        6 * (16 + 2) * 256 * 2 * 16384)
    least, bound = flops_qwen3next.flash_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(
        attention / 197e12, rel=1e-6)


def test_the_tile_fill_reader_reads_the_runners_counter(cell):
    from chipbench.runners import train_gdn_moe

    loads = [{"layer_0": {"held_pairs": 320, "live_tiles": 2},
              "layer_1": {"held_pairs": 192, "live_tiles": 1}}]
    assert train_gdn_moe.tile_fill(loads) == 512 / (3 * 256)
    read = harness.layer_reader("qnext.gmm_tile_fill_pct")
    assert read({"moe_tile_fill": 0.625}) == 62.5
    assert read({"moe_tile_fill": None}) is None

