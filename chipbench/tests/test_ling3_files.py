"""The Ling-3.0-flash configuration, its cell and its arithmetic."""

import json
import math
import os

import pytest

from chipbench import flops_ling3, harness, weights_ling3
from chipbench.tests import captures

CELL = "ling3flash-train-1chip"
CONFIG = "ling3flash-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_states_its_source_cut_and_deployment(cell):
    _, config, _, _ = cell
    assert (config["num_experts"], config["vocab_size"],
            config["n_layer"]) == (8, 19648, 6)
    assert (config["num_experts_published"],
            config["experts_held_first"]) == (512, 0)
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["n_group"], config["topk_group"]) == (42, 2560, 8, 4)
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
    for key in ("kda_gate", "kda_layout", "kda_norm_and_gate",
                "delta_rule_chunk", "mla", "mla_qk_norm", "router",
                "expert_bias", "experts", "dense_ffn",
                "multi_token_prediction", "auxiliary_loss", "ignored_keys",
                "weights", "optimizer"):
        assert config["assumed"][key]
    assert config["optimizer"]["learning_rate"] == 1e-06
    assert config["balancing"]["rate"] == 0.05
    assert config["precision"]["control"] == "fp8_e4m3"
    for key in ("router", "delta_rule", "attention", "experts"):
        assert config["precision"][key]
    for words in ("448 chips", "7 pipeline stages", "64 chips",
                  "vocabulary-parallel", "256 tokens", "Nothing stands in"):
        assert words in config["deployment"]


def test_the_config_is_the_catalog_rows_where_the_catalog_is_here(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    _, config, _, _ = cell
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Ling-3.0-flash"][0]
    assert config["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differing == {"num_experts", "vocab_size"}


def test_the_reckoning_is_the_built_models_count(cell):
    _, config, _, _ = cell
    shapes = weights_ling3.shapes(config)
    r = config["reckoning"]

    def count(*prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    kda = (3 * 2560 * 4096 + 3 * 4 * 4096 + 2560 * 4096 + 32 + 4096
           + 2 * 2560 * 32 + 128 + 4096 * 2560)
    assert kda == r["kda_mixer"] == 52_646_048
    assert count("layer_0", "KDAMixer_0") == kda
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256 + 2560 * 32
           + 2 * 128 + 4096 * 2560)
    assert mla == r["mla_mixer"] == 31_965_952
    assert count("layer_5", "MLAMixer_0") == mla
    assert r["dense_swiglu"] == 3 * 2560 * 6144 == count(
        "layer_1", "GatedFeedForward_0")
    expert = 3 * 2560 * 768
    assert expert == r["routed_expert"] == 5_898_240
    assert r["router_bias_shared_expert"] == 2560 * 512 + 512 + expert
    assert count("layer_2", "ExpertLayer_0") == (
        8 * expert + r["router_bias_shared_expert"])
    assert r["held_experts_a_layer"] == 8 * expert
    assert r["layer_norms"] == 2 * 2560
    assert count("layer_0") == count("layer_1") == r["kda_dense_layer"]
    assert count("layer_2") == count("layer_4") == r["kda_sparse_layer"]
    assert count("layer_5") == r["mla_sparse_layer"]
    assert r["period"] == sum(count(f"layer_{i}") for i in range(6))
    assert count("embed") == count("lm_head") == 19648 * 2560
    assert r["table_and_head"] == 2 * 19648 * 2560
    total = weights_ling3.n_params(config)
    assert total == r["total"] == (
        r["period"] + r["table_and_head"] + r["final_norm"])
    assert total == 707_780_640
    assert r["state_bytes"] == r["bytes_a_parameter"] * total
    assert 16 * total == pytest.approx(11.32e9, rel=1e-3)
    assert [m for m, _ in weights_ling3.kinds(config)] == (
        ["kda"] * 5 + ["mla"])
    assert [f for _, f in weights_ling3.kinds(config)] == (
        ["dense"] * 2 + ["sparse"] * 4)


def test_the_program_builds_the_cells_table_from_the_file(cell):
    """The file's published keys through the runner's own ``build_table``
    (what the chip run does), and the built model's parameter count."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM
    from chipbench import weights
    from chipbench.runners.train_kda_mla_moe import build_table

    _, config, _, _ = cell
    table = build_table(config)
    assert [r.mixer for r in table.layers] == ["kda"] * 5 + ["attention"]
    assert table.layers[5].mla.d_qk == 192 and table.layers[5].mla.d_v == 128
    assert table.layers[2].experts.held == (0, 8)
    model = TransformerLM(vocab=config["vocab_size"],
                          d_model=config["hidden_size"], table=table)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]
    built = {p: v.shape for p, v in weights.flatten(shapes).items()}
    assert built == weights_ling3.shapes(config)
    assert sum(math.prod(s) for s in built.values()) == config[
        "reckoning"]["total"]


def test_cell_traffic_and_limits(cell):
    entry, config, mix, limits = cell
    assert entry["chips"] == 1 and entry["traffic"] == "kdamla16k-b1"
    assert entry["config"] == CONFIG
    assert (mix["kind"], mix["global_batch"], mix["seq_len"]) == (
        "train_kda_mla_moe", 1, 16384)
    assert (mix["reference_steps"], mix["dispatch_ahead"],
            mix["trace_steps"]) == (2, 2, 4)
    assert mix["token_dist"] == {"name": "zipf", "s": 1.0}
    assert {"loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
            "router_pair_diff_share", "set_from"} <= set(limits)
    assert "PROVISIONAL" not in limits["set_from"]
    assert "8,192 rows" in config["program"]["moe_rows_bound_note"]


def test_rows_bound_of_the_cells_shape():
    from chainermn_tpu.parallel import moe_dropless as moe

    rows = moe.rows_bound(16384 * 8, 8, 512)
    assert rows == 8_192
    assert moe.buffer_tiles(rows, 8) == 8_192 // 256 + 8


def test_flop_and_byte_arithmetic_against_a_hand_count(cell):
    _, config, mix, _ = cell
    z = flops_ling3.sizes(config)
    assert (z["kda_layers"], z["mla_layers"], z["sparse_layers"]) == (5, 1, 4)
    assert (z["d_qk"], z["d_value"], z["heads"]) == (192, 128, 32)
    # one chunk of one head forward, product by product at C = 64, d = 128
    kk = qk = 2 * 64 * 64 * 128
    solve_applied = 2 * 64 * 64 * (128 + 128)
    local = 2 * 64 * 64 * 128
    with_state = 3 * 2 * 64 * 128 * 128
    chunk = kk + qk + solve_applied + local + with_state
    assert chunk == 11_534_336
    assert flops_ling3.kda_scan_flops_per_chunk_head(128, 128) == chunk
    scan = flops_ling3.kda_scan_flops(1, 16384, z)
    assert scan == 3 * chunk * 256 * 32 * 5                  # 1.42 TFLOP
    # a token and head: q, k, v, o, do, dq, dk, dv in bfloat16, g and dg
    # float32 a channel (three passes), beta and dbeta float32
    per = (3 * 2 * 128 * 2 + 5 * 128 * 2) + 3 * 128 * 4 + 3 * 4
    assert flops_ling3.kda_scan_bytes(1, 16384, z) == per * 32 * 16384 * 5
    least, bound = flops_ling3.kda_scan_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "memory"      # 11.4 GB at 819 GB/s against 7.2 ms
    assert least == pytest.approx(per * 32 * 16384 * 5 / 819e9, rel=1e-9)
    pairs = 16384 * 16385 // 2
    flash = flops_ling3.flash_flops(1, 16384, z)
    assert flash == 6 * pairs * (192 + 128) * 32             # 8.25 TFLOP
    assert flops_ling3.flash_bytes(1, 16384, z) == (
        6 * (192 + 128) * 32 * 2 * 16384)
    least, bound = flops_ling3.flash_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute"
    assert least == pytest.approx(flash / 197e12, rel=1e-9)
    assert flops_ling3.expected_held_pairs(config, mix) == 2_048
    held = 3 * 8 * 2560 * 768
    assert flops_ling3.gmm_flops([2048], z) == 18 * 2560 * 768 * 2048
    assert flops_ling3.gmm_bytes([2048], z) == (
        5 * 2048 * 2560 * 2 + held * 8)
    assert flops_ling3.gmm_roofline_seconds(
        config, mix, "TPU v5 lite")[1] == "memory"   # 256 rows an expert
    # every token multiplies: the mixers, the dense FFNs, the routers and
    # shared experts, the norms, the head — not the table, not the held
    # experts
    matrices = flops_ling3.matrix_params(config)
    assert matrices == (5 * 52_646_048 + 31_965_952 + 2 * 47_185_920
                        + 4 * 7_209_472 + 6 * 5120 + 2560 + 19648 * 2560)
    total = flops_ling3.train_flops_per_step(config, mix)
    assert total == pytest.approx(
        6 * matrices * 16384 + 4 * 18 * 2560 * 768 * 2048 + flash + scan,
        rel=1e-12)
    assert total == pytest.approx(56.03e12, rel=1e-3)


def test_readers_return_nothing_on_a_program_without_the_regions(cell):
    """A parent commit's step has no ``kda-scan`` and no ``mla-mixer``,
    and its attribution may carry no owner reading: the readers say
    nothing and do not raise."""
    _, config, mix, _ = cell
    row = {"region": {"flash-fwd": 1.0}, "phase": {}, "busy": 1.0}
    ctx = {"config": config, "mix": mix, "device_kind": "TPU v5 lite",
           "trace_steps": 4, "scope_table": {},
           "_scope_reduce": {"all": [row], "no_exchange": [row]}}
    for name in ("ling.kda_mixer_ms", "ling.kda_scan_ms",
                 "ling.kda_scan_roofline", "kernel.ssm_conv_ms",
                 "kernel.moe_gmm_roofline", "part.ffn_ms",
                 "step.fwd_bwd_ms"):
        assert harness.layer_reader(name)(ctx) is None


# ------------------------------------- the readers on a recorded capture

@pytest.fixture(scope="module")
def recorded():
    return captures.recorded(CELL)


def test_the_mixers_nest_on_the_recorded_capture(recorded):
    """The scan and the convolution are inside the KDA mixers' time, the
    flash kernels inside the latent row's."""
    ctx = dict(recorded)
    read = lambda n: harness.layer_reader(n)(ctx)  # noqa: E731
    assert read("ling.kda_scan_ms") + read("kernel.ssm_conv_ms") < read(
        "ling.kda_mixer_ms")
    assert read("kernel.flash_ms") < read("ling.mla_mixer_ms")
    assert (read("moe.route_ms") + read("moe.dispatch_ms")
            + read("kernel.moe_gmm_ms")) < read("moe.layer_ms")

