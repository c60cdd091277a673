"""The Mellum2-12B-A2.5B configuration, its cell and its arithmetic."""

import json
import math
import os

import pytest

from chipbench import (flops_mellum2, harness, mellum_reduce, scope_reduce,
                       weights_mellum2)
from chipbench.tests import captures

CELL = "mellum2-train-1chip"
CONFIG = "mellum2-12b-a2.5b-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: The source's ``config.json`` as the model catalog carries it
#: (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct), without
#: the two keys the cut changes.
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "use_sliding_window": True,
}


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_holds_every_published_key_unchanged(cell):
    _, config, _, _ = cell
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert (config["num_experts"], config["vocab_size"],
            config["n_layer"]) == (8, 12288, 4)
    assert (config["num_experts_published"],
            config["experts_held_first"]) == (64, 0)
    reduced = config["reduced"]
    assert set(reduced) == {"n_layer", "num_experts", "vocab_size"}
    assert reduced["n_layer"]["source_key"] == "num_hidden_layers"
    assert reduced["n_layer"]["source"] == config["num_hidden_layers"]
    assert reduced["num_experts"]["source"] == 64
    assert reduced["vocab_size"]["source"] == 8 * config["vocab_size"]
    for key, entry in reduced.items():
        assert entry["here"] == config[key] and entry["why"]
    entry = [c for c in harness.load_manifest()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == sorted(reduced)
    assert entry["source"] == config["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    for key in ("qk_norm", "router", "rotary", "yarn_truncate", "window",
                "multi_token_prediction", "load_balancing_loss", "weights",
                "expert_placement", "optimizer", "remat", "documents"):
        assert config["assumed"][key]
    assert config["optimizer"]["learning_rate"] == 1e-07
    assert config["precision"]["control"] == "fp8_e4m3"
    for key in ("router", "attention", "experts"):
        assert config["precision"][key]
    assert "not measured yet" not in json.dumps(config)
    for words in ("56 chips", "7 pipeline stages", "vocabulary-parallel",
                  "2,048 tokens", "Attention is whole"):
        assert words in config["deployment"]


def test_the_config_is_the_catalog_rows_where_the_catalog_is_here(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    _, config, _, _ = cell
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct"][0]
    assert config["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differing == {"num_experts", "vocab_size"}


def test_the_cut_counts_what_the_issue_counted(cell):
    _, config, _, _ = cell
    shapes = weights_mellum2.shapes(config)
    r = config["reckoning"]

    def count(*prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    # q 2304 -> 32 x 128, k and v 2304 -> 4 x 128, 4096 -> 2304, and the
    # two QK-norms' 128 each
    att = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert att == r["attention"] == 21_233_664
    assert count("layer_0", "MultiHeadAttention_0") == att + r["qk_norm"]
    assert r["qk_norm"] == 256
    expert = 3 * 2304 * 896
    assert expert == r["routed_expert"] == 6_193_152
    assert r["router"] == 2304 * 64 == 147_456
    assert count("layer_0", "ExpertLayer_0") == 8 * expert + r["router"]
    assert r["held_experts_a_layer"] == 8 * expert
    assert r["layer_norms"] == 2 * 2304
    for i in range(4):      # a sliding row and the full row hold the same
        assert count(f"layer_{i}") == r["layer"] == 70_931_200
    assert r["period"] == 4 * r["layer"] == 283_724_800
    assert count("embed") == count("lm_head") == 12288 * 2304
    assert r["table_and_head"] == 2 * 12288 * 2304 == 56_623_104
    total = weights_mellum2.n_params(config)
    assert total == r["total"] == (
        r["period"] + r["table_and_head"] + r["final_norm"])
    assert total == 340_350_208
    assert r["state_bytes"] == r["bytes_a_parameter"] * total
    assert 0.25 * 16e9 < 16 * total < 0.70 * 16e9            # 5.45 GB
    # what the builder read of the step's memory: at least a quarter of
    # the chip
    assert r["runtime_peak"]["memory_peak_bytes"] >= 0.25 * 16e9
    assert r["compiled_step"]["argument_bytes"] > 12 * total
    assert not any(part in ("router_bias", "shared")
                   for path in shapes for part in path)


def test_cell_traffic_and_limits(cell):
    entry, config, mix, limits = cell
    assert entry["chips"] == 1 and entry["traffic"] == "swamoe16k-b1"
    assert entry["config"] == CONFIG
    assert (mix["kind"], mix["global_batch"], mix["seq_len"]) == (
        "train_swa_moe", 1, 16384)
    assert (mix["reference_steps"], mix["dispatch_ahead"],
            mix["trace_steps"]) == (2, 2, 4)
    assert mix["token_dist"] == {"name": "zipf", "s": 1.0}
    assert {"loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
            "router_pair_diff_share", "set_from"} <= set(limits)
    assert "PROVISIONAL" not in limits["set_from"]
    assert "65,536" in config["program"]["moe_rows_bound_note"]


def test_rows_bound_and_tiles_of_the_cells_shape():
    from chainermn_tpu.parallel import moe_dropless as moe

    rows = moe.rows_bound(16384 * 8, 8, 64)
    assert rows == 65_536
    assert moe.buffer_tiles(rows, 8) == 65_536 // 256 + 8
    # a quarter of the experts held: the buffer is laid out for every pair
    assert moe.rows_bound(16384 * 8, 16, 64) == 131_072


@pytest.mark.parametrize("S,W", [(64, 16), (64, 17), (16384, 1024), (40, 64),
                                 (33, 1), (128, 128)])
def test_the_bands_pair_count_against_a_brute_force_count(S, W):
    brute = sum(1 for q in range(min(S, 512)) for k in range(min(S, 512))
                if 0 <= q - k < W)
    if S <= 512:
        assert flops_mellum2.attended_pairs(S, W) == brute
        assert flops_mellum2.attended_pairs(S) == S * (S + 1) // 2
    else:       # the cell's own: the closed form from its 512 first rows on
        assert flops_mellum2.attended_pairs(512, W) == brute
        assert flops_mellum2.attended_pairs(S, W) == 16_253_440
        assert flops_mellum2.attended_pairs(S) == 134_225_920


def test_flop_and_byte_arithmetic(cell):
    _, config, mix, _ = cell
    z = weights_mellum2.sizes(config)
    assert z["kinds"] == ("sliding_attention",) * 3 + ("full_attention",)
    assert (z["window"], z["heads"], z["kv_heads"], z["d_head"]) == (
        1024, 32, 4, 128)
    assert flops_mellum2.expected_held_pairs(config, mix) == 16_384
    held = 3 * 8 * 2304 * 896
    assert flops_mellum2.gmm_flops([16384], z) == 18 * 2304 * 896 * 16384
    assert flops_mellum2.gmm_bytes([16384], z) == (
        5 * 16384 * 2304 * 2 + held * 8)
    # every token multiplies: four attention mixers, four routers, the
    # norms, the head — not the embedding table, not the held experts
    matrices = flops_mellum2.matrix_params(config)
    assert matrices == (4 * (21_233_664 + 256 + 147_456 + 4608) + 2304
                        + 12288 * 2304)
    sliding = flops_mellum2.flash_flops(1, 16384, z, "sliding_attention")
    full = flops_mellum2.flash_flops(1, 16384, z, "full_attention")
    assert sliding == 3 * 12 * 16_253_440 * 4096      # 0.80 TF a row
    assert full == 12 * 134_225_920 * 4096            # 6.60 TF
    routed = flops_mellum2.gmm_flops([16384] * 4, z)
    total = flops_mellum2.train_flops_per_step(config, mix)
    assert total == pytest.approx(
        6 * matrices * 16384 + routed + sliding + full, rel=1e-12)
    # 11.2 T of matrices, 9.0 T of attention (6.6 of it the one full
    # row), 2.4 T of routed experts: 22.6 TFLOP a step
    assert total == pytest.approx(22.62e12, rel=1e-3)
    least, bound = flops_mellum2.gmm_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute"      # 2,048 rows an expert: the matrix unit
    assert least == pytest.approx(routed / 197e12, rel=1e-9)
    # a window moves no fewer bytes: every q, k, v of a row is read
    for kind, flops_ in (("sliding_attention", sliding),
                         ("full_attention", full)):
        least, bound = flops_mellum2.flash_roofline_seconds(
            config, mix, "TPU v5 lite", kind)
        assert bound == "compute"
        assert least == pytest.approx(flops_ / 197e12, rel=1e-9)
    assert flops_mellum2.flash_bytes(1, 16384, z, 1) == (
        6 * (32 + 4) * 128 * 2 * 16384)


def test_readers_return_nothing_on_a_program_without_the_reading(cell):
    """A parent commit's attribution has no ``within`` and its scope table
    no ``tiles_within``: the readers say nothing and do not raise."""
    _, config, mix, _ = cell
    row = {"owner": {"attn-mixer": 1.0}, "busy": 1.0}
    ctx = {"config": config, "mix": mix, "device_kind": "TPU v5 lite",
           "trace_steps": 4, "scope_table": {},
           "_scope_reduce": {"all": [row], "no_exchange": [row]}}
    assert mellum_reduce.within_ms(ctx, "attn-window") is None
    assert mellum_reduce.flash_roofline_pct(ctx, "full_attention") is None
    assert mellum_reduce.window_tile_fill_pct(ctx) is None


# ------------------------------------- the readers on a recorded capture

@pytest.fixture(scope="module")
def recorded():
    return captures.recorded(CELL)


def test_the_row_kinds_add_up_on_the_recorded_capture(recorded):
    """A window of 256 at tiles of 256 over 1,024 tokens: 7 live tiles of
    16, 229,504 pairs in 7 x 65,536; and the two row kinds' flash times
    are the flash regions' whole time."""
    ctx = dict(recorded)
    window = mellum_reduce.within_ms(ctx, "attn-window", *mellum_reduce.FLASH)
    full = mellum_reduce.within_ms(ctx, "attn-mixer", *mellum_reduce.FLASH)
    assert window + full == pytest.approx(
        scope_reduce.region_ms(ctx, *mellum_reduce.FLASH), rel=1e-6)
    assert mellum_reduce.within_ms(ctx, "attn-window") > window
    fill = mellum_reduce.window_tile_fill_pct(ctx)
    assert flops_mellum2.attended_pairs(1024, 256) == 229_504
    assert fill == pytest.approx(100 * 229_504 / (7 * 65_536))
    tiles = ctx["notes"]["window_tiles"]
    assert len(tiles) == 3 and all(
        (t["live"], t["visited"]) == (7, 16) for t in tiles)


# ------------------------------------------- the placement and the row

@pytest.mark.parametrize("loads,ranks", [
    ([9, 1, 1, 1, 8, 2, 2, 2], 2), ([5] * 16, 4),
    ([100, 90, 1, 1, 1, 1, 1, 1, 50, 50, 40, 40], 3)])
def test_place_experts_evens_the_ranks(loads, ranks):
    order = weights_mellum2.place_experts(loads, ranks)
    assert sorted(order) == list(range(len(loads)))      # a renaming
    room = len(loads) // ranks
    totals = [sum(loads[e] for e in order[r * room:(r + 1) * room])
              for r in range(ranks)]
    by_index = [sum(loads[r * room:(r + 1) * room]) for r in range(ranks)]
    assert max(totals) - min(totals) <= max(by_index) - min(by_index)
    assert max(totals) - min(totals) <= max(loads)
    assert list(order) == list(weights_mellum2.place_experts(loads, ranks))


def test_placement_renames_experts_and_evens_this_ranks_load():
    import jax
    import numpy as np

    from chipbench import traffic
    from chipbench.refs import mellum2 as reference
    from chipbench.tests import tiny_swa_moe

    config, mix = tiny_swa_moe.CONFIG, tiny_swa_moe.MIX
    E, held = config["num_experts_published"], config["num_experts"]
    first, k = config["experts_held_first"], config["num_experts_per_tok"]
    tokens, _ = traffic.train_batches(mix, config["vocab_size"], 11)(0)
    params = weights_mellum2.make(config, 11)
    order = weights_mellum2.placement(params, tokens, config)
    assert sorted(order) == [f"layer_{i}" for i in range(4)]
    placed = weights_mellum2.with_placement(params, order)
    for name, perm in order.items():
        assert sorted(perm) == list(range(E))
        old, new = (p[name]["ExpertLayer_0"] for p in (params, placed))
        np.testing.assert_array_equal(
            new["router"], np.asarray(old["router"])[:, perm])
        assert all(new[key] is old[key] for key in old if key != "router")
    assert placed["embed"] is params["embed"]
    # under the placement every rank's load is within one expert's of the
    # others', layer by layer, each layer fed by the placed layers before
    chosen = jax.device_get(reference.chosen_experts(
        placed, jax.numpy.asarray(tokens), config))
    for name, mask in chosen.items():
        loads = mask.reshape(-1, E).sum(axis=0)
        ranks = loads.reshape(E // held, held).sum(axis=1)
        assert ranks.sum() == tokens.size * k
        assert ranks.max() - ranks.min() <= loads.max(), name
        assert abs(int(ranks[first // held]) - tokens.size * k * held / E) \
            <= loads.max(), name


def test_the_references_row_is_rounded_as_the_programs_and_its_gradient_not():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.refs import mellum2 as reference

    table = jnp.asarray([[1.001, -0.3337], [3.14159, 2.5]], jnp.float32)
    params = {"embed": {"embedding": table}}
    tokens = jnp.asarray([[1, 0, 1]])
    plain = reference.embed(params, tokens)
    np.testing.assert_array_equal(plain, table[tokens])
    config = {"precision": {"compute": "bfloat16"}}
    rows = reference.embed(params, tokens, config)
    np.testing.assert_array_equal(
        rows, table.astype(jnp.bfloat16).astype(jnp.float32)[tokens])
    assert float(jnp.abs(rows - plain).max()) > 0
    weight = jnp.asarray([[[0.1234567, 1.0], [1.0, 1.0], [2.0, 1.0]]])
    grad = jax.grad(lambda p: jnp.sum(
        weight * reference.embed(p, tokens, config)))(params)
    np.testing.assert_array_equal(
        grad["embed"]["embedding"],
        jnp.asarray([[1.0, 1.0], [2.1234567, 2.0]], jnp.float32))
