"""The SDAR-30B-A3B-Chat configuration, its cell, its arithmetic, its two
readers and the shared readers whose lists the cell joined — what
``test_layer_metrics.py``'s table-driven cases would check for this
family once a ``benchmark`` PR gives the cell its rows there (``REPORTS``,
``OWN_FILES``)."""

import gzip
import json
import math
import os

import pytest

from chipbench import flops, flops_sdar_moe, harness, sdar_reduce
from chipbench import trace_reduce, weights_sdar_moe

CELL = "sdar30b-train-1chip"
CONFIG = "sdar-30b-a3b-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SDAR = {"sdar.attn_mixer_ms", "sdar.mask_tile_fill_pct"}
#: The accepted entries whose ``workloads`` lists the cell joined: what
#: such a step has for them to read.
SHARED = {
    "step.mfu", "step.fwd_bwd_ms", "step.opt_update_ms", "kernel.flash_ms",
    "kernel.flash_roofline", "kernel.fused_ce_ms", "kernel.moe_gmm_ms",
    "kernel.moe_gmm_roofline", "moe.layer_ms", "moe.route_ms",
    "moe.dispatch_ms", "part.mixer_proj_ms", "part.norm_ms",
    "part.residual_ms", "part.embed_ms", "part.recompute_ms",
    "parts.unowned_pct", "parts.shared_pct", "device.idle.train",
    "trace.unattributed_pct"}
OWN_FILES = ("refs/sdar_moe.py", "weights_sdar_moe.py", "flops_sdar_moe.py",
             "traffic_bd.py")
DATA = os.path.join(harness.HERE, "data")


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_states_its_source_cut_and_deployment(cell):
    _, config, mix, limits = cell
    assert (config["num_experts"], config["vocab_size"],
            config["n_layer"], config["block_length"]) == (16, 18992, 6, 4)
    assert (config["num_experts_published"],
            config["experts_held_first"]) == (128, 0)
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["moe_intermediate_size"],
            config["num_experts_per_tok"]) == (48, 2048, 32, 4, 128, 768, 8)
    reduced = config["reduced"]
    assert set(reduced) == {"n_layer", "num_experts", "vocab_size"}
    assert reduced["n_layer"]["source_key"] == "num_hidden_layers"
    assert reduced["n_layer"]["source"] == config["num_hidden_layers"]
    assert reduced["num_experts"]["source"] == 128
    assert reduced["vocab_size"]["source"] == 8 * config["vocab_size"]
    for key, entry in reduced.items():
        assert entry["here"] == config[key] and entry["why"]
    entry = [c for c in harness.load_manifest()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == sorted(reduced)
    assert entry["source"] == config["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert "645.6 M = 10.33 GB" in entry["why"]
    for key in ("block_length", "noise_schedule", "noise_levels",
                "prediction", "normaliser", "mask_id", "mask", "qk_norm",
                "router", "rotary", "load_balancing_loss",
                "expert_placement", "weights", "optimizer", "remat",
                "documents"):
        assert config["assumed"][key], key
    assert config["optimizer"]["learning_rate"] == 1e-07
    assert config["precision"]["control"] == "fp8_e4m3"
    for words in ("64 chips", "8 pipeline stages of 6 layers",
                  "vocabulary-parallel", "1,024 rows", "16,384 rows",
                  "Nothing stands in"):
        assert words in config["deployment"], words
    assert (mix["kind"], mix["global_batch"], mix["seq_len"]) == (
        "train_bd_moe", 1, 8192)
    assert mix["token_dist"] == {"name": "zipf", "s": 1.0}
    assert mix["sampling_eps"] == 1e-3
    assert set(limits) == {"loss_rel_gap", "grad_norm_gap",
                           "delta_norm_gap", "attention_row_gap",
                           "set_from"}


def test_the_config_is_the_catalog_rows_where_the_catalog_is_here(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    _, config, _, _ = cell
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"][0]
    assert config["source"].startswith(row["source_url"])
    differing = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differing == {"num_experts", "vocab_size"}


def test_the_reckoning_is_the_built_models_count(cell):
    _, config, _, _ = cell
    shapes = weights_sdar_moe.shapes(config)
    r = config["reckoning"]

    def count(*prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert attention == r["attention"] == 18_874_368
    assert count("layer_0", "MultiHeadAttention_0") == (
        attention + r["qk_norm"])
    expert = 3 * 2048 * 768
    assert expert == r["routed_expert"] == 4_718_592
    assert r["held_experts_a_layer"] == 16 * expert
    assert count("layer_3", "ExpertLayer_0") == 16 * expert + r["router"]
    assert r["router"] == 2048 * 128 and r["layer_norms"] == 2 * 2048
    assert count("layer_0") == count("layer_5") == r["layer"] == 94_638_336
    assert r["layers"] == 6 * r["layer"]
    assert count("embed") == count("lm_head") == 18992 * 2048
    assert r["table_and_head"] == 2 * 18992 * 2048
    total = weights_sdar_moe.n_params(config)
    assert total == r["total"] == (
        r["layers"] + r["table_and_head"] + r["final_norm"])
    assert total == 645_623_296
    assert r["state_bytes"] == r["bytes_a_parameter"] * total
    assert 16 * total == pytest.approx(10.33e9, rel=1e-3)
    assert r["four_layers"]["total"] == 4 * r["layer"] + (
        r["table_and_head"] + r["final_norm"])


def test_the_program_builds_the_cells_table_from_the_file(cell):
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM
    from chipbench.runners import train_bd_moe

    _, config, _, _ = cell
    table = train_bd_moe.build_table(config)
    assert len(table.layers) == 6 and table.block_diffusion == 4
    assert len(set(table.layers)) == 1
    row = table.layers[0]
    assert (row.n_heads, row.n_kv_heads, row.d_head, row.rotary_dim,
            row.rope_theta, row.qk_norm, row.window) == (
        32, 4, 128, 128, 1e6, True, None)
    assert row.experts.experts_held == (0, 16)
    assert (row.experts.n_experts, row.experts.top_k,
            row.experts.d_expert, row.experts.d_shared) == (128, 8, 768, 0)
    lm = TransformerLM(vocab=config["vocab_size"],
                       d_model=config["hidden_size"], table=table)
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32),
        position_offset=jnp.tile(jnp.arange(8), 2)))["params"]
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == (
        weights_sdar_moe.n_params(config))


def test_the_cell_reports_its_two_end_to_end_metrics_its_two_and_the_shared():
    manifest = harness.load_manifest()
    names = {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "per_layer")}
    assert names == SDAR | SHARED
    e2e = {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "end_to_end")}
    assert e2e == {"train_step_ms", "setup_s"}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in SDAR:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_step_ms"
    for name in SHARED:       # appended: the last of an accepted list
        assert entries[name]["workloads"][-1] == CELL
        assert len(entries[name]["workloads"]) > 1
    assert {entries[n]["layer"] for n in SDAR} == {"Models", "Kernels"}
    assert len(manifest["per_layer"]) == 56
    assert len(manifest["workloads"]) == 9 and sum(
        w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_flops_family_finds_the_module_and_its_arithmetic(cell):
    _, config, mix, _ = cell
    assert flops.family(config) is flops_sdar_moe
    assert flops_sdar_moe.attended_pairs(8192, 4) == 67_141_632
    z = weights_sdar_moe.sizes(config)
    attention = flops_sdar_moe.flash_flops(mix, z)
    assert attention == 12.0 * 67_141_632 * 32 * 128 * 6
    total = flops_sdar_moe.train_flops_per_step(config, mix)
    assert attention / total > 0.5           # over half of the needed FLOPs
    assert total == pytest.approx(35.79e12, rel=1e-3)
    through, head = flops_sdar_moe.matrix_params(config)
    assert head == 18992 * 2048
    assert through == 6 * (18_874_368 + 256 + 262_144 + 4096) + 2048
    assert flops_sdar_moe.expected_held_pairs(config, mix) == 16384
    least, bound = flops_sdar_moe.flash_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(
        attention / 197e12, rel=1e-6)
    least, bound = flops_sdar_moe.gmm_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert least > 0 and bound in ("compute", "memory")


@pytest.mark.parametrize("name", OWN_FILES)
def test_the_familys_files_import_nothing_of_the_program(name):
    with open(os.path.join(harness.HERE, name)) as f:
        text = f.read()
    assert "import chainermn_tpu" not in text
    assert "from chainermn_tpu" not in text


def test_the_readers_return_nothing_without_a_trace(cell):
    _, config, mix, _ = cell
    ctx = {"config": config, "mix": mix, "device_kind": "TPU v5 lite",
           "devices": [None], "trace_steps": 4, "trace": None}
    for name in sorted(SDAR):
        assert harness.layer_reader(name)(ctx) is None, name


# ------------------------------------- the readers on a recorded capture

def recorded():
    """The readers' context on ``chipbench/data/tiny_bd_moe.*``, recorded
    on the chip by ``tools/record_bd_moe_trace.py`` (this file's own few
    lines: ``captures.py`` rebuilds the accepted cells' from
    ``record_trace.KINDS``, which this kind is not in)."""
    device_trace = pytest.importorskip(
        "chainermn_tpu.observability.device_trace")
    from chipbench.tools import record_bd_moe_trace, record_trace

    name = record_bd_moe_trace.NAME
    kinds, record_trace.KINDS = record_trace.KINDS, (
        record_bd_moe_trace.kinds(record_trace))
    try:
        _, config, mix, _ = record_trace.context(name)
    finally:
        record_trace.KINDS = kinds
    trace = trace_reduce.TraceData.from_file(
        os.path.join(DATA, name + ".xplane.pb.gz"), n_devices=1)
    with gzip.open(os.path.join(DATA, name + ".hlo.txt.gz"), "rt") as f:
        table = device_trace.scope_table(f.read())
    return {"trace": trace, "trace_steps": record_trace.STEPS,
            "scope_table": table, "config": config, "mix": mix,
            "devices": [None], "device_kind": "TPU v5 lite"}


@pytest.fixture(scope="module")
def ctx():
    if not os.path.exists(os.path.join(DATA, "tiny_bd_moe.hlo.txt.gz")):
        pytest.skip("no capture recorded yet")
    return recorded()


def test_the_two_readers_read_a_number_in_range_on_the_capture(ctx):
    from chipbench import scope_reduce

    phase_ms = scope_reduce.phase_ms(ctx, "fwd-bwd")
    mixer = harness.layer_reader("sdar.attn_mixer_ms")(ctx)
    # every flash call of such a step is under the scope: the shared
    # reader's three regions are the scope's (the region reading and the
    # owner reading under the scope part by a cast or two at the edge)
    flash = harness.layer_reader("kernel.flash_ms")(ctx)
    assert 0 < flash < mixer < phase_ms
    assert flash == pytest.approx(sum(
        sdar_reduce.within_ms(ctx, region) or 0.0
        for region in sdar_reduce.FLASH), rel=1e-2)
    # 512 tokens a copy at tiles of 256: 2 + 1 clean tiles and, for the
    # noisy copy, 1 + 2 clean and 2 diagonal: 8 live tiles of 16, every
    # one visited and none besides; L (L + B) pairs over their area
    fill = harness.layer_reader("sdar.mask_tile_fill_pct")(ctx)
    tiles = ctx["notes"]["blockdiff_tiles"]
    assert all(t["live"] == t["visited"] == 8 for t in tiles)
    assert fill == pytest.approx(100.0 * 512 * 516 / (8 * 256 * 256))


def test_the_readers_need_the_scope(ctx):
    """A program without ``attn-blockdiff`` (the parent's) reads nothing,
    and does not raise."""
    bare = dict(ctx, scope_table=None)
    assert sdar_reduce.mask_tile_fill_pct(bare) is None


@pytest.mark.parametrize("name", sorted(SHARED))
def test_the_shared_readers_read_this_kind_of_step(ctx, name):
    """The cell joined these accepted entries' lists by a data edit: the
    shared readers find their regions, and ``flops_sdar_moe.py`` has the
    functions they ask the family's module for (``kernel.flash_roofline``
    the MASK's pairs, not the triangle's)."""
    value = harness.layer_reader(name)(dict(ctx, moe_held_pairs=None))
    assert value is not None and value >= 0
    if name.endswith("_roofline") or name == "step.mfu":
        assert 0 < value < 100
