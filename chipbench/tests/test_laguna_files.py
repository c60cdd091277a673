"""The Laguna-S-2.1 configuration, its cell, its arithmetic, its seven
readers and the shared readers whose lists the cell joined — what
``test_layer_metrics.py``'s table-driven cases would check for this
family once a ``benchmark`` PR gives the cell its rows there (``REPORTS``,
``OWN_FILES``)."""

import gzip
import json
import math
import os

import pytest

from chipbench import flops, flops_laguna, harness, laguna_reduce
from chipbench import trace_reduce, weights_laguna

CELL = "laguna-train-1chip"
CONFIG = "laguna-s-2.1-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LAGUNA = {
    "laguna.attn_window_ms", "laguna.attn_full_ms",
    "laguna.flash_window_ms", "laguna.flash_window_roofline",
    "laguna.flash_full_roofline", "laguna.head_gate_ms",
    "laguna.window_tile_fill_pct"}
#: The accepted entries whose ``workloads`` lists the cell joined: what
#: such a step has for them to read.
SHARED = {
    "step.mfu", "step.fwd_bwd_ms", "step.opt_update_ms", "kernel.flash_ms",
    "kernel.flash_roofline", "kernel.fused_ce_ms", "kernel.moe_gmm_ms",
    "kernel.moe_gmm_roofline", "qnext.gmm_tile_fill_pct", "moe.layer_ms",
    "moe.route_ms",
    "moe.dispatch_ms", "moe.shared_ms", "part.ffn_ms",
    "part.mixer_proj_ms", "part.mixer_gate_ms", "part.norm_ms",
    "part.residual_ms", "part.embed_ms", "part.recompute_ms",
    "parts.unowned_pct", "parts.shared_pct", "device.idle.train",
    "trace.unattributed_pct"}
SETUP = {"setup.import_s", "setup.backend_s", "setup.build_s",
         "setup.first_call_s", "setup.first_steps_s",
         "setup.trace_lower_s", "setup.compile_s", "setup.cache_misses"}
OWN_FILES = ("refs/laguna.py", "weights_laguna.py", "flops_laguna.py",
             "laguna_reduce.py")
DATA = os.path.join(harness.HERE, "data")


@pytest.fixture(scope="module")
def cell():
    return harness.find_cell(harness.load_manifest(), CELL)


def test_config_states_its_source_cut_and_deployment(cell):
    _, config, mix, limits = cell
    assert (config["num_experts"], config["vocab_size"],
            config["n_layer"]) == (8, 12544, 5)
    assert (config["num_experts_published"], config["experts_held_first"],
            config["vocab_size_published"]) == (256, 0, 100352)
    # every published width, unchanged
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["num_key_value_heads"], config["head_dim"],
            config["sliding_window"], config["intermediate_size"],
            config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"],
            config["moe_routed_scaling_factor"]) == (
        48, 3072, 8, 128, 512, 12288, 1024, 1024, 10, 2.5)
    assert config["num_attention_heads_per_layer"][:5] == [
        48, 72, 72, 72, 48]
    assert config["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert config["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert config["rope_parameters"]["full_attention"][
        "partial_rotary_factor"] == 0.5
    reduced = config["reduced"]
    assert set(reduced) == {"n_layer", "num_experts", "vocab_size"}
    assert reduced["n_layer"]["source_key"] == "num_hidden_layers"
    assert reduced["n_layer"]["source"] == config["num_hidden_layers"]
    assert reduced["num_experts"]["source"] == 256
    assert reduced["vocab_size"]["source"] == 8 * config["vocab_size"]
    for key, entry in reduced.items():
        assert entry["here"] == config[key] and entry["why"]
    entry = [c for c in harness.load_manifest()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == sorted(reduced)
    assert entry["source"] == config["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert "811 M, 12.98 GB" in entry["why"]
    for key in ("gate", "router", "shared_expert", "qk_norm", "rotary",
                "yarn_truncate", "window", "load_balancing_loss",
                "expert_placement", "weights", "optimizer", "remat",
                "documents", "attention_rows_compared"):
        assert config["assumed"][key], key
    assert config["attention_rows_compared"] == ["layer_1", "layer_4"]
    assert config["optimizer"]["learning_rate"] == 1e-07
    assert config["precision"]["control"] == "fp8_e4m3"
    for words in ("32 chips", "8 a chip", "vocabulary-parallel",
                  "12,544 rows", "four layers", "Nothing stands in",
                  "811,017,216 parameters x 16 B"):
        assert words in config["deployment"], words
    assert (mix["kind"], mix["global_batch"], mix["reference_steps"],
            mix["dispatch_ahead"]) == ("train_gswa_moe", 1, 2, 2)
    assert mix["token_dist"] == {"name": "zipf", "s": 1.0}
    # seq_len by the issue's rule, from the compiled step's bytes
    step = config["reckoning"]["compiled_step"]
    total = (step["argument_bytes"] + step["temporary_bytes"]
             + step["code_bytes"])
    assert mix["seq_len"] == step["seq_len"] == (
        8192 if total <= 15.0e9 else 4096)
    assert set(limits) == {"loss_rel_gap", "grad_norm_gap",
                           "delta_norm_gap", "router_pair_diff_share",
                           "attention_row_gap", "set_from"}


def test_the_config_is_the_catalog_rows_where_the_catalog_is_here(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    _, config, _, _ = cell
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Laguna-S-2.1"][0]
    assert config["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differing == {"num_experts", "vocab_size"}


def test_the_reckoning_is_the_built_models_count(cell):
    _, config, _, _ = cell
    shapes = weights_laguna.shapes(config)
    r = config["reckoning"]

    def count(*prefix):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p[:len(prefix)] == prefix)

    att = "MultiHeadAttention_0"
    assert count("layer_0", att) == r["attention_full_row"] == 44_187_648
    assert count("layer_4", att) == r["attention_full_row"]
    assert count("layer_1", att) == r["attention_sliding_row"] == 63_135_744
    assert count("layer_0", "GatedFeedForward_0") == r["dense_ffn"] == (
        113_246_208)
    assert r["routed_expert"] == r["shared_expert"] == 9_437_184
    assert count("layer_2", "ExpertLayer_0") == (
        9 * r["routed_expert"] + r["router"] + r["router_correction_bias"])
    assert count("layer_0") == r["layer_0"] == 157_440_000
    assert count("layer_1") == count("layer_3") == r[
        "sliding_sparse_layer"] == 148_862_976 + 256
    assert count("layer_4") == r["layer_4"] == 129_914_880 + 256
    assert count("embed") == count("lm_head") == 12544 * 3072
    total = weights_laguna.n_params(config)
    assert total == r["total"] == r["issue_total"] + 4 * 256
    assert r["issue_total"] == 811_017_216
    assert r["state_bytes"] == r["bytes_a_parameter"] * total
    assert 16 * total == pytest.approx(12.98e9, rel=1e-3)


def test_the_program_builds_the_cells_table_from_the_file(cell):
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM
    from chipbench.runners import train_gswa_moe

    _, config, _, _ = cell
    table = train_gswa_moe.build_table(config)
    assert [(r.n_heads, r.window, r.rotary_dim, r.ffn)
            for r in table.layers] == [
        (48, None, 64, "swiglu"), (72, 512, 128, "experts"),
        (72, 512, 128, "experts"), (72, 512, 128, "experts"),
        (48, None, 64, "experts")]
    assert all(r.head_gate and r.n_kv_heads == 8 and r.d_head == 128
               and not r.qk_norm for r in table.layers)
    row = table.layers[1]
    assert row.experts.experts_held == (0, 8)
    assert (row.experts.n_experts, row.experts.top_k, row.experts.d_expert,
            row.experts.d_shared, row.experts.scaling) == (
        256, 10, 1024, 1024, 2.5)
    lm = TransformerLM(vocab=config["vocab_size"],
                       d_model=config["hidden_size"], table=table)
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == (
        weights_laguna.n_params(config))


def test_the_cell_reports_its_end_to_end_metrics_its_seven_and_the_shared():
    manifest = harness.load_manifest()
    names = {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "per_layer")}
    assert names == LAGUNA | SHARED | SETUP
    e2e = {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "end_to_end")}
    assert e2e == {"train_step_ms", "setup_s"}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in LAGUNA:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_step_ms"
    for name in SHARED | SETUP:   # appended: the last of an accepted list
        assert entries[name]["workloads"][-1] == CELL
        assert len(entries[name]["workloads"]) > 1
    assert {entries[n]["layer"] for n in LAGUNA} == {"Models", "Kernels"}
    # the last seven of the list, in the issue's order (the issue's
    # eighth, the held experts' tile fill, is the accepted
    # ``qnext.gmm_tile_fill_pct``: one reader a measured thing, PR 46)
    assert [m["name"] for m in manifest["per_layer"][-7:]] == [
        "laguna.attn_window_ms", "laguna.attn_full_ms",
        "laguna.flash_window_ms", "laguna.flash_window_roofline",
        "laguna.flash_full_roofline", "laguna.head_gate_ms",
        "laguna.window_tile_fill_pct"]
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["workloads"][-1]["chips"] == 1
    assert manifest["configs"][-1]["name"] == CONFIG


def test_flops_family_finds_the_module_and_its_arithmetic(cell):
    _, config, mix, _ = cell
    assert flops.family(config) is flops_laguna
    S = mix["seq_len"]
    band = flops_laguna.attended_pairs(S, 512)
    assert band == 512 * 513 // 2 + (S - 512) * 512
    z = weights_laguna.sizes(config)
    assert flops_laguna.heads_of(z, "sliding_attention") == [72, 72, 72]
    assert flops_laguna.heads_of(z, "full_attention") == [48, 48]
    window = flops_laguna.flash_flops(1, S, z, "sliding_attention")
    full = flops_laguna.flash_flops(1, S, z, "full_attention")
    assert window == 12.0 * band * 3 * 72 * 128
    assert full == 12.0 * (S * (S + 1) // 2) * 2 * 48 * 128
    total = flops_laguna.train_flops_per_step(config, mix)
    assert total == pytest.approx(30.0e12, rel=0.01)
    assert flops_laguna.sparse_layers(z) == 4
    assert flops_laguna.expected_held_pairs(config, mix) == S * 10 * 8 / 256
    for kind, flops_needed in (("sliding_attention", window),
                               ("full_attention", full), (None,
                                                          window + full)):
        least, bound = flops_laguna.flash_roofline_seconds(
            config, mix, "TPU v5 lite", kind)
        assert bound == "compute" and least == pytest.approx(
            flops_needed / 197e12, rel=1e-6)
    least, bound = flops_laguna.gmm_roofline_seconds(
        config, mix, "TPU v5 lite")
    assert least > 0 and bound == "memory"   # 320 rows an expert
    # the matrices every token passes: everything but the held stacks
    # and the table
    assert flops_laguna.matrix_params(config) == (
        weights_laguna.n_params(config) - 4 * 8 * 9_437_184
        - 12544 * 3072)


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
    for name in sorted(LAGUNA):
        assert harness.layer_reader(name)(ctx) is None, name


# ------------------------------------- the readers on a recorded capture

def recorded():
    """The readers' context on ``chipbench/data/tiny_gswa_moe.*``,
    recorded on the chip by ``tools/record_gswa_moe_trace.py`` (this
    file's own few lines: ``captures.py`` rebuilds the accepted cells'
    from ``record_trace.KINDS``, which this kind is not in)."""
    device_trace = pytest.importorskip(
        "chainermn_tpu.observability.device_trace")
    from chipbench.tools import record_gswa_moe_trace, record_trace

    name = record_gswa_moe_trace.NAME
    kinds, record_trace.KINDS = record_trace.KINDS, (
        record_gswa_moe_trace.kinds(record_trace))
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
            "devices": [None], "device_kind": "TPU v5 lite",
            "moe_held_pairs": None, "moe_tile_fill": 0.4}


@pytest.fixture(scope="module")
def ctx():
    if not os.path.exists(os.path.join(DATA, "tiny_gswa_moe.hlo.txt.gz")):
        pytest.skip("no capture recorded yet")
    return recorded()


def test_the_seven_readers_read_a_number_in_range_on_the_capture(ctx):
    from chipbench import scope_reduce

    read = lambda name: harness.layer_reader(name)(ctx)  # noqa: E731
    phase_ms = scope_reduce.phase_ms(ctx, "fwd-bwd")
    window, full = read("laguna.attn_window_ms"), read("laguna.attn_full_ms")
    assert 0 < window and 0 < full and window + full < phase_ms
    flash_window = read("laguna.flash_window_ms")
    assert 0 < flash_window < window
    # the rows' flash kernels together are the shared reader's
    flash_full = laguna_reduce.within_ms(
        ctx, "attn-mixer", *laguna_reduce.FLASH)
    assert flash_window + flash_full == pytest.approx(
        read("kernel.flash_ms"), rel=1e-2)
    gate = read("laguna.head_gate_ms")
    assert 0 < gate < window + full
    for name in ("laguna.flash_window_roofline",
                 "laguna.flash_full_roofline"):
        assert 0 < read(name) < 100, name
    # a window of 256 at 1,024 tokens: at tiles of 256 a diagonal and a
    # far tile a query block, 7 live; the band's pairs over their area
    fill = read("laguna.window_tile_fill_pct")
    tiles = ctx["notes"]["window_tiles"]
    pairs = flops_laguna.attended_pairs(1024, 256)
    assert fill == pytest.approx(100.0 * pairs * len(tiles) / sum(
        t["live"] * t["block_q"] * t["block_k"] for t in tiles))
    assert 50.0 < fill < 60.0
    assert read("qnext.gmm_tile_fill_pct") == pytest.approx(40.0)


def test_the_readers_need_the_scopes(ctx):
    """A program without the rows' scopes (or a run without a scope
    table) reads nothing, and does not raise."""
    bare = dict(ctx, scope_table=None)
    assert laguna_reduce.window_tile_fill_pct(bare) is None
    assert harness.layer_reader("qnext.gmm_tile_fill_pct")(
        dict(ctx, moe_tile_fill=None)) is None


@pytest.mark.parametrize("name", sorted(SHARED))
def test_the_shared_readers_read_this_kind_of_step(ctx, name):
    """The cell joined these accepted entries' lists by a data edit: the
    shared readers find their regions and owners (``moe.shared_ms`` the
    shared expert, ``part.ffn_ms`` the leading dense FFN,
    ``part.mixer_gate_ms`` the gate a head), and ``flops_laguna.py`` has
    the functions they ask the family's module for."""
    value = harness.layer_reader(name)(ctx)
    assert value is not None and value >= 0
    if name in ("moe.shared_ms", "part.ffn_ms", "part.mixer_gate_ms"):
        assert value > 0
    if name.endswith("_roofline") or name == "step.mfu":
        assert 0 < value < 100
