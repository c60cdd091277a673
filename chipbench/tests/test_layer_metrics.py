"""The manifest's per-layer entries as a whole: one reader a measured
thing (PR 46).  An entry is found by its name and never by its position;
what belongs to one family alone stays in that family's
``test_*_files.py``."""

import ast
import json
import os

import pytest

from chipbench import harness, scope_reduce
from chipbench.tests import captures

READERS_DIR = os.path.join(harness.HERE, "layer_metrics")
RECORDED = captures.RECORDED
MANIFEST = harness.load_manifest()
ENTRIES = {m["name"]: m for m in MANIFEST["per_layer"]}
CELLS = [w["name"] for w in MANIFEST["workloads"]]

#: What every training cell reports, and what a kind of layer adds.
EVERY = {"step.fwd_bwd_ms", "step.opt_update_ms", "kernel.fused_ce_ms",
         "trace.unattributed_pct", "device.idle.train", "part.mixer_proj_ms",
         "part.norm_ms", "part.residual_ms", "part.embed_ms",
         "parts.unowned_pct", "parts.shared_pct"}
FLASH = {"kernel.flash_ms", "kernel.flash_roofline"}
FLASH_PASSES = {"kernel.flash_fwd_ms", "kernel.flash_bwd_ms"}
DENSE_FFN = {"part.ffn_ms", "part.ffn_roofline"}
MAMBA = {"ssm.mixer_ms", "kernel.ssd_ms", "kernel.ssd_roofline",
         "kernel.ssm_conv_ms", "part.mixer_gate_ms"}
EXPERTS = {"moe.layer_ms", "moe.route_ms", "moe.dispatch_ms",
           "kernel.moe_gmm_ms", "kernel.moe_gmm_roofline"}
REMAT = {"step.mfu", "part.recompute_ms"}    # the six cells after cgpt's
CGPT = EVERY | FLASH | FLASH_PASSES | DENSE_FFN | {"model.mfu"}
REPORTS = {
    "cgpt-train-1chip": CGPT,
    "cgpt-train-dp4": CGPT | {"comm.exchange_ms", "comm.exposed_ms",
                              "comm.pack_ms"},
    "granite4hm-train-1chip": EVERY | REMAT | MAMBA | DENSE_FFN | {
        "kernel.flash_ms"},
    "nemo3nano-train-1chip": EVERY | REMAT | MAMBA | EXPERTS | FLASH
    | FLASH_PASSES | {"moe.shared_ms"},
    "zaya1-train-1chip": EVERY | REMAT | EXPERTS | FLASH | {
        "cca.mixer_ms", "cca.conv_ms", "cca.rope_norm_ms"},
    "qwen3next-train-1chip": EVERY | REMAT | EXPERTS | FLASH | {
        "moe.shared_ms", "kernel.ssm_conv_ms", "part.mixer_gate_ms",
        "qnext.gdn_mixer_ms", "qnext.gdn_scan_ms", "qnext.gdn_scan_roofline",
        "qnext.attn_mixer_ms", "qnext.gmm_tile_fill_pct"},
    "mellum2-train-1chip": EVERY | REMAT | EXPERTS | {
        "mellum.attn_window_ms", "mellum.attn_full_ms",
        "mellum.flash_window_ms", "mellum.flash_full_ms",
        "mellum.flash_window_roofline", "mellum.flash_full_roofline",
        "mellum.window_tile_fill_pct", "mellum.rope_ms"},
    "ling3flash-train-1chip": EVERY | REMAT | EXPERTS | FLASH | DENSE_FFN | {
        "kernel.ssm_conv_ms", "part.mixer_gate_ms", "ling.kda_mixer_ms",
        "ling.kda_scan_ms", "ling.kda_scan_roofline", "ling.mla_mixer_ms"},
}
#: Read from the run's own clock or counter, not from a capture: each has
#: a test of its own (``test_trace_reduce.py``, ``test_qwen3next_files.py``).
OFF_TRACE = {"model.mfu", "qnext.gmm_tile_fill_pct"}
#: A family's own files, none of which may import the program.
OWN_FILES = {
    "gpt2": ("refs/gpt2_dense.py", "weights.py", "flops_gpt2.py", "flops.py"),
    "granitemoehybrid": ("refs/granite_hybrid.py", "weights_hybrid.py",
                         "flops_hybrid.py"),
    "nemotron_h": ("refs/nemotron_h.py", "weights_nemotron.py",
                   "flops_nemotron.py"),
    "zaya": ("refs/zaya1.py", "weights_zaya.py", "flops_zaya.py"),
    "qwen3_next": ("refs/qwen3_next.py", "weights_qwen3next.py",
                   "flops_qwen3next.py"),
    "mellum": ("refs/mellum2.py", "weights_mellum2.py", "flops_mellum2.py"),
    "bailing_hybrid": ("refs/ling3.py", "weights_ling3.py", "flops_ling3.py"),
}


def body(path):
    """A reader's file without its docstrings and its layout."""
    with open(path) as f:
        text = f.read()
    if path.endswith(".json"):
        return json.dumps(json.loads(text), sort_keys=True)
    tree = ast.parse(text)
    for node in ast.walk(tree):
        block = getattr(node, "body", None)
        if (isinstance(block, list) and block
                and isinstance(block[0], ast.Expr)
                and isinstance(block[0].value, ast.Constant)
                and isinstance(block[0].value.value, str)):
            node.body = block[1:] or [ast.Pass()]
    return ast.unparse(tree)


def test_the_manifest_has_room_and_every_entry_lists_its_cells():
    assert len(ENTRIES) == len(MANIFEST["per_layer"]) <= 64
    for name, entry in ENTRIES.items():
        assert entry["workloads"], name
        assert set(entry["workloads"]) <= set(CELLS), name
        assert entry["moves"] == "train_step_ms"
        if name.endswith("_roofline") or "mfu" in name.split("."):
            assert (entry["unit"], entry["better"]) == ("%", "higher")


def test_one_file_an_entry_and_none_besides():
    files = sorted(os.listdir(READERS_DIR))
    assert sorted(os.path.splitext(f)[0] for f in files) == sorted(ENTRIES)
    assert all(f.endswith((".py", ".json")) for f in files)
    for name in ENTRIES:
        assert callable(harness.layer_reader(name))


def test_no_two_entries_resolve_to_readers_with_the_same_body():
    seen = {}
    for f in sorted(os.listdir(READERS_DIR)):
        seen.setdefault(body(os.path.join(READERS_DIR, f)), []).append(f)
    assert [fs for fs in seen.values() if len(fs) > 1] == []


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_what_it_reported(cell):
    """By name: the ledger's newest per-layer values of each cell have
    these successors (PERF.md section 6, PR 46)."""
    names = {m["name"] for m in harness.cell_metrics(
        MANIFEST, cell, "per_layer")}
    assert names == REPORTS[cell]
    e2e = {m["name"] for m in harness.cell_metrics(
        MANIFEST, cell, "end_to_end")}
    assert e2e == {"train_step_ms", "setup_s"}


def test_the_remat_cells_are_the_recompute_readers_list():
    remat = [c for c in CELLS
             if harness.find_cell(MANIFEST, c)[1]["program"]["remat"]]
    assert ENTRIES["part.recompute_ms"]["workloads"] == remat


@pytest.mark.parametrize("cell", CELLS)
def test_readers_return_nothing_without_a_trace(cell):
    _, config, mix, _ = harness.find_cell(MANIFEST, cell)
    ctx = {"config": config, "mix": mix, "device_kind": "TPU v5 lite",
           "devices": [None], "trace_steps": 4, "trace": None}
    for metric in harness.cell_metrics(MANIFEST, cell, "per_layer"):
        if metric["source"] == "device_trace":
            assert harness.layer_reader(metric["name"])(ctx) is None, metric


@pytest.mark.parametrize("family", sorted(OWN_FILES))
def test_the_reference_imports_nothing_of_the_program(family):
    for name in OWN_FILES[family]:
        with open(os.path.join(harness.HERE, name)) as f:
            text = f.read()
        assert "import chainermn_tpu" not in text, name
        assert "from chainermn_tpu" not in text, name


def test_every_configuration_finds_its_flops_module():
    from chipbench import flops

    for entry in MANIFEST["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        module = flops.family(config)
        family = config.get("model_type", "gpt2")
        assert module.__name__.split(".")[-1] + ".py" in OWN_FILES[family]
        assert family == "gpt2" or callable(module.train_flops_per_step)
    with pytest.raises(ModuleNotFoundError):
        flops.family({"model_type": "no_such_family"})


# ----------------------------------- every reader on a recorded capture

@pytest.fixture(scope="module")
def recorded():
    return {cell: captures.recorded(cell) for cell in RECORDED}


@pytest.mark.parametrize("cell,name", [
    (cell, name) for cell in RECORDED for name in sorted(REPORTS[cell])])
def test_each_cell_of_a_list_gets_a_number_on_its_recorded_capture(
        recorded, cell, name):
    """Every entry that lists a cell reads that cell's kind of step: a
    list that grew past what its reader reads shows here, off the chip."""
    if name in OFF_TRACE:
        pytest.skip("read from the run's own clock or counter")
    ctx = recorded[cell]
    value = harness.layer_reader(name)(ctx)
    phase_ms = scope_reduce.phase_ms(ctx, "fwd-bwd")
    assert value is not None
    if name.endswith("_roofline") or name == "step.mfu":
        assert 0 < value < 100     # tiny shapes keep the matrix unit idle
    elif name.endswith("_pct") or name == "device.idle.train":
        assert 0 <= value <= 100
    elif name in ("step.fwd_bwd_ms", "step.opt_update_ms"):
        assert value > 0
    elif name in ("part.residual_ms", "part.mixer_gate_ms"):
        # fused into their neighbours on the chip: a reading, maybe 0
        assert 0 <= value < phase_ms
    else:
        assert 0 < value < phase_ms
