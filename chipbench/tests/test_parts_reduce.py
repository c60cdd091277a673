"""The per-layer readers that read device time by OWNER
(``chipbench/parts_reduce.py``, ``flops_parts.py``): on a capture recorded
on the chip with its step's ``as_text()``
(``chipbench/tools/record_trace.py tiny_hybrid``: a tiny ``train_hybrid`` run,
two Mamba-2 layers, an attention layer and a third Mamba-2 layer under
``remat``, three traced steps on one TPU v5 lite), on the older fixture
that has no table, and on contexts a parent commit would hand over."""

import json
import os

import pytest

from chipbench import (check_manifest, flops, flops_parts, harness,
                       parts_reduce, scope_reduce, trace_reduce)
from chipbench.tests import captures

device_trace = pytest.importorskip(
    "chainermn_tpu.observability.device_trace")

DATA = os.path.join(harness.HERE, "data")


CELL = "granite4hm-train-1chip"      # the capture stands for it
STEPS = captures.TOOL.STEPS
_, CONFIG, MIX, _ = captures.TOOL.context(captures.RECORDED[CELL])
READERS = ("part.ffn_ms", "part.ffn_roofline", "part.mixer_proj_ms",
           "part.mixer_gate_ms", "part.norm_ms", "part.residual_ms",
           "part.embed_ms", "part.recompute_ms", "parts.unowned_pct",
           "parts.shared_pct")


def read(name, ctx):
    return harness.layer_reader(name)(ctx)


@pytest.fixture(scope="module")
def recorded():
    return captures.recorded(CELL)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_on_the_recorded_capture(recorded, name):
    ctx = dict(recorded)
    value = read(name, ctx)
    (got,) = scope_reduce.attribution(ctx)["all"]
    phase_ms = scope_reduce.phase_ms(ctx, "fwd-bwd")
    if name == "part.ffn_roofline":
        # a tiny FFN keeps the matrix unit idle: far under its peak
        assert 0 < value < 100
        assert value == pytest.approx(
            100 * flops_parts.ffn_train_flops(CONFIG, MIX, 1)
            / flops.peaks("TPU v5 lite")["bf16_flops"]
            / (read("part.ffn_ms", ctx) / 1e3))
    elif name == "parts.unowned_pct":
        assert 0 <= value < 5.0
    elif name == "parts.shared_pct":
        assert 0 < value < 100
        assert value == pytest.approx(
            100 * sum(got["shared"].values()) / got["busy"])
    elif name == "part.residual_ms":
        # the adds are fused into their neighbours on the chip: a
        # reading, and it may be 0
        assert 0 <= value < phase_ms
    else:
        assert 0 < value < phase_ms


def test_the_owners_add_up_to_the_phase_on_the_recorded_capture(recorded):
    ctx = dict(recorded)
    (got,) = scope_reduce.attribution(ctx)["all"]
    per_step = 1e3 / STEPS
    by_owner = sum(got["owner"].values()) * per_step
    assert sum(parts_reduce.pass_ms(ctx, p) for p in (
        "forward", "recompute", "backward")) == pytest.approx(by_owner)
    # the phase by the owner rule is the phase by the fusions' own names
    # but for the fusions that hold a second phase, and what inherited
    assert by_owner == pytest.approx(
        scope_reduce.phase_ms(ctx, "fwd-bwd"),
        abs=(got["mixed"] + got["unattributed"]) * per_step + 1e-9)
    named = ("ffn", "mixer-proj", "mixer-gate", "norm", "residual", "embed")
    assert parts_reduce.owner_ms(ctx, *named) == pytest.approx(
        sum(read(f"part.{n.replace('-', '_')}_ms", ctx) for n in named))
    assert parts_reduce.owner_ms(ctx, *named) < by_owner
    assert set(got["owner"]) >= {"ssd-scan", "ssm-conv", "fused-ce",
                                 "flash-fwd", *named} - {"residual"}
    assert got["joined"] / got["busy"] >= device_trace.MIN_JOINED_SHARE


def test_the_readers_return_nothing_where_there_is_no_owner_reading(
        recorded, monkeypatch):
    for name in READERS:
        assert read(name, {"trace": None}) is None
    # a parent commit's ``attribute`` has no such key
    ctx = dict(recorded)
    for g in scope_reduce.attribution(ctx)["all"]:
        for key in ("owner", "shared", "pass"):
            g.pop(key)
    for name in READERS:
        assert read(name, ctx) is None, name
    # ... and one older still has no ``device_trace``
    monkeypatch.setattr(scope_reduce, "_device_trace", lambda: None)
    for name in READERS:
        assert read(name, dict(recorded)) is None


def test_the_older_fixture_has_no_table_and_reads_nothing():
    """``tiny_train.xplane.pb.gz`` came without its step's text: joined
    to a table compiled here, on the CPU, under 98% of it joins."""
    import jax

    from chipbench.tests import tiny

    ctx = {"trace": trace_reduce.TraceData.from_file(
               os.path.join(DATA, "tiny_train.xplane.pb.gz"), n_devices=1),
           "trace_steps": 3, "config": tiny.TRAIN_CONFIG,
           "mix": dict(tiny.TRAIN_MIX, global_batch=4),
           "devices": jax.devices()[:1], "device_kind": "TPU v5 lite"}
    for name in READERS:
        assert read(name, ctx) in (None, 0.0), name


@pytest.mark.parametrize("config,width,matrices,n_layers", [
    ("cgpt1p3b-train", (2048, 8192), 2, 8),
    ("granite4hmicro-train", (2048, 8192), 3, 10),
    ("ling3flash-train", (2560, 6144), 3, 2)])
def test_ffn_flops_from_the_cells_configurations(config, width, matrices,
                                                 n_layers):
    c = harness.load_json(os.path.join(
        harness.HERE, "configs", config + ".json"))
    mix = {"global_batch": 8, "seq_len": 2048}
    d, d_ff, m, layers = flops_parts.ffn_shape(c)
    assert ((d, d_ff), m, layers) == (width, matrices, n_layers)
    assert flops_parts.ffn_train_flops(c, mix, 1) == (
        3 * 2 * 16384 * d * d_ff * matrices * layers)
    # a chip's own tokens
    assert flops_parts.ffn_train_flops(
        c, dict(mix, global_batch=32), 4) == (
            flops_parts.ffn_train_flops(c, mix, 1))
    assert flops_parts.ffn_train_flops({"model_type": "zaya"}, mix, 1) is (
        None)


def test_the_manifest_lists_the_owner_readers_where_they_read():
    """Found by name: every cell reads the parts every model has, the
    cells with a dense FFN the ``ffn`` pair, the cells whose mixers have a
    float32 side ``mixer-gate``."""
    manifest = harness.load_manifest()
    assert check_manifest.check(manifest, harness.ROOT) == []
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for name in READERS:
        entry = entries[name]
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_step_ms"
        assert set(entry["workloads"]) <= set(cells)
    for name in ("part.mixer_proj_ms", "part.norm_ms", "part.residual_ms",
                 "part.embed_ms", "parts.unowned_pct", "parts.shared_pct"):
        assert entries[name]["workloads"] == cells, name
    dense = [c for c in cells if flops_parts.ffn_shape(
        harness.find_cell(manifest, c)[1]) is not None]
    assert entries["part.ffn_roofline"]["workloads"] == (
        entries["part.ffn_ms"]["workloads"]) == dense
    assert len(dense) == 4
    assert set(entries["part.mixer_gate_ms"]["workloads"]) == {
        c for c in cells if not c.startswith(("cgpt", "zaya1", "mellum2"))}
    json.dumps(entries)
