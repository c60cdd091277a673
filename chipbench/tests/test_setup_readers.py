"""The ``setup.*`` readers (``chipbench/setup_reduce.py``): on a made-up
ledger the five phases partition set-up, the window is found from
``reference_steps``, overlapping spans are counted once, a program
without the ledger or its marks reads nothing; and one tiny step on the
CPU reads a number through each of the eight."""

import pytest

from chainermn_tpu.observability import startup
from chipbench import harness, setup_reduce
from chipbench.tests import tiny

NAMES = ["setup." + k for k in (
    "import_s", "backend_s", "build_s", "first_call_s", "first_steps_s",
    "trace_lower_s", "compile_s", "cache_misses")]
STAGE = startup.EVENT_OF_STAGE


def made_up(marks=True, steps=3, feeds=4):
    """Process at 100, import 100.5-103.5, the first call at 110, steps
    [150, 190] [191, 192] [193, 194], feeds before each and one at 200."""
    led = startup.Ledger({"first": 100.5, "jax": (100.6, 102.0),
                          "last": 103.5})
    led.process_start = 100.0
    if marks:
        led.marks[setup_reduce.FIRST_CALL] = 110.0
    ids = iter(range(1000, 2000))

    def call(name, index, start, end):
        led.add(startup.Span(next(ids), "call", name, start, end,
                             index=index, thread=1))

    for i, (start, end) in enumerate(
            [(150.0, 190.0), (191.0, 192.0), (193.0, 194.0)][:steps]):
        call("train_step", i, start, end)
    for i, start in enumerate([149.0, 190.5, 192.5, 200.0][:feeds]):
        call("global_batch", i, start, start + 0.25)

    def stage(name, start, end, program, cache=None):
        led.add(startup.Span(next(ids), "stage", name, start, end,
                             program=program, thread=1, cache=cache))

    stage("trace", 120.0, 121.0, "make_weights")
    stage("lower", 121.0, 121.5, "make_weights")
    stage("compile", 121.5, 130.0, "make_weights",
          {"state": "miss", "requests": 1, "misses": 1})
    stage("trace", 150.0, 160.0, "train_step")
    stage("trace", 152.0, 155.0, "flash_fwd")        # inside the step's
    stage("trace", 159.0, 161.0, "fused_ce")         # overlaps its end
    stage("lower", 161.0, 165.0, "train_step")
    stage("compile", 165.0, 189.0, "train_step",
          {"state": "hit", "requests": 1, "hits": 1, "retrieval_s": 2.0})
    stage("compile", 195.0, 195.5, "_norms",
          {"state": "uncached", "requests": 1})
    stage("compile", 199.0, 205.0, "late",           # runs into the window
          {"state": "miss", "requests": 1, "misses": 1})
    stage("compile", 230.0, 260.0, "reference",      # after set-up
          {"state": "miss", "requests": 1, "misses": 1})
    return led


def ctx_of(led, reference_steps=3):
    return {"mix": {"reference_steps": reference_steps},
            "startup_ledger": led}


def test_the_five_phases_partition_set_up(capsys):
    got = setup_reduce.reduce_ledger(made_up(), 3)
    assert [got[k] for k in setup_reduce.PHASES] == pytest.approx(
        [3.5, 6.5, 40.0, 40.0, 10.0])
    assert sum(got[k] for k in setup_reduce.PHASES) == pytest.approx(
        got["process_start_to_window_s"]) == pytest.approx(100.0)


@pytest.mark.parametrize("reference_steps,window", [
    (1, 190.5), (2, 192.5), (3, 200.0)])
def test_the_window_is_found_from_reference_steps(reference_steps, window):
    at = setup_reduce.cuts(made_up(), reference_steps)
    assert at[-1] == window and at[3:5] == (150.0, 190.0)


def test_the_window_may_open_with_a_step():
    led = made_up(feeds=3)              # no feed after the third step
    assert setup_reduce.cuts(led, 3) is None
    led.add(startup.Span(1, "call", "train_step", 201.0, 202.0, index=3))
    assert setup_reduce.cuts(led, 3)[-1] == 201.0


def test_overlapping_spans_count_once_and_set_up_clips_them():
    got = setup_reduce.reduce_ledger(made_up(), 3)
    # trace 120-121, lower 121-121.5; 150-161 (three traces), 161-165
    assert got["trace_lower_s"] == pytest.approx(1.5 + 15.0)
    # 121.5-130, 165-189, 195-195.5, 199-200 of the one the window cuts
    assert got["compile_s"] == pytest.approx(8.5 + 24.0 + 0.5 + 1.0)
    assert (got["cache_misses"], got["cache_hits"],
            got["cache_too_quick_to_keep"]) == (2, 1, 1)
    assert [r["program"] for r in got["programs"]] == [
        "make_weights", "train_step", "flash_fwd", "fused_ce", "_norms",
        "late"]


@pytest.mark.parametrize("led", [
    None, startup.Ledger(), made_up(marks=False), made_up(steps=2),
], ids=["no-ledger", "fresh-ledger", "no-marks", "too-few-steps"])
def test_every_reader_reads_nothing_where_the_source_is_not_there(
        led, capsys):
    ctx = ctx_of(led)
    for name in NAMES:
        assert harness.layer_reader(name)(ctx) is None
    assert "setup" not in capsys.readouterr().out


def test_a_program_from_before_the_ledger_reads_nothing(monkeypatch):
    import sys

    monkeypatch.setitem(
        sys.modules, "chainermn_tpu.observability.startup", None)
    monkeypatch.delattr("chainermn_tpu.observability.startup")
    assert setup_reduce.ledger({}) is None
    for name in NAMES:
        assert harness.layer_reader(name)({"mix": {"reference_steps": 2}}) \
            is None


def test_the_table_is_printed_once_a_run(capsys):
    ctx = ctx_of(made_up())
    values = [harness.layer_reader(name)(ctx) for name in NAMES]
    assert values == pytest.approx(
        [3.5, 6.5, 40.0, 40.0, 10.0, 16.5, 34.0, 2])
    out = capsys.readouterr().out
    assert out.count("[chipbench] setup: import_s=3.5000") == 1
    assert out.count("setup program train_step: trace_s=10.000") == 1
    assert ctx["notes"]["setup"]["programs"][1]["cache"] == "hit"


def test_the_manifest_holds_the_eight_in_every_cell():
    manifest = harness.load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-8:]] == NAMES
    for name in NAMES:
        entry = by_name[name]
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert entry["workloads"] == cells
        assert entry["layer"] in ("Entry points", "Train step assembly")
    for cell in cells:
        assert {m["name"] for m in harness.cell_metrics(
            manifest, cell, "per_layer")} >= set(NAMES)


def test_one_tiny_step_reads_a_number_through_each_of_the_eight():
    manifest = harness.load_manifest()
    entries = [dict(m, workloads=["tiny-train"])
               for m in manifest["per_layer"] if m["name"] in NAMES]
    with startup.use(startup.Ledger()) as led:
        line, run = tiny.tiny_run(seed=5, seconds=0.3, per_layer=entries)
        got = harness.read_layer_metrics(
            run.manifest, "tiny-train", {"mix": run.mix})
    assert set(got) == set(NAMES)
    assert all(v["value"] >= 0 for v in got.values())
    five = sum(got["setup." + k]["value"] for k in setup_reduce.PHASES)
    # the run's own process-start-to-window time: its ``setup_s`` counts
    # from ``t_start``, the ledger from the process's start
    own = line["metrics"]["setup_s"]["value"] + (
        run.t_start - led.process_start)
    assert five == pytest.approx(own, abs=0.05)
    assert got["setup.first_call_s"]["value"] > 0
    assert got["setup.trace_lower_s"]["value"] > 0
    assert got["setup.compile_s"]["value"] <= five
    assert got["setup.cache_misses"]["unit"] == "count"
