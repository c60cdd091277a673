"""Device time by program scope: the vocabulary is entered where the work
happens (``observability/spans.py``), the compiled step carries it, and
``observability/device_trace.py`` joins it to a profiler capture.

One test reads a fixture recorded on the chip with this tree
(``tests/data/record_scope_fixture.py``): a tiny traced run's
``.xplane.pb.gz`` and the ``as_text()`` of its step.
"""

import gzip
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.observability import device_trace, hlo_audit, spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _recorder_tool():
    spec = importlib.util.spec_from_file_location(
        "record_scope_fixture",
        os.path.join(DATA, "record_scope_fixture.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tiny_step():
    return _recorder_tool().build_tiny_step(jax.devices()[:2])


@pytest.fixture
def annotations(monkeypatch):
    """The names of the host annotations entered, in order."""
    names = []
    real = jax.profiler.TraceAnnotation

    def noting(name, **kwargs):
        names.append(name)
        return real(name, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", noting)
    return names


# ---------------------------------------------------------------- vocabulary
def test_compiled_step_holds_every_phase_and_kernel_region(tiny_step):
    step, params, state, feed = tiny_step
    lowered = step.lower(params, state, feed(0))
    assert lowered.as_text().startswith("module @jit_train_step ")
    table = device_trace.scope_table(lowered.compile())
    assert table.program == "jit_train_step"
    found = {device_trace.classify(path) for path in table.values()}
    phases = {phase for phase, _ in found}
    regions = {region for _, region in found}
    assert set(spans.STEP_PHASES) <= phases
    assert {"flash-fwd", "flash-bwd-dq", "flash-bwd-dkv", "fused-ce",
            "grad-stage0", "grad-unpack"} <= regions
    # a kernel region sits inside the phase that runs it
    assert ("fwd-bwd", "fused-ce") in found
    assert ("allreduce", "grad-unpack") in found
    # the scans of fused CE are containers: their bodies are attributed
    assert table.containers


def test_named_scope_takes_vocabulary_names_only():
    for name in (spans.STEP_PHASES + spans.ALLREDUCE_STAGES
                 + spans.KERNEL_REGIONS + ("grad-stage0", "grad-stage12")):
        with spans.named_scope(name):
            pass
    for name in ("fwd", "grad-stage", "grad-stagex", "train_step", ""):
        with pytest.raises(ValueError, match="scope vocabulary"):
            spans.named_scope(name)


@pytest.mark.parametrize("zero_stage,with_state,name", [
    (1, False, "jit_train_step_zero"),
    (3, False, "jit_train_step_zero3"),
    (0, True, "jit_train_step_with_state"),
    (1, True, "jit_train_step_zero_with_state"),
    (3, True, "jit_train_step_zero3_with_state"),
])
def test_every_train_step_compiles_under_its_program_name(
        zero_stage, with_state, name):
    import optax

    import chainermn_tpu
    from chainermn_tpu.communicators import build_mesh

    assert name[len("jit_"):] in spans.PROGRAM_NAMES
    comm = chainermn_tpu.create_communicator("xla_ici", mesh=build_mesh(
        inter_size=1, intra_size=2, devices=jax.devices()[:2]))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1), comm, zero_stage=zero_stage)
    params = {"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))}
    batch = (jnp.ones((4, 8)), jnp.zeros((4, 4)))
    state = opt.init(params)
    if zero_stage == 3:
        params = opt.shard_params(params)
    if with_state:
        def loss_fn(p, model_state, b):
            return jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2), model_state

        step = opt.make_train_step_with_state(loss_fn)
        args = (params, state, {"n": jnp.zeros(())}, batch)
    else:
        def loss_fn(p, b):
            return jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2)

        step = opt.make_train_step(loss_fn)
        args = (params, state, batch)
    text = step.lower(*args).as_text(debug_info=True)
    assert f"module @{name} " in text[:400], text[:400]
    for phase in spans.STEP_PHASES:
        assert f'"{phase}/' in text, phase


def test_serving_programs_and_host_stages_are_named(annotations):
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler, EngineConfig, InferenceEngine, Request)

    lm = TransformerLM(vocab=32, d_model=16, n_heads=2, d_ff=32,
                       n_layers=1, max_len=32)
    params = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = InferenceEngine(lm, params, EngineConfig(
        block_size=4, n_blocks=16, max_len=32, max_batch=2))
    for attr, name in (("_prefill_jit", "prefill_step"),
                       ("_decode_jit", "decode_step"),
                       ("_chunk_jit", "chunk_step"),
                       ("_cow_jit", "cow_step")):
        assert getattr(engine, attr).__name__ == name
        assert name in spans.PROGRAM_NAMES
    sched = ContinuousBatchingScheduler(engine)
    sched.add_request(Request(request_id=0, prompt=[1, 2, 3],
                              max_new_tokens=3))
    sched.run_to_completion()
    stages = {"admit", "prefill", "decode", "sample", "emit",
              "table-build", "dispatch", "readback"}
    assert stages <= set(spans.HOST_SPANS)
    assert {spans.HOST_PREFIX + s for s in stages} <= set(annotations)
    # the request tracer's spans sit on the same clock
    tracer = obs.Tracer()
    with tracer.span("queue"):
        pass
    assert annotations[-1] == "chainermn:queue"
    # the decode program's attention is in its region
    lowered = engine._decode_jit.lower(
        engine.params, engine._cache, jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32))
    assert lowered.as_text().startswith("module @jit_decode_step ")
    assert "paged-decode-attn" in lowered.as_text(debug_info=True)


def test_step_and_batch_annotations_need_no_telemetry(tiny_step,
                                                      annotations):
    step, params, state, feed = tiny_step
    assert not spans.telemetry_active()
    abstract = jax.eval_shape(lambda: (params, state))
    batch = feed(0)
    assert annotations == ["chainermn:global_batch"]
    step.lower(*abstract, batch)      # the AOT surface enters nothing
    assert annotations == ["chainermn:global_batch"]
    with spans.span("evaluate"):
        pass
    assert annotations[-1] == "chainermn:evaluate"


# --------------------------------------------------------------- attribution
def test_hlo_instructions_read_the_ops_own_metadata():
    text = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "%body (p: f32[8]) -> f32[8] {",
        '  %p = f32[8]{0} parameter(0), metadata={op_name="x"}',
        '  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(train_step)/fwd-bwd/mul"}',
        "}",
        "ENTRY %main.3 (a: bf16[8,128]) -> f32[8] {",
        '  %MultiHeadAttention_0.6 = (bf16[8,128]{1,0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call", backend_config={"x": '
        '{"metadata={}": 1}}, metadata={op_name="jit(train_step)/fwd-bwd/'
        'jvp(LM)/flash-fwd/flash-fwd" stack_frame_id=2}',
        "  %while.5 = (s32[]) while(%t), condition=%c, body=%body",
        "  %copy.1 = f32[8]{0} copy(%p)",
    ])
    got = {i.name: i for i in hlo_audit.hlo_instructions(text)}
    assert hlo_audit.hlo_module_name(text) == "jit_train_step"
    assert got["fusion.7"].op_name == "jit(train_step)/fwd-bwd/mul"
    assert got["MultiHeadAttention_0.6"].op_name.endswith(
        "flash-fwd/flash-fwd")
    assert got["copy.1"].op_name == "" and got["while.5"].opcode == "while"
    assert got["fusion.7"].computation == "body"
    assert got["copy.1"].computation == "main.3"
    assert "fc" in got["fusion.7"].operands
    table = device_trace.scope_table(text)
    assert table.containers == {"while.5"}
    assert table.program == "jit_train_step"
    assert table["copy.1"] == ""


def test_fusions_that_hold_a_second_phase_are_counted_as_mixed():
    """The compiler names a fusion after one of the ops it fused: the
    weight-gradient matmul with the update fused in reads ``fwd-bwd``."""
    wgrad = "jit(train_step)/fwd-bwd/transpose(jvp(LM))/dot_general"
    text = "\n".join([
        "HloModule jit_train_step",
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        f'  %d = f32[8]{{0}} convolution(%p, %p), metadata={{op_name="{wgrad}"}}',
        '  ROOT %a = f32[8]{0} add(%d, %p), '
        'metadata={op_name="jit(train_step)/opt-update/add"}',
        "}",
        "%fused_computation.2 (p: f32[8]) -> f32[8] {",
        "  %q = f32[8]{0} parameter(0)",
        '  ROOT %m = f32[8]{0} multiply(%q, %q), '
        'metadata={op_name="jit(train_step)/fwd-bwd/mul"}',
        "}",
        "ENTRY %main.1 (x: f32[8]) -> f32[8] {",
        "  %x = f32[8]{0} parameter(0)",
        "  %fusion.1 = f32[8]{0} fusion(%x), kind=kOutput, "
        f'calls=%fused_computation.1, metadata={{op_name="{wgrad}"}}',
        "  ROOT %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, "
        "calls=%fused_computation.2, "
        'metadata={op_name="jit(train_step)/fwd-bwd/mul"}',
        "}",
    ])
    table = device_trace.scope_table(text)
    assert table.mixed == {"fusion.1"}
    got = device_trace.attribute(
        [("fusion.1", 0.0, 3.0), ("fusion.2", 3.0, 4.0)], table)
    assert got["phase"] == {"fwd-bwd": 4.0} and got["mixed"] == 3.0


@pytest.mark.parametrize("path,expected", [
    ("jit(train_step)/shard_map/fwd-bwd/jvp(LM)/layer_0/mul",
     ("fwd-bwd", None)),
    ("jit(train_step)/fwd-bwd/transpose(fwd-bwd)/jvp(LM)/flash-bwd-dq/"
     "flash-bwd-dq", ("fwd-bwd", "flash-bwd-dq")),
    ("transpose(fwd-bwd)/jvp()/transpose(jvp(fused-ce))/while/body/dot",
     ("fwd-bwd", "fused-ce")),
    ("jit(train_step)/allreduce/grad-stage3/psum", ("allreduce",
                                                    "grad-stage3")),
    ("jit(train_step)/allreduce/grad-unpack/opt-update/add",
     ("allreduce", "grad-unpack")),
    ("jit(train_step)/opt-update/mul", ("opt-update", None)),
    ("params['embed']['embedding']", (None, None)),
    ("jit(train_step)/flash-fwd/flash-fwd", (None, "flash-fwd")),
    ("", (None, None)),
])
def test_classify_strips_wrappers_and_reads_both_ways(path, expected):
    assert device_trace.classify(path) == expected


def _table():
    return device_trace.ScopeTable({
        "a": "jit(train_step)/fwd-bwd/jvp(LM)/mul",
        "k": "jit(train_step)/fwd-bwd/transpose(jvp(LM))/flash-bwd-dkv/"
             "flash-bwd-dkv",
        "r": "jit(train_step)/allreduce/grad-stage0/psum",
        "u": "jit(train_step)/opt-update/add",
        "c": "",                       # a copy the compiler gave no path
        "loop": "jit(train_step)/fwd-bwd/jvp(fused-ce)/while",
        "body": "jit(train_step)/fwd-bwd/jvp(fused-ce)/while/body/dot",
    }, containers={"loop"}, program="jit_train_step")


def test_attribute_partitions_the_busy_time():
    ops = [
        ("%a = f32[8]{0} fusion(%p), kind=kLoop", 0.0, 2.0),
        ("%k = (bf16[8]) custom-call(%q)", 2.0, 5.0),
        ("%loop = (s32[]) while(%t), body=%b", 5.0, 9.0),   # container
        ("%body = f32[8] fusion(%x)", 5.0, 6.0),
        ("%body = f32[8] fusion(%x)", 7.0, 8.5),            # idle 6-7
        ("%r = f32[8] all-reduce(%g)", 9.0, 12.0),
        ("%u = f32[8] fusion(%g)", 11.0, 13.0),             # overlaps %r
        ("c", 13.0, 13.5),                                  # a bare name
        ("%stranger.1 = f32[8] fusion(%g)", 13.5, 14.0),    # not in table
        ("%a = f32[8]{0} fusion(%p)", 14.0, 14.0),          # empty
    ]
    got = device_trace.attribute(ops, _table())
    assert got["busy"] == pytest.approx(12.5)    # idle 6-7 and 8.5-9
    assert got["phase"] == pytest.approx(
        {"fwd-bwd": 2 + 3 + 1 + 1.5, "allreduce": 2.0, "opt-update": 2.0})
    assert got["region"] == pytest.approx(
        {"flash-bwd-dkv": 3.0, "fused-ce": 2.5, "grad-stage0": 2.0})
    assert got["unattributed"] == pytest.approx(1.0)     # c + stranger
    assert got["joined"] == pytest.approx(12.0)          # all but stranger
    assert sum(got["phase"].values()) + got["unattributed"] == (
        pytest.approx(got["busy"]))
    # an op inside another: the inner one takes its time, the outer the rest
    nested = device_trace.attribute(
        [("a", 0.0, 10.0), ("u", 2.0, 3.0), ("k", 2.5, 4.0)], _table())
    assert nested["busy"] == pytest.approx(10.0)
    assert nested["phase"] == pytest.approx(
        {"fwd-bwd": 2 + 1.5 + 6, "opt-update": 0.5})
    empty = device_trace.attribute([], _table())
    assert empty["busy"] == 0 and empty["phase"] == {}


def test_joined_share_is_what_a_reader_refuses_on():
    ops = [("a", 0.0, 97.0), ("stranger", 97.0, 100.0)]
    got = device_trace.attribute(ops, _table())
    assert got["joined"] / got["busy"] < device_trace.MIN_JOINED_SHARE
    ops = [("a", 0.0, 98.5), ("stranger", 98.5, 100.0)]
    got = device_trace.attribute(ops, _table())
    assert got["joined"] / got["busy"] >= device_trace.MIN_JOINED_SHARE
    report = device_trace.report_from(
        [{"name": "/device:TPU:0", "ops": ops,
          "modules": [("jit_train_step(123)", 0.0, 100.0)]}], [],
        {"train_step": _table()})
    row = report["programs"]["train_step"]
    assert row["joined_share"] == pytest.approx(0.985)
    assert row["calls"] == 1 and row["busy_ms"] == pytest.approx(1e5)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    ops = [("a", 0.0, 1.0), ("loop", 1.0, 2.0), ("u", 4.0, 5.0),
           ("a", 5.5, 6.0), ("a", 9.0, 10.0)]
    host = [("chainermn:global_batch", 1.9, 3.8),
            ("chainermn:train_step", 3.8, 4.1),
            ("chainermn:train_step", 5.0, 5.6),
            ("chipbench:wait_step", 6.0, 9.0),      # not the library's
            ("chainermn:train_step", 20.0, 30.0)]
    got = device_trace.idle_by_host_span(ops, host)
    assert got == pytest.approx({
        "chainermn:global_batch": 2.0, "chainermn:train_step": 0.5,
        "unannotated": 3.0})
    assert device_trace.idle_by_host_span([], host) == {}


def test_report_splits_the_capture_by_program():
    decode = device_trace.ScopeTable(
        {"d": "jit(decode_step)/paged-decode-attn/dot"},
        program="jit_decode_step")
    devices = [{
        "name": "/device:TPU:0",
        "ops": [("a", 0.0, 1.0), ("u", 1.0, 1.5), ("d", 2.0, 2.25),
                ("a", 3.0, 4.0), ("u", 4.0, 4.5), ("x", 5.0, 5.5)],
        "modules": [("jit_train_step(7)", 0.0, 1.5),
                    ("jit_decode_step(9)", 2.0, 2.25),
                    ("jit_train_step(7)", 3.0, 4.5),
                    ("jit__norms(3)", 5.0, 5.5)],
    }]
    report = device_trace.report_from(
        devices, [("chainermn:train_step", 1.5, 2.0)],
        {"train_step": _table(), "decode_step": decode})
    assert set(report["programs"]) == {"train_step", "decode_step", "other"}
    train = report["programs"]["train_step"]
    assert train["calls"] == 2
    assert train["phase_ms"] == pytest.approx(
        {"fwd-bwd": 1000.0, "opt-update": 500.0})
    assert train["unattributed_ms"] == 0 and train["joined_share"] == 1
    assert report["programs"]["decode_step"]["region_ms"] == (
        pytest.approx({"paged-decode-attn": 250.0}))
    assert report["programs"]["other"]["unattributed_ms"] == (
        pytest.approx(500.0))
    assert report["busy_s"] == pytest.approx(3.75)
    assert report["idle_share"] == pytest.approx(1 - 3.75 / 5.5)
    assert report["idle_by_host_span_ms"] == pytest.approx(
        {"chainermn:train_step": 500.0, "unannotated": 1250.0})


def test_tiles_component_is_a_record_not_a_region():
    part = "tiles-q512-k1024-live10-visited16-copied9"
    assert spans.parse_tiles(part) == {
        "block_q": 512, "block_k": 1024, "live": 10, "visited": 16,
        "copied": 9}
    assert spans.parse_tiles("flash-fwd") is None
    assert not spans.is_scope(part)
    path = f"jit(train_step)/fwd-bwd/jvp(LM)/flash-fwd/{part}/pallas_call"
    assert device_trace.classify(path) == ("fwd-bwd", "flash-fwd")

    def f(x):
        with spans.tiles_scope(block_q=512, block_k=1024, live=10,
                               visited=16, copied=9):
            return jnp.sin(x)

    compiled = jax.jit(f).lower(jnp.ones((8,))).compile()
    assert f"jit(f)/{part}/sin" in compiled.as_text()


def test_report_puts_the_tiles_beside_the_region():
    tiles = "tiles-q64-k32-live6-visited8-copied4"
    table = device_trace.ScopeTable({
        "f": f"jit(train_step)/fwd-bwd/jvp(LM)/flash-fwd/{tiles}/"
             "pallas_call",
        "f2": f"jit(train_step)/fwd-bwd/jvp(LM)/flash-fwd/{tiles}/"
              "pallas_call",
        "g": "jit(train_step)/fwd-bwd/transpose(jvp(LM))/flash-bwd-dq/"
             "tiles-q32-k32-live10-visited16-copied9/pallas_call",
        "a": "jit(train_step)/fwd-bwd/jvp(LM)/mul",
    }, program="jit_train_step")
    assert table.tiles == {
        "flash-fwd": [{"block_q": 64, "block_k": 32, "live": 6,
                       "visited": 8, "copied": 4}],
        "flash-bwd-dq": [{"block_q": 32, "block_k": 32, "live": 10,
                          "visited": 16, "copied": 9}],
    }
    devices = [{"name": "/device:TPU:0",
                "ops": [("f", 0.0, 1.0), ("g", 1.0, 3.0)],
                "modules": [("jit_train_step(1)", 0.0, 3.0)]}]
    row = device_trace.report_from(
        devices, [], {"train_step": table})["programs"]["train_step"]
    assert row["region_ms"] == pytest.approx(
        {"flash-fwd": 1000.0, "flash-bwd-dq": 2000.0})
    assert row["region_tiles"] == table.tiles


def test_compiled_step_carries_the_flash_geometry(tiny_step):
    """The three kernels' blocks and tile census ride in the compiled
    step's own paths: a capture shows them whether or not the step was
    traced under a telemetry sink."""
    step, params, state, feed = tiny_step
    table = device_trace.scope_table(
        step.lower(params, state, feed(0)).compile())
    assert set(table.tiles) == {"flash-fwd", "flash-bwd-dq",
                                "flash-bwd-dkv"}
    for found in table.tiles.values():
        assert len(found) == 1 and set(found[0]) == set(spans.TILE_FIELDS)
        assert 0 < found[0]["live"] <= found[0]["visited"]
        assert 0 < found[0]["copied"] <= found[0]["visited"]


# ------------------------------------------------------------------- capture
def test_capture_reports_and_hands_the_report_to_the_sinks(tmp_path,
                                                           tiny_step):
    """On the CPU the profiler writes no device plane: the report is
    empty but whole, the host annotations are in the capture, and the
    row round-trips through StepRecorder and ``tools.obs``."""
    from chainermn_tpu.tools import obs as obs_cli

    step, params, state, feed = tiny_step
    params, state = jax.tree.map(jnp.copy, (params, state))
    log = str(tmp_path / "steps.jsonl")
    reporter = obs.Reporter()
    with obs.scope(reporter), obs.StepRecorder(log) as recorder:
        with device_trace.capture({"train_step": step},
                                  logdir=str(tmp_path / "trace")) as cap:
            for i in range(2):
                params, state, loss = cap["train_step"](
                    params, state, feed(i))
            jax.block_until_ready(loss)
        recorder.step(step=0)
    assert cap.report["devices"] == 0 and cap.report["programs"] == {}
    (found,) = (tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb")
    _, host = device_trace.read_capture(str(found))
    assert {"chainermn:train_step", "chainermn:global_batch"} <= {
        name for name, _, _ in host}
    rows = [r for r in obs.read_records(log)
            if r["event"] == "device_profile"]
    assert len(rows) == 1 and rows[0]["programs"] == {}
    # a report with programs in it, through the same sinks
    report = device_trace.report_from(
        [{"name": "/device:TPU:0", "ops": [("a", 0.0, 1.0), ("u", 1.0, 1.5)],
          "modules": [("jit_train_step(7)", 0.0, 1.5)]}], [],
        {"train_step": _table()})
    with obs.scope(reporter), obs.StepRecorder(log):
        device_trace.publish(report)
    scalars = reporter.summary()["scalars"]
    assert scalars["device/train_step/fwd-bwd_ms"]["last"] == (
        pytest.approx(1000.0))
    summary = obs_cli.summarize(list(obs.read_records(log)))
    assert summary["device_profile"] == json.loads(json.dumps(report))
    assert summary["events"]["device_profile"] == 2


def test_capture_leaves_no_session_behind_when_the_body_raises(tmp_path):
    with pytest.raises(RuntimeError, match="boom"):
        with device_trace.capture({}, logdir=str(tmp_path)):
            raise RuntimeError("boom")
    with device_trace.capture({}, logdir=str(tmp_path)) as cap:
        pass                         # a new session starts: none was open
    assert cap.report["programs"] == {}


# ------------------------------------------------- the fixture from the chip
def test_join_holds_on_the_capture_recorded_on_the_chip():
    devices, host = device_trace.read_capture(
        os.path.join(DATA, "tiny_step.xplane.pb.gz"))
    with gzip.open(os.path.join(DATA, "tiny_step.hlo.txt.gz"), "rt") as f:
        table = device_trace.scope_table(f.read())
    assert table.program == "jit_train_step" and len(devices) == 1
    tool = _recorder_tool()
    report = device_trace.report_from(devices, host, {"train_step": table})
    row = report["programs"]["train_step"]
    assert row["calls"] == tool.STEPS
    assert row["joined_share"] > 0.999
    assert 0 <= row["mixed_ms"] < row["busy_ms"]
    # What joins to no phase is `copy` / `copy-done` ops the compiler gave
    # no op_name: 3.6% of this tiny step, 0.76% of the 690 ms step of the
    # benchmark's cells (PERF.md section 6, PR 24).
    assert row["unattributed_ms"] < 0.05 * row["busy_ms"]
    assert sum(row["phase_ms"].values()) + row["unattributed_ms"] == (
        pytest.approx(row["busy_ms"], rel=1e-9))
    assert set(row["phase_ms"]) == set(spans.STEP_PHASES)
    assert {"flash-fwd", "flash-bwd-dq", "flash-bwd-dkv", "fused-ce",
            "grad-stage0", "grad-unpack"} <= set(row["region_ms"])
    assert row["phase_ms"]["fwd-bwd"] > row["phase_ms"]["opt-update"] > 0
    flash = sum(row["region_ms"][k] for k in
                ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv"))
    assert 0 < flash < row["phase_ms"]["fwd-bwd"]
    # a tiny step leaves the chip idle, under the host feeding the batch
    assert 0.3 < report["idle_share"] < 1.0
    assert any(k.startswith("chainermn:")
               for k in report["idle_by_host_span_ms"])
    assert {name for name, _, _ in host} >= {
        "chainermn:train_step", "chainermn:global_batch"}
