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
    # (the flash backward is one pass under flash-bwd-dkv: PR 48)
    assert {"flash-fwd", "flash-bwd-dkv", "fused-ce",
            "grad-stage0", "grad-unpack"} <= regions
    assert "flash-bwd-dq" not in regions
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


@pytest.mark.parametrize("event,label", sorted(
    spans.COMPILE_HOST_EVENTS.items()))
def test_a_gap_a_compilation_covers_is_called_that(event, label):
    """A recompilation inside a captured step: the gap lies under
    ``chainermn:train_step`` whole and under JAX's own compile-stage
    event nearly whole; the innermost of the two names it."""
    ops = [("a", 0.0, 1.0), ("a", 9.0, 10.0), ("a", 10.5, 11.0),
           ("a", 14.0, 15.0)]
    host = [("chainermn:train_step", 0.9, 9.1), (event, 1.4, 8.9),
            ("PjitFunction(train_step)", 0.95, 9.05),   # not declared
            ("chainermn:train_step", 10.0, 10.6),
            # a stage that covers under half of a gap does not take it
            ("chainermn:global_batch", 10.9, 14.1), (event, 11.0, 12.0)]
    got = device_trace.idle_by_host_span(ops, host)
    assert got == pytest.approx({
        label: 8.0, "chainermn:train_step": 0.5,
        "chainermn:global_batch": 3.0})
    assert spans.host_label(event) == label
    assert spans.host_label("chainermn:decode") == "chainermn:decode"
    assert spans.host_label("PjitFunction(train_step)") is None


def test_an_op_that_ends_a_nanosecond_past_its_program_is_the_programs():
    """``(start_ns + duration_ns) * 1e-9`` of an op and of its module's
    event round apart: a program's LAST op stays its program's."""
    ops = [("a", 0.0, 1.0), ("u", 1.0, 1.5 + 1e-9),
           ("a", 2.0, 3.0), ("u", 3.0, 3.5),
           ("stray", 3.6, 3.7)]          # far past the run: nobody's
    devices = [{"name": "/device:TPU:0", "ops": ops,
                "modules": [("jit_train_step(7)", 0.0, 1.5),
                            ("jit_train_step(7)", 2.0, 3.5)]}]
    row = device_trace.report_from(
        devices, [], {"train_step": _table()})["programs"]["train_step"]
    assert row["calls"] == 2
    assert row["phase_ms"] == pytest.approx(
        {"fwd-bwd": 1000.0, "opt-update": 500.0})
    assert row["busy_ms"] == pytest.approx(1500.0)


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
    """The kernels' blocks and tile census ride in the compiled step's
    own paths: a capture shows them whether or not the step was traced
    under a telemetry sink.  (Two kernels since PR 48: the backward is
    one pass on dq's grid, under ``flash-bwd-dkv``.)"""
    step, params, state, feed = tiny_step
    table = device_trace.scope_table(
        step.lower(params, state, feed(0)).compile())
    assert set(table.tiles) == {"flash-fwd", "flash-bwd-dkv"}
    for found in table.tiles.values():
        assert len(found) == 1 and set(found[0]) == set(spans.TILE_FIELDS)
        assert 0 < found[0]["live"] <= found[0]["visited"]
        assert 0 < found[0]["copied"] <= found[0]["visited"]


# --------------------------------------------------------------- the owners
def test_the_models_parts_are_in_the_vocabulary():
    assert spans.MODEL_PARTS == (
        "embed", "norm", "residual", "attn-mixer", "attn-window",
        "attn-blockdiff", "mixer-proj", "mixer-gate", "ffn")
    assert not set(spans.MODEL_PARTS) & set(spans.KERNEL_REGIONS)
    for name in spans.MODEL_PARTS:
        with spans.named_scope(name):
            pass
        assert spans.is_scope(name) and not spans.is_region(name)
    for name in spans.KERNEL_REGIONS + ("grad-pack", "grad-stage3"):
        assert spans.is_region(name)
    for name in ("mlp", "attn", "ffn-wi", "mixer", "layer_0", "fwd-bwd/ffn"):
        with pytest.raises(ValueError, match="scope vocabulary"):
            spans.named_scope(name)


LM = "jit(train_step)/fwd-bwd/jvp(TransformerLM)"
BACK = ("jit(train_step)/fwd-bwd/transpose(jvp(TransformerLM))/"
        "jvp(TransformerLM)/checkpoint")


@pytest.mark.parametrize("path,region,owned_by", [
    (f"{LM}/layer_0/Mamba2Mixer_0/mamba-mixer/mixer-proj/in_proj/"
     "dot_general", "mamba-mixer", "mixer-proj"),
    (f"{LM}/layer_0/Mamba2Mixer_0/mamba-mixer/mixer-gate/mul",
     "mamba-mixer", "mixer-gate"),
    (f"{LM}/layer_1/ExpertLayer_0/moe-layer/moe-shared/shared/wi/"
     "dot_general", "moe-shared", "moe-shared"),
    (f"{LM}/layer_1/norm/RMSNorm_1/rsqrt", None, "norm"),
    (f"{LM}/layer_2/attn-mixer/MultiHeadAttention_0/flash-fwd/flash-fwd",
     "flash-fwd", "flash-fwd"),
    (f"{LM}/layer_2/attn-mixer/MultiHeadAttention_0/mixer-proj/out/"
     "dot_general", None, "mixer-proj"),
    (f"{LM}/layer_2/attn-mixer/MultiHeadAttention_0/transpose", None,
     "attn-mixer"),
    (f"{LM}/embed/embed/jit(_take)/gather", None, "embed"),
    (f"{LM}/layer_0/ffn/FeedForward_0/wi/dot_general", None, "ffn"),
    (f"{LM}/div", None, None),
])
def test_the_region_reading_does_not_see_the_parts(path, region, owned_by):
    assert device_trace.classify(path) == ("fwd-bwd", region)
    assert device_trace.owner(path) == ("fwd-bwd", owned_by)


@pytest.mark.parametrize("path,which,layer", [
    (f"{LM}/layer_3/ffn/FeedForward_0/wi/dot_general", "forward", "3"),
    (f"{BACK}/layer_11/ffn/FeedForward_0/wi/dot_general", "backward", "11"),
    (f"{BACK}/rematted_computation/layer_0/norm/LayerNorm_1/rsqrt",
     "recompute", "0"),
    ("jit(train_step)/fwd-bwd/transpose(jvp(TransformerLM))/fwd-bwd/"
     "jvp(TransformerLM)/remat2", "backward", None),
    (f"{LM}/final_norm/mul", "forward", None),
    (f"{LM}/player_1/my_layer_2/mul", "forward", None),
])
def test_pass_and_layer_come_from_the_paths_wrappers(path, which, layer):
    assert device_trace.pass_of(path) == which
    assert device_trace.layer_of(path) == layer


def _owners_text():
    """Five fusions and two copies of a made-up step: a dot under ``ffn``
    with a residual add behind it; two elementwise ops of which the norm's
    is the larger; a weight-gradient dot with the update fused in; a dot
    in a NESTED fusion; a copy the compiler gave no path, before its one
    consumer; a prefetch named after an argument, read by two."""
    ffn = f"{LM}/layer_0/ffn/FeedForward_0/wo/dot_general"
    res = f"{LM}/layer_0/residual/add"
    nrm = f"{LM}/layer_1/norm/LayerNorm_0/mul"
    wgrad = (f"{BACK}/layer_0/attn-mixer/MultiHeadAttention_0/mixer-proj/"
             "query/dot_general")
    upd = "jit(train_step)/opt-update/add"
    gate = f"{BACK}/rematted_computation/layer_2/mixer-gate/mul"

    def meta(path):
        return f'metadata={{op_name="{path}"}}'

    return "\n".join([
        "HloModule jit_train_step",
        "%fc.dot (p: bf16[8,8]) -> bf16[8,8] {",
        "  %p.0 = bf16[8,8]{1,0} parameter(0), " + meta("args[0]['w']"),
        f"  %d.0 = bf16[8,8]{{1,0}} convolution(%p.0, %p.0), {meta(ffn)}",
        "  %zero = bf16[8,8]{1,0} constant(0), " + meta(upd),
        f"  ROOT %a.0 = f32[8,8]{{1,0}} add(%d.0, %zero), {meta(res)}",
        "}",
        "%fc.loop (p: f32[8,8]) -> f32[8,8] {",
        "  %p.1 = f32[8,8]{1,0} parameter(0)",
        f"  %m.1 = f32[8,8]{{1,0}} multiply(%p.1, %p.1), {meta(nrm)}",
        f"  ROOT %a.1 = bf16[8,8]{{1,0}} add(%m.1, %m.1), {meta(res)}",
        "}",
        "%fc.wgrad (p: f32[8,8]) -> f32[8,8] {",
        "  %p.2 = f32[8,8]{1,0} parameter(0)",
        f"  %d.2 = f32[8,8]{{1,0}} convolution(%p.2, %p.2), {meta(wgrad)}",
        f"  ROOT %a.2 = f32[8,8]{{1,0}} add(%d.2, %p.2), {meta(upd)}",
        "}",
        "%fc.inner (p: f32[8,8]) -> f32[8,8] {",
        "  %p.3 = f32[8,8]{1,0} parameter(0)",
        f"  ROOT %d.3 = f32[4,4]{{1,0}} convolution(%p.3, %p.3), {meta(ffn)}",
        "}",
        "%fc.outer (p: f32[8,8]) -> f32[8,8] {",
        "  %p.4 = f32[8,8]{1,0} parameter(0)",
        "  %nested = f32[4,4]{1,0} fusion(%p.4), kind=kOutput, "
        f"calls=%fc.inner, {meta(ffn)}",
        f"  ROOT %m.4 = f32[8,8]{{1,0}} multiply(%p.4, %p.4), {meta(gate)}",
        "}",
        "%fc.plain (p: f32[8,8]) -> f32[8,8] {",
        "  %p.5 = f32[8,8]{1,0} parameter(0)",
        "  %big = f32[64,64]{1,0} broadcast(%p.5), "
        + meta("jit(_take_call)/moe-dispatch/gather"),    # no phase
        f"  ROOT %m.5 = f32[8,8]{{1,0}} multiply(%p.5, %p.5), {meta(gate)}",
        "}",
        "ENTRY %main (x: f32[8,8], w: f32[8,8]) -> f32[8,8] {",
        "  %x = f32[8,8]{1,0} parameter(0), " + meta("x"),
        "  %w = f32[8,8]{1,0} parameter(1), " + meta("args[0]['w']"),
        "  %copy.9 = f32[8,8]{0,1} copy(%x)",
        "  %prefetch = f32[8,8]{1,0:S(1)} copy(%w), "
        + meta("args[0]['w']"),
        f"  %dot_add = f32[8,8]{{1,0}} fusion(%copy.9), kind=kOutput, "
        f"calls=%fc.dot, {meta(res)}",
        f"  %loop = bf16[8,8]{{1,0}} fusion(%dot_add), kind=kLoop, "
        f"calls=%fc.loop, {meta(res)}",
        f"  %wgrad = f32[8,8]{{1,0}} fusion(%prefetch), kind=kOutput, "
        f"calls=%fc.wgrad, {meta(upd)}",
        f"  %outer = f32[8,8]{{1,0}} fusion(%prefetch), kind=kOutput, "
        f"calls=%fc.outer, {meta(gate)}",
        f"  ROOT %plain = f32[8,8]{{1,0}} fusion(%outer), kind=kLoop, "
        f"calls=%fc.plain, {meta(gate)}",
        "}",
    ])


@pytest.mark.parametrize("name,owned_by,n_owners,inherited", [
    ("dot_add", ("fwd-bwd", "ffn"), 2, False),      # the dot, not the root
    ("loop", ("fwd-bwd", "norm"), 2, False),        # the largest result
    ("wgrad", ("fwd-bwd", "mixer-proj"), 2, False),  # not the update's
    ("outer", ("fwd-bwd", "ffn"), 2, False),        # the nested dot
    ("plain", ("fwd-bwd", "mixer-gate"), 1, False),  # not the phase-less
    ("copy.9", ("fwd-bwd", "ffn"), 0, True),        # its one consumer's
    ("prefetch", ("fwd-bwd", "mixer-proj"), 0, True),   # its first's
])
def test_a_fusion_is_owned_by_its_heaviest_op(name, owned_by, n_owners,
                                              inherited):
    table = device_trace.scope_table(_owners_text())
    assert device_trace.owner(table.owner_path(name)) == owned_by
    assert len(table.owners_in.get(name, ())) == n_owners
    assert (name in table.inherited) == inherited
    # the phase and region readings keep the fusion's own name
    assert table["dot_add"].endswith("residual/add")
    assert table["copy.9"] == ""
    # ... and ``mixed`` reads a fusion's own computation, constants too
    assert table.mixed == {"dot_add", "wgrad"}


def test_owners_partition_the_phase_and_say_what_they_cannot_split():
    table = device_trace.scope_table(_owners_text())
    ops = [("copy.9", 0.0, 1.0), ("dot_add", 1.0, 4.0), ("loop", 4.0, 6.0),
           ("prefetch", 5.0, 5.5), ("wgrad", 6.0, 10.0),
           ("outer", 10.0, 11.0), ("plain", 11.0, 11.5),
           ("stranger", 11.5, 12.0)]
    got = device_trace.attribute(ops, table)
    assert got["owner"] == pytest.approx({
        "ffn": 1 + 3 + 1, "norm": 1.5, "mixer-proj": 0.5 + 4,
        "mixer-gate": 0.5})
    assert sum(got["owner"].values()) == pytest.approx(11.5)
    # every fusion with a second owner's ops in it, by its owner
    assert got["shared"] == pytest.approx(
        {"ffn": 3 + 1, "norm": 1.5, "mixer-proj": 4})
    assert got["shared_fusions"] == pytest.approx(
        {"dot_add": 3, "loop": 1.5, "wgrad": 4, "outer": 1})
    assert got["inherited"] == pytest.approx(1.5)
    assert got["unowned"] == pytest.approx(0.5)          # the stranger
    assert got["pass"]["forward"] == pytest.approx({"ffn": 5, "norm": 1.5})
    assert got["pass"]["backward"] == pytest.approx({"mixer-proj": 4.5})
    assert got["pass"]["recompute"] == pytest.approx({"mixer-gate": 0.5})
    assert got["layer"] == pytest.approx({"0": 9.5, "1": 1.5, "2": 0.5})
    # the old readings, by the fusions' own names: the update owns the
    # weight gradient's 4 s and the gate the nested dot's second
    assert got["phase"] == pytest.approx({"fwd-bwd": 6.0, "opt-update": 4})
    assert got["region"] == {} and got["mixed"] == pytest.approx(3 + 4)
    assert got["unattributed"] == pytest.approx(1 + 0.5 + 0.5)


def test_the_report_carries_the_owner_reading_to_the_sinks():
    table = device_trace.scope_table(_owners_text())
    devices = [{"name": "/device:TPU:0", "modules": [
        ("jit_train_step(1)", 0.0, 6.0), ("jit_train_step(1)", 6.0, 12.0)],
        "ops": [("dot_add", 0.0, 3.0), ("loop", 3.0, 4.0),
                ("stranger", 4.0, 4.5), ("dot_add", 6.0, 9.0),
                ("plain", 9.0, 10.0)]}]
    report = device_trace.report_from(devices, [], {"train_step": table})
    row = report["programs"]["train_step"]
    assert row["owner_ms"] == pytest.approx(
        {"ffn": 3000.0, "norm": 500.0, "mixer-gate": 500.0})
    assert row["shared_ms"] == pytest.approx({"ffn": 3000.0, "norm": 500.0})
    assert row["pass_ms"] == {
        "forward": pytest.approx({"ffn": 3000.0, "norm": 500.0}),
        "recompute": pytest.approx({"mixer-gate": 500.0}), "backward": {}}
    assert row["layer_ms"] == pytest.approx(
        {"0": 3000.0, "1": 500.0, "2": 500.0})
    assert list(row["layer_ms"]) == ["0", "1", "2"]
    assert row["inherited_ms"] == 0 and row["unowned_ms"] == 250.0
    assert row["shared_fusions"] == [
        {"fusion": "dot_add", "owner": "ffn", "count": 1, "ms": 3000.0,
         "owners": ["fwd-bwd/ffn", "fwd-bwd/residual"]},
        {"fusion": "loop", "owner": "norm", "count": 1, "ms": 500.0,
         "owners": ["fwd-bwd/norm", "fwd-bwd/residual"]}]
    reporter = obs.Reporter()
    with obs.scope(reporter):
        device_trace.publish(report)
    scalars = reporter.summary()["scalars"]
    assert scalars["device/train_step/owner/ffn_ms"]["last"] == 3000.0
    assert scalars["device/train_step/fwd-bwd_ms"]["last"] == 4000.0
    json.dumps(report)                       # a row of the step log


def _tiny_lm(kind):
    from chainermn_tpu.models.block_table import (
        BlockTable, CCASpec, ExpertsSpec, LayerSpec, SSMSpec)
    from chainermn_tpu.models.transformer import TransformerLM

    if kind in ("attention", "remat"):
        return TransformerLM(vocab=64, d_model=32, n_heads=2, d_ff=64,
                             n_layers=2, max_len=32, remat=kind == "remat")
    row = {
        "mamba2": LayerSpec(
            mixer="mamba2", norm="rmsnorm", ffn="swiglu", d_ff=64,
            ssm=SSMSpec(n_heads=4, d_head=16, d_state=16, chunk=16),
            residual_multiplier=0.5),
        "cca": LayerSpec(
            mixer="cca", norm="rmsnorm", ffn="swiglu", d_ff=64,
            cca=CCASpec(n_heads=4, n_kv_heads=2, d_head=16, rotary_dim=8)),
        "experts": LayerSpec(
            mixer="attention", norm="rmsnorm", ffn="experts", n_heads=2,
            experts=ExpertsSpec(n_experts=8, top_k=2, d_expert=16,
                                d_shared=32)),
    }[kind]
    table = BlockTable(
        layers=(row, row), final_norm="rmsnorm", embedding_multiplier=2.0,
        positions="rotary" if kind == "cca" else "none")
    return TransformerLM(vocab=64, d_model=32, table=table, max_len=32,
                         remat=True)


@pytest.mark.parametrize("kind,parts,regions", [
    ("attention", ("attn-mixer", "mixer-proj", "ffn"), ()),
    ("remat", ("attn-mixer", "mixer-proj", "ffn"), ()),
    ("mamba2", ("mixer-proj", "mixer-gate", "ffn"),
     ("mamba-mixer", "ssd-scan", "ssm-conv")),
    ("cca", ("mixer-proj", "ffn"), ("cca-mixer", "cca-conv", "cca-rope")),
    ("experts", ("attn-mixer", "mixer-proj"),
     ("moe-route", "moe-dispatch", "moe-experts", "moe-shared")),
])
def test_every_part_of_a_compiled_model_has_an_owner(kind, parts, regions):
    """A tiny ``TransformerLM`` of each mixer kind through
    ``make_train_step``, compiled on the CPU: the new names reach the
    compiled text, nearly every ``fwd-bwd`` instruction has an owner, and
    the passes are told apart."""
    import re

    import optax

    import chainermn_tpu
    from chainermn_tpu.communicators import build_mesh
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    lm = _tiny_lm(kind)
    comm = chainermn_tpu.create_communicator("xla_ici", mesh=build_mesh(
        inter_size=1, intra_size=1, devices=jax.devices()[:1]))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = lm.init(jax.random.PRNGKey(0), tokens)["params"]

    def loss_fn(p, batch):
        h = lm.apply({"params": p}, batch[0], return_hidden=True)
        return fused_cross_entropy(h, p["embed"]["embedding"], batch[1],
                                   chunk=32)

    text = opt.make_train_step(loss_fn).lower(
        params, opt.init(params), (tokens, tokens)).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    components = {part for path in paths for part in path.split("/")}
    assert {"embed", "norm", "residual", *parts, *regions} <= components
    # the gated norm is the gate's, whatever flax calls the module
    assert not any("mixer-gate/norm" in path for path in paths)

    table = device_trace.scope_table(text)
    instructions = hlo_audit.hlo_instructions(text)
    fused = {c for i in instructions if i.opcode == "fusion"
             for c in i.operands}
    ran = [i for i in instructions
           if i.computation not in fused and i.name not in table.containers
           and i.opcode not in ("parameter", "constant", "tuple",
                                "get-tuple-element", "bitcast")]
    owned = [(device_trace.owner(table.owner_path(i.name)),
              device_trace.pass_of(table.owner_path(i.name))) for i in ran]
    in_phase = [(name, which) for (phase, name), which in owned
                if phase == "fwd-bwd"]
    assert len(in_phase) > 100
    named = [name for name, _ in in_phase if name is not None]
    assert len(named) >= 0.95 * len(in_phase)
    assert {"embed", "norm", "fused-ce", *parts, *regions} <= set(named)
    passes = {which for _, which in in_phase}
    assert passes == ({"forward", "backward"} if kind == "attention"
                      else {"forward", "recompute", "backward"})
    # the region reading is what it was: no part is a region
    assert not {device_trace.classify(p)[1] for p in paths} & set(
        spans.MODEL_PARTS)


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
    # the first captured call lowered its program: JAX's own compile-stage
    # events are kept beside the library's (``backend_compile_and_load``
    # too where the suite's cache missed: a hit's retrieval has no event),
    # and nothing else of the host's is
    assert all(spans.host_label(name) for name, _, _ in host)
    assert "lower_sharding_computation" in {name for name, _, _ in host}
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


#: What the parent of PR 34 read on the same fixture, ms a step: the
#: phase, region, unattributed, joined and mixed readings do not move
#: when the owner reading is added beside them.
PINNED = {
    "busy_ms": 0.08846499999998897,
    "unattributed_ms": 0.0031529999999548495,
    "mixed_ms": 0.0033233333333373047,
    "joined_share": 1.0,
    "phase_ms": {"allreduce": 0.013522000000001921,
                 "fwd-bwd": 0.0704543333333608,
                 "opt-update": 0.0013356666666713979},
    "region_ms": {"flash-bwd-dkv": 0.007597999999997551,
                  "flash-bwd-dq": 0.005720333333338508,
                  "flash-fwd": 0.008780999999999373,
                  "fused-ce": 0.02293133333335316,
                  "grad-stage0": 0.008990333333333544,
                  "grad-unpack": 0.004531666666668377},
}


@pytest.fixture(scope="module")
def chip_row():
    devices, host = device_trace.read_capture(
        os.path.join(DATA, "tiny_step.xplane.pb.gz"))
    with gzip.open(os.path.join(DATA, "tiny_step.hlo.txt.gz"), "rt") as f:
        table = device_trace.scope_table(f.read())
    report = device_trace.report_from(devices, host, {"train_step": table})
    return report["programs"]["train_step"]


@pytest.mark.parametrize("key", sorted(PINNED))
def test_the_old_readings_are_the_parents_on_the_chip_fixture(chip_row, key):
    assert chip_row[key] == pytest.approx(PINNED[key], rel=1e-12, abs=0)


def test_the_owner_reading_on_the_chip_fixture(chip_row):
    """The fixture was recorded before the model's parts had names (a
    flax module called ``embed`` is the one that shows): the kernel
    regions own what they own in the region reading or more (a copy
    beside a kernel inherits it), the rest of ``fwd-bwd`` is ``(none)``,
    and the readings add up."""
    owners = chip_row["owner_ms"]
    assert set(owners) >= {"(none)", "flash-fwd", "flash-bwd-dq",
                           "flash-bwd-dkv", "fused-ce"}
    assert set(owners) <= {"(none)", "embed"} | set(spans.KERNEL_REGIONS)
    for region in ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv"):
        assert owners[region] >= chip_row["region_ms"][region]
    by_pass = chip_row["pass_ms"]
    assert by_pass["recompute"] == {} and by_pass["backward"]
    assert sum(sum(p.values()) for p in by_pass.values()) == pytest.approx(
        sum(owners.values()), rel=1e-9)
    assert sum(chip_row["layer_ms"].values()) < sum(owners.values())
    assert set(chip_row["layer_ms"]) == {"0", "1"}
    # the copies the compiler gave no path found an owner
    assert chip_row["unowned_ms"] < chip_row["unattributed_ms"]
    assert 0 < chip_row["inherited_ms"] < 0.1 * chip_row["busy_ms"]
    assert sum(chip_row["shared_ms"].values()) < sum(owners.values())
    assert len(chip_row["shared_fusions"]) <= 10
