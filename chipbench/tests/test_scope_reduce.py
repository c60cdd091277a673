"""The per-layer readers that read device time by program scope
(``chipbench/scope_reduce.py``): on the capture recorded on the chip with
its step's ``as_text()`` (``tests/data/record_scope_fixture.py``: a tiny
step, three traced steps on one TPU v5 lite), on the cell's step built
once more on the CPU, and on made-up ops of four devices."""

import gzip
import os

import pytest

from chipbench import harness, scope_reduce, trace_reduce

device_trace = pytest.importorskip(
    "chainermn_tpu.observability.device_trace")

DATA = os.path.join(harness.ROOT, "tests", "data")
NEW = ("step.fwd_bwd_ms", "step.opt_update_ms", "comm.pack_ms",
       "kernel.fused_ce_ms", "kernel.flash_fwd_ms", "kernel.flash_bwd_ms",
       "trace.unattributed_pct")


def read(name, ctx):
    return harness.layer_reader(name)(ctx)


@pytest.fixture(scope="module")
def recorded():
    trace = trace_reduce.TraceData.from_file(
        os.path.join(DATA, "tiny_step.xplane.pb.gz"), n_devices=1)
    with gzip.open(os.path.join(DATA, "tiny_step.hlo.txt.gz"), "rt") as f:
        table = device_trace.scope_table(f.read())
    return {"trace": trace, "trace_steps": 3, "scope_table": table}


@pytest.mark.parametrize("name", NEW)
def test_each_reader_on_the_recorded_capture(recorded, name):
    ctx = dict(recorded)
    value = read(name, ctx)
    busy_ms = _busy_ms(ctx)
    if name == "comm.pack_ms":
        # one chip: the allreduce phase is pack and unpack only
        assert 0 < value < 0.5 * busy_ms
        assert value == pytest.approx(
            scope_reduce.phase_ms(ctx, "allreduce"))
    elif name == "trace.unattributed_pct":
        # copies the compiler gave no op_name: 3.6% of this tiny step
        # (0.76% of the cells' 690 ms step, PERF.md section 6, PR 24)
        assert 0 <= value < 5.0
    else:
        assert 0 < value < busy_ms


def _busy_ms(ctx):
    """Busy ms a step as the attribution counts it: the union of the ops
    that are no containers (``TraceData.busy_s`` also counts the time a
    ``while`` spends between the ops of its body)."""
    (got,) = scope_reduce.attribution(ctx)["all"]
    return got["busy"] / ctx["trace_steps"] * 1e3


def test_the_readings_add_up_on_the_recorded_capture(recorded):
    ctx = dict(recorded)
    busy_ms = _busy_ms(ctx)
    assert busy_ms == pytest.approx(ctx["trace"].busy_s / 3 * 1e3, rel=0.1)
    phases = sum(scope_reduce.phase_ms(ctx, p)
                 for p in ("fwd-bwd", "allreduce", "opt-update"))
    loose = read("trace.unattributed_pct", ctx) / 100 * busy_ms
    assert phases + loose == pytest.approx(busy_ms, rel=1e-6)
    # the two flash readings are the old one, cut in two
    flash = trace_reduce.TraceData.seconds(
        ctx["trace"], 'custom_call_target="tpu_custom_call"') / 3 * 1e3
    assert (read("kernel.flash_fwd_ms", ctx)
            + read("kernel.flash_bwd_ms", ctx)) == pytest.approx(
                flash, rel=1e-6)
    assert read("kernel.fused_ce_ms", ctx) < read("step.fwd_bwd_ms", ctx)


def _op(name, start, end):
    return trace_reduce.Op(name, start, end, name)


def _made_up(n_devices, stranger=0.0):
    table = device_trace.ScopeTable({
        "f": "jit(train_step)/fwd-bwd/jvp(LM)/dot",
        "p": "jit(train_step)/allreduce/grad-stage0/concatenate",
        "ar": "jit(train_step)/allreduce/grad-stage0/psum",
        "u": "jit(train_step)/opt-update/add",
    }, program="jit_train_step")
    devices = []
    for i in range(n_devices):
        ops = [_op("%f = f32[8] fusion(%x), kind=kLoop", 0.0, 6.0),
               _op("%p = f32[8] fusion(%g), kind=kLoop", 6.0, 6.5 + 0.1 * i),
               _op("%ar = f32[8] all-reduce(%p), replica_groups={}",
                   7.0, 8.0),
               _op("%u = f32[8] fusion(%ar), kind=kLoop", 8.0, 10.0)]
        if stranger:
            ops.append(_op("%stranger = f32[8] copy(%x)", 10.0,
                           10.0 + stranger))
        devices.append({"name": f"/device:TPU:{i}", "ops": ops,
                        "modules": [_op("jit_train_step(1)", 0.0, 11.0)]})
    return {"trace": trace_reduce.TraceData(devices, []), "trace_steps": 2,
            "scope_table": table}


def test_pack_is_the_allreduce_phase_less_the_collectives():
    ctx = _made_up(4)
    # per device 0.5 + 0.1 i seconds of pack and 1 s of all-reduce
    assert read("comm.pack_ms", ctx) == pytest.approx(
        (0.5 + 0.15) / 2 * 1e3)
    assert scope_reduce.phase_ms(ctx, "allreduce") == pytest.approx(
        (1.5 + 0.15) / 2 * 1e3)
    assert read("step.fwd_bwd_ms", ctx) == pytest.approx(3000.0)
    assert read("step.opt_update_ms", ctx) == pytest.approx(1000.0)
    assert read("trace.unattributed_pct", ctx) == 0
    assert read("kernel.fused_ce_ms", ctx) is None     # no such region


def test_the_exchange_as_a_ring_of_collective_permutes():
    """PR 45's exchange: the hops are ``collective-permute-start`` /
    ``-done`` pairs laid under ``fwd-bwd``; the wait sits in ``-done``,
    the ring's adds and write-backs are the phase's local work."""
    table = device_trace.ScopeTable({
        "f": "jit(train_step)/fwd-bwd/jvp(LM)/dot",
        "s": "jit(train_step)/allreduce/grad-stage0/ppermute",
        "d": "jit(train_step)/allreduce/grad-stage0/ppermute",
        "a": "jit(train_step)/allreduce/grad-stage0/add",
        "w": "jit(train_step)/allreduce/grad-stage0/dynamic_update_slice",
        "ar": "jit(train_step)/allreduce/psum",
    }, program="jit_train_step")
    ops = [_op("%s = (f32[8]) collective-permute-start(%g)", 0.0, 0.1),
           _op("%f = f32[8] fusion(%x), kind=kLoop", 0.1, 4.1),
           _op("%d = f32[8] collective-permute-done(%s)", 4.1, 4.6),
           _op("%a = f32[8] fusion(%d, %g), kind=kLoop", 4.6, 5.0),
           _op("%w = f32[8] dynamic-update-slice(%b, %a)", 5.0, 5.8),
           _op("%ar = f32[] all-reduce(%l), replica_groups={}", 5.8, 6.0)]
    ctx = {"trace": trace_reduce.TraceData(
        [{"name": "/device:TPU:0", "ops": ops,
          "modules": [_op("jit_train_step(1)", 0.0, 6.0)]}], []),
        "trace_steps": 2, "scope_table": table}
    assert read("comm.exchange_ms", ctx) == pytest.approx(400.0)
    assert read("comm.exposed_ms", ctx) == pytest.approx(400.0)
    assert read("comm.pack_ms", ctx) == pytest.approx(600.0)
    assert scope_reduce.phase_ms(ctx, "allreduce") == pytest.approx(1000.0)
    # a hop an op of another stream covers is not exposed
    ops.append(_op("%f2 = f32[8] fusion(%x), kind=kLoop", 4.1, 4.4))
    ctx = dict(ctx, trace=trace_reduce.TraceData(
        [{"name": "/device:TPU:0", "ops": ops,
          "modules": [_op("jit_train_step(1)", 0.0, 6.0)]}], []))
    ctx.pop("_scope_reduce", None)
    assert read("comm.exposed_ms", ctx) == pytest.approx(250.0)
    assert read("comm.exchange_ms", ctx) == pytest.approx(400.0)


def test_readers_refuse_a_slice_that_joins_under_98_percent():
    ok = _made_up(1, stranger=0.15)           # 9.5 of 9.65 s join: 98.4%
    assert read("trace.unattributed_pct", ok) == pytest.approx(
        100 * 0.15 / 9.65)
    refused = _made_up(1, stranger=0.25)      # 9.5 of 9.75 s: 97.4%
    for name in NEW:
        assert read(name, dict(refused)) is None, name
    assert refused.get("_scope_reduce", "unset") == "unset"


def test_readers_return_nothing_without_a_trace_or_without_the_program(
        monkeypatch):
    for name in NEW:
        assert read(name, {"trace": None}) is None
    monkeypatch.setattr(scope_reduce, "_device_trace", lambda: None)
    ctx = _made_up(1)                # a parent commit: no device_trace
    for name in NEW:
        assert read(name, ctx) is None


def test_the_cell_step_is_built_once_more_for_its_table():
    """On the CPU, at the tiny size: the table is the runner's own step's
    (``jit_train_step``, every phase and kernel region in it), and ops
    named by its instructions all join."""
    import jax

    from chipbench.tests import tiny

    ctx = {"config": tiny.TRAIN_CONFIG,
           "mix": dict(tiny.TRAIN_MIX, global_batch=4),
           "devices": jax.devices()[:2]}
    table = scope_reduce.build_table(ctx, device_trace)
    assert table.program == "jit_train_step"
    found = {device_trace.classify(path) for path in table.values()}
    assert {"fwd-bwd", "allreduce", "opt-update"} <= {p for p, _ in found}
    assert {"flash-fwd", "flash-bwd-dq", "flash-bwd-dkv", "fused-ce"} <= {
        r for _, r in found}
    names = [n for n, path in table.items()
             if path and n not in table.containers][:200]
    ops = [_op(f"%{n} = f32[8] fusion(%x)", float(i), i + 1.0)
           for i, n in enumerate(names)]
    ctx.update(trace_steps=1, trace=trace_reduce.TraceData(
        [{"name": "/device:TPU:0", "ops": ops, "modules": []}], []))
    assert read("trace.unattributed_pct", ctx) is not None
    assert ctx["scope_table"].program == "jit_train_step"
