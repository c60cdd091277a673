"""The reduction from trace to numbers, on the small trace recorded on
the chip (``chipbench/tools/record_tiny_trace.py``: the tiny training run
of ``tiny.py``, three traced steps on one TPU v5 lite) and on made-up
intervals."""

import os

import pytest

from chipbench import harness, reducers, trace_reduce

TRACE = os.path.join(harness.HERE, "data", "tiny_train.xplane.pb.gz")
FLASH = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.TraceData.from_file(TRACE, n_devices=1)


def test_interval_arithmetic():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.union_length([]) == 0
    assert trace_reduce.merged([(1, 3), (0, 2), (5, 6)]) == [[0, 3], [5, 6]]
    # a collective from 0 to 10, compute covering 2..4 and 6..9
    assert trace_reduce.subtract_length(
        [(0, 10)], [(2, 4), (6, 9), (20, 30)]) == 5
    assert trace_reduce.subtract_length([(0, 1), (0.5, 2)], []) == 2


def test_names_are_reduced_to_instruction_and_opcode():
    text = ('%MultiHeadAttention_0.45 = (bf16[128,2048,128]{2,1,0:T(8,128)'
            '(2,1)}, f32[1]{0}) custom-call(bf16[1]{0} %bitcast.1), '
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.opcode(text) == "custom-call"
    assert trace_reduce.short_name(text) == (
        "%MultiHeadAttention_0 custom-call")
    loop = "%while.6 = (s32[]{:T(128)}, f32[2]{0}) while((s32[]) %t)"
    assert trace_reduce.opcode(loop) == "while"


def test_recorded_trace_window_and_busy(trace):
    assert len(trace.devices) == 1 and trace.programs == 3
    assert 0 < trace.busy_s < trace.window_s < 0.1
    assert 0.5 < trace.idle_share < 1.0     # a tiny step leaves the chip idle
    names = {h.name for h in trace.host}
    assert names == {"chipbench:global_batch", "chipbench:train_step",
                     "chipbench:wait_step"}


def test_recorded_trace_finds_the_flash_kernels(trace):
    flash = trace.select(FLASH)[0]
    # 2 layers x (forward, dq, dk/dv) x 3 traced steps
    assert len(flash) == 18
    assert all("MultiHeadAttention_0" in o.name for o in flash)
    sec = trace.seconds(FLASH)
    assert 0 < sec < trace.busy_s
    # kernels do not overlap other ops on the device's op line
    assert trace.exposed_seconds(FLASH) == pytest.approx(sec, rel=0.05)
    assert trace.seconds("no-such-op") is None
    assert trace.seconds(r"\sall-reduce(-start|-done)?\(") is None


def test_breakdown_shape(trace):
    b = trace.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])
    assert not any(" while" in n for n, _ in b["device_ops"])
    # the idle time of a tiny step sits under the host feeding the batch
    assert b["idle_gaps"][0][0].startswith("chipbench:")


def test_readers_return_numbers_or_nothing(trace):
    ctx = {"trace": trace, "trace_steps": 3}
    ms = reducers.trace_ms_per_step(ctx, FLASH)
    assert ms == pytest.approx(trace.seconds(FLASH) / 3 * 1e3)
    assert reducers.trace_ms_per_step(ctx, "all-reduce") is None
    assert 50 < reducers.idle_share_pct(ctx) < 100
    assert reducers.trace_ms_per_step({"trace": None}, FLASH) is None


def test_mfu_reads_the_steps_outside_the_profiler_slice():
    from chipbench import weights
    from chipbench.tests import tiny

    ctx = {"mix": tiny.TRAIN_MIX, "config": tiny.TRAIN_CONFIG,
           "device_kind": "TPU v5 lite", "devices": [0],
           "n_params": weights.n_params(tiny.TRAIN_CONFIG),
           "step_ms": 9.0, "clear_step_ms": 3.0}
    slow = reducers.train_mfu_pct(dict(ctx, step_ms=90.0))
    assert slow == reducers.train_mfu_pct(ctx) > 0
    assert reducers.train_mfu_pct(dict(ctx, clear_step_ms=6.0)) == (
        pytest.approx(slow / 2))
    with pytest.raises(RuntimeError, match="no peaks recorded"):
        reducers.train_mfu_pct(dict(ctx, device_kind="no such chip"))
