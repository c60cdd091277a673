"""Model FLOPs (``train_flops_per_step`` of the configuration's flops
module: nothing recomputed, the held experts at their expected load) over
the device's time for a traced step — the traced slice's first program
start to its last program end, idle gaps between the programs included,
over its steps — over chips x peak.  From the device's clock, not from
the window's steps outside the slice as ``model.mfu`` is: stopping the
profiler on a trace of hundreds of thousands of loop-body events takes
seconds, and the steps around it were read at twice their untraced time
(PERF.md section 6, PR 26)."""

from chipbench import flops


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.window_s:
        return None
    step_s = trace.window_s / ctx["trace_steps"]
    peak = flops.peaks(ctx["device_kind"])["bf16_flops"]
    rate = flops.family(ctx["config"]).train_flops_per_step(
        ctx["config"], ctx["mix"]) / step_s
    return 100.0 * rate / (len(ctx["devices"]) * peak)
