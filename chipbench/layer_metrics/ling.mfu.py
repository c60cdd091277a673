"""Model FLOPs (``flops_ling3.py``: nothing recomputed, no embedding
lookup, the delta rule in its chunked form, attention at 192 / 128 over
the triangle, the held experts at their expected load) over the device's
time for a traced step — the traced slice's first program start to its
last program end, idle gaps included, over its steps — over chips x peak.
From the device's clock, as ``qnext.mfu`` is."""

from chipbench import flops, flops_ling3


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.window_s:
        return None
    step_s = trace.window_s / ctx["trace_steps"]
    peak = flops.peaks(ctx["device_kind"])["bf16_flops"]
    rate = flops_ling3.train_flops_per_step(
        ctx["config"], ctx["mix"]) / step_s
    return 100.0 * rate / (len(ctx["devices"]) * peak)
