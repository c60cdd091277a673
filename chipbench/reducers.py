"""Reducers the per-layer metric files name (``{"reducer": ..., "args":
...}`` in ``chipbench/layer_metrics/<metric>.json``).  Each takes the
run's ``layer_ctx`` and returns a number or, where there is nothing to
read, ``None``."""

from chipbench import flops, weights


def _per_step(ctx, seconds):
    if seconds is None:
        return None
    return seconds / ctx["trace_steps"] * 1e3


def trace_ms_per_step(ctx, pattern, exclude=None):
    """Device ms a step of the ops matching ``pattern``."""
    if ctx.get("trace") is None:
        return None
    return _per_step(ctx, ctx["trace"].seconds(pattern, exclude))


def trace_exposed_ms_per_step(ctx, pattern, exclude=None):
    if ctx.get("trace") is None:
        return None
    return _per_step(ctx, ctx["trace"].exposed_seconds(pattern, exclude))


def idle_share_pct(ctx):
    if ctx.get("trace") is None or not ctx["trace"].window_s:
        return None
    return 100.0 * ctx["trace"].idle_share


def train_mfu_pct(ctx):
    """Model FLOPs (copied arithmetic) x steps a second over chips x
    peak, from the step time of the window's steps outside the profiler's
    slice (the slice holds the profiler's start and stop, which are no
    work of the model's)."""
    mix, z = ctx["mix"], weights.sizes(ctx["config"])
    peak = flops.peaks(ctx["device_kind"])["bf16_flops"]
    per_token = flops.lm_train_flops_per_token(
        ctx["n_params"], int(mix["seq_len"]), z["d"], z["layers"])
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    rate = per_token * tokens / (ctx["clear_step_ms"] / 1e3)
    return 100.0 * rate / (len(ctx["devices"]) * peak)
