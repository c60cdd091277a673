"""Device time by program scope, for the per-layer readers that ask for
it: a step phase (``step.*``, ``comm.pack_ms``), kernel regions
(``kernel.*``, ``moe.*``, ``ssm.*``, ``cca.*`` and the one-cell mixers),
a region's share of its roofline, ``trace.unattributed_pct``.

The trace's op events are named by HLO instruction and carry no scope;
the compiled step does (``metadata={op_name="jit(train_step)/fwd-bwd/
..."}`` in ``compiled.as_text()``).  So the cell's step is built once
more from ``ctx["config"]``, ``ctx["mix"]`` and ``ctx["devices"]`` — the
runner's own ``TrainJob`` lowered with abstract parameters and state, no
weights are made — and the program's one attribution path
(``chainermn_tpu.observability.device_trace``: ``scope_table`` of the
compiled step, ``attribute`` over each device's ops) does the join.  It
runs after the window and after set-up, in ``--trace 1`` runs only: no
end-to-end metric sees it.

Every reader returns ``None`` where there is nothing sound to read: no
trace, a program without ``device_trace`` (a parent commit), or a slice
of whose busy time less than 98% joins to the table — an approximate
attribution is worse than none.
"""

import re

from chipbench import flops, harness, traffic, weights

#: The ops of the gradient exchange itself: the synchronous ``all-reduce``s
#: and, since the exchange is a ring (PR 45), the hops'
#: ``collective-permute-start`` / ``-done`` (the wait sits in ``-done``).
#: ``comm.exchange_ms`` and ``comm.exposed_ms`` name the same pattern.
EXCHANGE = re.compile(r"\s(all-reduce|collective-permute)(-start|-done)?\(")
#: The flash kernels' three regions.
FLASH = ("flash-fwd", "flash-bwd-dq", "flash-bwd-dkv")


def _device_trace():
    try:
        from chainermn_tpu.observability import device_trace
    except ImportError:
        return None
    return device_trace


def build_table(ctx, device_trace):
    """The scope table of the cell's step: the runner's job, lowered from
    abstract parameters and state and one real (small) batch placed as
    the window places it, so that the program is the window's own (its
    compilation is a cache hit)."""
    import jax

    from chipbench.runners.train import TrainJob

    config, mix = ctx["config"], ctx["mix"]
    job = TrainJob(config, mix, ctx["devices"])

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=job.replicated), tree)

    params = placed(jax.eval_shape(lambda: weights.make(config, 0)))
    state = placed(jax.eval_shape(job.opt.init, params))
    batch = job.comm.global_batch(
        traffic.train_batches(mix, config["vocab_size"], 0)(0))
    compiled = job.step_fn.lower(params, state, batch).compile()
    return device_trace.scope_table(compiled)


def attribution(ctx):
    """``{"all": [per device], "no_exchange": [per device]}`` of
    ``device_trace.attribute`` results, memoised in ``ctx``; ``None``
    where there is nothing sound to read."""
    if "_scope_reduce" in ctx:
        return ctx["_scope_reduce"]
    ctx["_scope_reduce"] = None
    device_trace = _device_trace()
    trace = ctx.get("trace")
    if device_trace is None or trace is None or not trace.devices:
        return None
    table = ctx.get("scope_table")
    if table is None:
        table = ctx["scope_table"] = build_table(ctx, device_trace)
    out = {"all": [], "no_exchange": []}
    for d in trace.devices:
        ops = [(o.name, o.start, o.end) for o in d["ops"]]
        got = device_trace.attribute(ops, table)
        if got["busy"] <= 0:
            return None
        share = got["joined"] / got["busy"]
        if share < device_trace.MIN_JOINED_SHARE:
            harness.log(
                f"scope_reduce: {100 * share:.2f}% of {d.get('name')}'s "
                "busy time joins to the compiled step's instructions, "
                f"under {100 * device_trace.MIN_JOINED_SHARE:.0f}%: "
                "nothing is reported")
            return None
        out["all"].append(got)
        out["no_exchange"].append(device_trace.attribute(
            [op for op in ops if not EXCHANGE.search(op[0])], table))
    ctx["_scope_reduce"] = out
    return out


def _ms_per_step(ctx, values):
    values = list(values)
    return sum(values) / len(values) / ctx["trace_steps"] * 1e3


def phase_ms(ctx, phase, without_exchange=False):
    """Device ms a step of a step phase (mean over devices), with or
    without the ``EXCHANGE`` ops under it."""
    got = attribution(ctx)
    if got is None:
        return None
    rows = got["no_exchange" if without_exchange else "all"]
    if not any(phase in g["phase"] for g in rows):
        return None
    return _ms_per_step(ctx, (g["phase"].get(phase, 0.0) for g in rows))


def region_ms(ctx, *regions):
    """Device ms a step of the named regions together."""
    got = attribution(ctx)
    if got is None:
        return None
    rows = got["all"]
    if not any(r in g["region"] for g in rows for r in regions):
        return None
    return _ms_per_step(ctx, (
        sum(g["region"].get(r, 0.0) for r in regions) for g in rows))


def roofline_pct(ctx, count, *regions, extra=()):
    """What the algorithm needs over the peaks, over the regions' device
    time.  ``count`` names the function of the configuration's own flops
    module (``flops.family``) that returns ``(least seconds, bound)`` from
    the configuration, ONE CHIP's rows of the step, the device kind and
    ``extra``; which bound applies is left in ``ctx["notes"]`` under
    ``count``'s name with ``_bound`` for ``_seconds``."""
    ms = region_ms(ctx, *regions)
    if not ms:
        return None
    mix = dict(ctx["mix"], global_batch=(
        int(ctx["mix"]["global_batch"]) // len(ctx["devices"])))
    least, bound = getattr(flops.family(ctx["config"]), count)(
        ctx["config"], mix, ctx["device_kind"], *extra)
    ctx.setdefault("notes", {})[count.replace("_seconds", "_bound")] = bound
    return 100.0 * least / (ms / 1e3)


def unattributed_pct(ctx):
    """Busy time whose instruction the table does not hold, or whose
    path names no phase, over busy time."""
    got = attribution(ctx)
    if got is None:
        return None
    rows = got["all"]
    return 100.0 * (sum(g["unattributed"] for g in rows)
                    / sum(g["busy"] for g in rows))
