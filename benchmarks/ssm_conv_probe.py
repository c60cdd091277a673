#!/usr/bin/env python
"""The causal convolution alone, on the chip: device time a call of its
forward and of its backward, beside the plain formulas and what autodiff
makes of them.

``ops.ssd.causal_conv_silu`` is compiled at ``--batch x --seq x
--channels`` with ``--taps`` taps (the hybrid cell's (2, 8192, 4352), 4,
by default), forward and vjp apart, and so is a plain ``jax.numpy`` copy
of its forward with no kernel and no backward of its own.  The
activations' layouts are left to the compiler, as they are inside a step
(it puts the sequence on the lanes for the kernels).  Each program runs
``--calls`` times inside one profiler capture and is read by DEVICE time
(``observability.device_trace``), with its largest ops.

    chiprun -- env PYTHONPATH=. python benchmarks/ssm_conv_probe.py \
        --out chiprun_out/ssm_conv_probe.json

Half a minute on one chip.  Off the chip the capture has no device plane:
rows without times.  PERF.md §6 (PR 29) rests on this table.
"""

import argparse
import collections
import glob
import json
import os
import tempfile

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from chainermn_tpu.observability import device_trace
from chainermn_tpu.ops import ssd


def plain_conv_silu(x, kernel, bias):
    """The forward's formulas with no rule of their own: what the
    backward was before it was written."""
    K, S = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    acc = bias.astype(jnp.float32)
    for j in range(K):
        acc = acc + (padded[:, j:j + S].astype(jnp.float32)
                     * kernel[j].astype(jnp.float32))
    return jax.nn.silu(acc).astype(x.dtype)


def device_ms(programs, calls, top=6):
    """``{name: (compiled, operands)}`` → ``{name: {"ms": [...],
    "ops_ms": [...]}}``: every program run ``calls`` times in ONE capture,
    in order, timed on the device's clock, with its ``top`` largest ops.
    Empty rows off the chip."""
    for c, operands in programs.values():
        jax.block_until_ready(c(*operands))
    logdir = tempfile.mkdtemp(prefix="ssm_conv_probe_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    for c, operands in programs.values():
        for _ in range(calls):
            out = c(*operands)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    devices, _ = device_trace.read_capture(max(found, key=os.path.getmtime))
    ran = sorted(devices[0]["modules"], key=lambda t: t[1]) if devices else []
    rows = {name: {} for name in programs}
    if len(ran) != calls * len(programs):
        return rows
    for i, name in enumerate(programs):
        runs = ran[i * calls:(i + 1) * calls]
        by_op = collections.Counter()
        for op, start, end in devices[0]["ops"]:
            if any(s <= start and end <= t for _, s, t in runs):
                by_op[device_trace.instruction_name(op)] += (
                    (end - start) * 1e3 / calls)
        rows[name] = {
            "ms": [round((t - s) * 1e3, 4) for _, s, t in runs],
            "ops_ms": [[op, round(ms, 4)] for op, ms in by_op.most_common(top)]}
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--channels", type=int, default=4352)
    ap.add_argument("--taps", type=int, default=4)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rng = np.random.RandomState(0)
    shape = (args.batch, args.seq, args.channels)
    x = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    dy = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    kernel = jnp.asarray(rng.randn(args.taps, args.channels) * 0.5,
                         jnp.float32)
    bias = jnp.asarray(rng.randn(args.channels) * 0.1, jnp.float32)

    def vjp_of(conv):
        return lambda x, k, b, dy: jax.vjp(conv, x, k, b)[1](dy)

    here = SingleDeviceSharding(jax.devices()[0])
    free = Format(Layout.AUTO, here)

    def compiled(fn, operands, n_out):
        """``fn`` with the layouts of its activations (operands and
        results of three axes) the compiler's choice, the others'
        row-major, and the operands placed in them."""
        def row_major(ndim):
            return Format(Layout(major_to_minor=tuple(range(ndim))), here)

        c = jax.jit(
            fn, in_shardings=tuple(free if a.ndim == 3 else row_major(a.ndim)
                                   for a in operands),
            out_shardings=(free, row_major(2), row_major(1))[:n_out]
            if n_out > 1 else free).lower(*operands).compile()
        return c, tuple(jax.device_put(a, f)
                        for a, f in zip(operands, c.input_formats[0]))

    programs = {
        "forward": compiled(ssd.causal_conv_silu, (x, kernel, bias), 1),
        "forward_plain": compiled(plain_conv_silu, (x, kernel, bias), 1),
        "backward": compiled(
            vjp_of(ssd.causal_conv_silu), (x, kernel, bias, dy), 3),
        "backward_autodiff": compiled(
            vjp_of(plain_conv_silu), (x, kernel, bias, dy), 3)}
    rows = []
    for name, timed in device_ms(programs, args.calls).items():
        c = programs[name][0]
        row = {"program": name, "shape": list(shape), "taps": args.taps,
               "temp_mb": round(
                   c.memory_analysis().temp_size_in_bytes / 1e6, 1), **timed}
        rows.append(row)
        print(json.dumps(row))
    # the kernels against the plain formulas and autodiff, on this device
    def ran(name):
        c, operands = programs[name]
        out = c(*operands)
        return out if isinstance(out, (tuple, list)) else (out,)

    # (on the host: the results keep the layouts the compiler chose)
    gaps = [float(np.abs(g - w).max() / np.abs(w).max())
            for g, w in zip(
                *([np.asarray(a, np.float32) for a in ran(f) + ran(b)]
                  for f, b in (("forward", "backward"),
                               ("forward_plain", "backward_autodiff"))))]
    print(json.dumps({"gap_y_dx_dkernel_dbias": gaps}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "rows": rows, "gap_y_dx_dkernel_dbias": gaps},
                      f, indent=1)


if __name__ == "__main__":
    main()
