#!/usr/bin/env python
"""The gradient exchange alone, on the chips: device time a call of one
float32 bucket's mean over every chip of the host through ``lax.psum``
(one synchronous ``all-reduce`` in the core's instruction stream) and
through ``communicators.ring.ring_mean`` (a two-way ring of
``collective-permute`` DMAs with the adds between them), at the dp4
cell's two bucket sizes — a 64 MB FFN matrix and the 412 MB tied
embedding — and at small sizes, where the two cross (``--sizes-mb``;
``overlap.RING_MIN_BYTES`` stands higher, at the smallest bucket a whole
step has shown to pay).  ``--together`` also runs that many 64 MB buckets
in ONE program, as a step holds them once the backward pass is over: the
rings of different buckets then share the wire.

Each program runs ``--calls`` times inside one profiler capture and is
read by DEVICE time (``observability.device_trace``) with its largest
ops: a ring's ``collective-permute-done`` rows are the wire time nothing
hid (the probe has nothing to hide it under), its ``fusion`` and
``dynamic-update-slice`` rows the core time of the adds and of writing
the gathered pieces back.  The last line says how far the ring's mean
lies from ``psum``'s and whether every chip holds the same bits.

    chiprun --chips 4 -- env PYTHONPATH=. python \
        benchmarks/grad_exchange_probe.py --out chiprun_out/grad_exchange_probe.json

About a minute on four chips.  Off the chip the capture has no device
plane: rows without times (``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=4 ... --sizes-mb 1
--together 0`` rehearses it).  PERF.md §6 (PR 45) rests on this table.
"""

import argparse
import json
import os

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

setup_compilation_cache()

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec
from ssm_conv_probe import device_ms  # (beside this file)

import chainermn_tpu
from chainermn_tpu.communicators import build_mesh, ring

#: The dp4 cell's tied embedding, 50,257 x 2,048 float32.
EMBEDDING_MB = 50257 * 2048 * 4 / 2**20


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default=f"0.25,1,4,64,{EMBEDDING_MB}")
    ap.add_argument("--together", type=int, default=8,
                    help="64 MB buckets in one program (0: leave out)")
    ap.add_argument("--leaf", default="2048x8192",
                    help="a 2-D leaf exchanged in its own shape")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    devices = jax.devices()
    n = len(devices)
    mesh = build_mesh(inter_size=1, intra_size=n, devices=devices)
    comm = chainermn_tpu.create_communicator("xla_ici", mesh=mesh)
    axes = comm.axes
    order = ring.ring_order(mesh, axes)
    rows_of = NamedSharding(mesh, PartitionSpec(axes))

    def means(count):
        def psum(bufs):
            return [lax.psum(b, axes) / n for b in bufs]

        def rings(bufs):
            return [ring.ring_mean(b, axes, order) for b in bufs]

        def program(body):
            def per_device(*bufs):
                return tuple(o[None] for o in body([b[0] for b in bufs]))

            spec = (PartitionSpec(axes),) * count
            return jax.jit(comm.shard_map(per_device, spec, spec))

        return {"psum": program(psum), "ring": program(rings)}

    rng = np.random.RandomState(0)
    programs, shapes = {}, {}
    cases = [(float(mb), 1) for mb in args.sizes_mb.split(",")]
    if args.together:
        cases.append((64.0, args.together))
    leaf = tuple(int(d) for d in args.leaf.split("x"))
    for mb, count in cases + [(None, 1)]:
        # the last case: a 2-D gradient leaf exchanged in its own shape
        shape = leaf if mb is None else (int(mb * 2**20 / 4),)
        elems = int(np.prod(shape))
        mb = elems * 4 / 2**20 if mb is None else mb
        operands = tuple(
            jax.device_put(jnp.asarray(
                rng.rand(n, *shape), jnp.float32), rows_of)
            for _ in range(count))
        for form, fn in means(count).items():
            name = f"{form}.{count}x{mb:g}MB" + ("" if len(shape) == 1
                                                  else ".leaf")
            programs[name] = (fn.lower(*operands).compile(), operands)
            shapes[name] = [count, elems]
    rows = []
    for name, timed in device_ms(programs, args.calls, top=8).items():
        count, elems = shapes[name]
        row = {"program": name, "buckets": count, "elems": elems, **timed}
        if timed.get("ms"):
            # NCCL's convention: what each link direction pair carries.
            best = min(timed["ms"]) * 1e-3
            row["bus_GBps"] = round(
                2 * (n - 1) / n * count * elems * 4 / best / 1e9, 2)
        rows.append(row)
        print(json.dumps(row))
    gaps = {}
    for name in programs:
        if name.startswith("ring."):
            a, b = (np.asarray(programs[form + name[4:]][0](
                *programs[form + name[4:]][1])[0])
                for form in ("psum", "ring"))
            gaps[name[5:]] = {
                "largest_gap": float(np.abs(a - b).max()),
                "every_chip_the_same_bits": bool((b == b[:1]).all())}
    print(json.dumps({"ring_order": list(order), "ring_against_psum": gaps}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": devices[0].device_kind, "chips": n,
                       "ring_order": list(order), "rows": rows,
                       "ring_against_psum": gaps}, f, indent=1)


if __name__ == "__main__":
    main()
