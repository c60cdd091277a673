#!/usr/bin/env python
"""Allreduce bus-bandwidth micro-benchmark — BASELINE.md's second metric
("allreduce bus bandwidth: report GB/s over ICI for the gradient-allreduce
path").

Reference analogue: the relative ranking discussion in the reference's docs
(pure_nccl > two_dimensional > hierarchical > flat > naive, SURVEY §6) and
NCCL's own ``all_reduce_perf`` convention: for an allreduce over ``n``
ranks the *bus bandwidth* is ``2*(n-1)/n * bytes / time`` — the wire-level
traffic each link actually carries, making numbers comparable across
device counts.

Runs the REAL gradient-allreduce path of each requested communicator (the
same ``allreduce_grad`` that ``create_multi_node_optimizer`` traces into
the train step), jitted via ``shard_map`` over the full mesh, across a
sweep of buffer sizes.

Usage::

    python benchmarks/allreduce_bench.py                 # all devices, xla_ici
    python benchmarks/allreduce_bench.py --communicators xla_ici,two_dimensional \
        --sizes-mb 1,16,64 --dtype bfloat16

On one real chip there is no inter-chip wire, so the number degenerates to
0 (n=1 → factor 0); use the virtual CPU mesh (``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``) to exercise the
collective algorithm itself, and a real slice for true ICI GB/s.

Prints one JSON line per (communicator, size) with keys
{"metric", "communicator", "bytes", "value", "unit", "time_ms"}.
"""

from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np


from chainermn_tpu.observability.hlo_audit import (  # noqa: F401
    assert_two_dimensional_inter_savings,  # re-export: external callers
    audit_allreduce,
)


def collective_profile(comm, nbytes: int, dtype) -> dict:
    """Per-communicator collective-op counts from the traced
    ``allreduce_grad`` lowering (jaxpr-level, environment-independent).

    Recorded alongside every bandwidth number so a future multi-chip run
    is one command AND the algorithm each backend actually lowered to is
    pinned in the same JSON line (e.g. two_dimensional must show
    psum_scatter + psum + all_gather; xla_ici one fused psum).

    Thin view over :mod:`chainermn_tpu.observability.hlo_audit` — the
    library owns the census; this keeps the bench's record shape."""
    return audit_allreduce(comm, nbytes, dtype).census()


def bytes_per_leg(comm, nbytes: int, dtype) -> dict:
    """Static per-mesh-axis collective OPERAND bytes from the traced
    ``allreduce_grad`` — the wire-cost structure of each backend's
    algorithm, readable without any multi-chip hardware.

    For every collective in the lowering, the per-device operand size is
    charged to each mesh axis the op runs over.  This pins the
    two_dimensional backend's bandwidth claim STATICALLY: its inter-axis
    (DCN-analogue) traffic must be the flat backend's divided by
    ``intra_size``, because the inter psum runs on the
    ``reduce_scatter``'d 1/intra shard (SURVEY §2.1 two-dimensional row;
    the reference's rationale for the 2D algorithm on >1 GbE clusters).

    Thin view over :func:`hlo_audit.audit_allreduce` (one source of
    truth for the bytes-per-leg metric)."""
    return audit_allreduce(comm, nbytes, dtype).bytes_per_axis


def bench_one(comm, nbytes: int, dtype, iters: int, warmup: int) -> dict:
    n = comm.device_size
    elems_per_dev = max(1, nbytes // np.dtype(dtype).itemsize)
    # The stacked-tree shape eager_allreduce_grad expects: leading
    # device_size axis, one shard per device.
    buf = jnp.ones((n, elems_per_dev), dtype=dtype)

    # Chain each iteration's input to the previous output so the timed loop
    # is one serial dependency chain, and synchronize with a host readback
    # (sync) rather than block_until_ready — see profiling.sync's docstring.
    from chainermn_tpu.utils.profiling import sync

    out = {"g": buf}
    for _ in range(warmup):
        out = comm.eager_allreduce_grad(out)
    sync(out)

    import jax

    if jax.default_backend() == "cpu":
        # Per-iteration sync: CPU readback is ~free (nothing for the
        # slope method to cancel), and letting many 8-virtual-device
        # programs pile up in flight starves the single-host execution
        # pool mid-rendezvous (XLA CPU aborts after 40 s: "Expected 8
        # threads to join").
        t0 = time.perf_counter()
        for _ in range(iters):
            out = comm.eager_allreduce_grad(out)
            sync(out)
        dt = (time.perf_counter() - t0) / iters
    else:
        # Slope timing (profiling.slope_time): cancels the per-run
        # dispatch + readback constant.
        from chainermn_tpu.utils.profiling import slope_time

        def run(k):
            nonlocal out
            t0 = time.perf_counter()
            for _ in range(k):
                out = comm.eager_allreduce_grad(out)
            sync(out)
            return time.perf_counter() - t0

        dt = slope_time(run, iters)

    payload = elems_per_dev * np.dtype(dtype).itemsize
    # A degenerate op (n=1 pass-through) can slope-time below measurement
    # noise (even negative); report zeros rather than a garbage bandwidth.
    if dt <= 1e-9:
        return {
            "metric": "allreduce_bus_bw", "communicator": comm.name,
            "devices": n, "bytes": payload, "value": 0.0, "unit": "GB/s",
            "time_ms": 0.0, "algo_bw_GBps": 0.0,
            "note": "below measurement noise",
        }
    bus_bw = 2 * (n - 1) / n * payload / dt if n > 1 else 0.0
    return {
        "metric": "allreduce_bus_bw",
        "communicator": comm.name,
        "devices": n,
        "bytes": payload,
        "value": round(bus_bw / 1e9, 4),
        "unit": "GB/s",
        "time_ms": round(dt * 1e3, 4),
        "algo_bw_GBps": round(payload / dt / 1e9, 4),
        "hlo_collectives": collective_profile(comm, nbytes, dtype),
        "bytes_per_leg": bytes_per_leg(comm, nbytes, dtype),
    }


def _time_tree(comm, stacked, iters: int, warmup: int) -> float:
    """Seconds per eager_allreduce_grad over a stacked tree (chained
    serial dependency; same sync discipline as :func:`bench_one`)."""
    import jax

    from chainermn_tpu.utils.profiling import sync

    out = stacked
    for _ in range(warmup):
        out = comm.eager_allreduce_grad(out)
    sync(out)
    if jax.default_backend() == "cpu":
        t0 = time.perf_counter()
        for _ in range(iters):
            out = comm.eager_allreduce_grad(out)
            sync(out)
        return (time.perf_counter() - t0) / iters
    from chainermn_tpu.utils.profiling import slope_time

    def run(k):
        nonlocal out
        t0 = time.perf_counter()
        for _ in range(k):
            out = comm.eager_allreduce_grad(out)
        sync(out)
        return time.perf_counter() - t0

    return slope_time(run, iters)


def bench_tree(name: str, n_leaves: int, total_bytes: int, dtype,
               iters: int, warmup: int, bucket_bytes: int | None,
               static_only: bool) -> dict:
    """The many-leaf ``allreduce_tree`` row: bucketed (GradPacker fusion)
    vs unbucketed (``bucket_bytes=0``) lowering of the SAME mixed-shape
    gradient tree through one communicator — collective census, per-axis
    and per-bucket operand bytes, and (unless ``static_only``) timings.
    """
    import jax

    import chainermn_tpu
    from chainermn_tpu.communicators.packing import (
        DEFAULT_BUCKET_BYTES,
        GradPacker,
        synthetic_grad_tree,
    )
    from chainermn_tpu.observability.hlo_audit import audit_allreduce_tree

    bb = DEFAULT_BUCKET_BYTES if bucket_bytes is None else int(bucket_bytes)
    tree = synthetic_grad_tree(n_leaves, total_bytes, dtypes=(str(dtype),))
    row: dict = {
        "metric": "allreduce_tree",
        "communicator": name,
        "n_leaves": n_leaves,
        "payload_bytes": sum(
            l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree)
        ),
        "bucket_bytes": bb,
        "packing": GradPacker.for_tree(tree, bucket_bytes=bb).describe(),
    }
    for label, cap in (("bucketed", bb), ("unbucketed", 0)):
        comm = chainermn_tpu.create_communicator(name, bucket_bytes=cap)
        audit = audit_allreduce_tree(comm, tree)
        entry = {
            "hlo_collectives": audit.census(),
            "reduction_collectives": audit.reduction_collectives(),
            "per_axis_operand_bytes": audit.bytes_per_axis,
            "op_bytes": {k: v for k, v in audit.op_bytes.items()},
        }
        if not static_only:
            n = comm.device_size
            stacked = jax.tree_util.tree_map(
                lambda l: jnp.stack([jnp.asarray(l)] * n), tree
            )
            dt = _time_tree(comm, stacked, iters, warmup)
            entry["time_ms"] = round(dt * 1e3, 4)
        row[label] = entry
    tb = row["bucketed"].get("time_ms")
    tu = row["unbucketed"].get("time_ms")
    if tb and tu:
        row["speedup_vs_unbucketed"] = round(tu / tb, 4)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--communicators", default="xla_ici",
                    help="comma-separated communicator names")
    ap.add_argument("--sizes-mb", default="1,4,16,64",
                    help="comma-separated per-device payload sizes in MiB")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "float16"])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--static-only", action="store_true",
                    help="skip timing; print each communicator's "
                         "jaxpr-level per-axis collective bytes and "
                         "assert the two_dimensional inter-leg savings "
                         "claim (runs on any backend, incl. the virtual "
                         "CPU mesh)")
    ap.add_argument("--tree-leaves", type=int, default=0,
                    help="many-leaf mode: bench allreduce_grad over a "
                         "synthetic mixed-shape gradient tree with this "
                         "many leaves, bucketed vs unbucketed (0 = the "
                         "classic single-buffer sweep)")
    ap.add_argument("--tree-total-mb", type=float, default=8.0,
                    help="total payload of the synthetic tree in MiB")
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="bucket cap for the tree mode's bucketed "
                         "variant (default: the 4 MiB packing default)")
    args = ap.parse_args()
    if args.iters < 1:
        ap.error("--iters must be >= 1")
    if args.warmup < 2:
        ap.error(
            "--warmup must be >= 2: the first call pays compilation for the "
            "fresh-buffer input sharding and the second for the chained "
            "(shard_map-output) sharding; with fewer, a compile lands inside "
            "the timed loop"
        )

    import chainermn_tpu

    dtype = jnp.dtype(args.dtype)
    if args.tree_leaves > 0:
        total_bytes = int(args.tree_total_mb * 2**20)
        for name in args.communicators.split(","):
            row = bench_tree(
                name.strip(), args.tree_leaves, total_bytes, dtype,
                args.iters, args.warmup, args.bucket_bytes,
                args.static_only,
            )
            print(json.dumps(row))
        return
    if args.static_only:
        nbytes = int(float(args.sizes_mb.split(",")[0]) * 2**20)
        profiles = {}
        intra = None
        for name in args.communicators.split(","):
            comm = chainermn_tpu.create_communicator(name.strip())
            intra = comm.intra_size
            profiles[comm.name] = bytes_per_leg(comm, nbytes, dtype)
            print(json.dumps({
                "metric": "allreduce_static_bytes_per_leg",
                "communicator": comm.name,
                "bytes": nbytes,
                "per_axis_operand_bytes": profiles[comm.name],
                "hlo_collectives": collective_profile(comm, nbytes, dtype),
            }))
        assert_two_dimensional_inter_savings(profiles, intra)
        return
    for name in args.communicators.split(","):
        comm = chainermn_tpu.create_communicator(name.strip())
        for mb in args.sizes_mb.split(","):
            nbytes = int(float(mb) * 2**20)
            row = bench_one(comm, nbytes, dtype, args.iters, args.warmup)
            print(json.dumps(row))


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
