#!/usr/bin/env python
"""Host-plane p2p throughput microbench (send_obj/recv_obj over the
jax.distributed KV store) — the wire the reference's
``MpiCommunicatorBase.send/recv`` provided (REF:chainermn/communicators/
mpi_communicator_base.py), here measured across a REAL process boundary
on localhost.

Spawns itself twice under ``jax.distributed`` (2 CPU processes), then
rank 0 sends a ``--size-mb`` payload to rank 1 repeatedly; rank 1 acks
with a tiny object so each iteration is a full send→recv→ack round trip.
Two payload flavors:

* ``ndarray`` — the typed fast path: raw buffer chunks, dtype/shape
  header, pipelined chunk RPCs, receiver chunks land in the preallocated
  result (no pickle either side).
* ``bytes``  — the generic pickled path (pickle of a bytes object is a
  near-memcpy, so this isolates the transport difference: serial vs
  pipelined chunk round-trips).

Prints one JSON line per flavor on rank 0:
``{"metric": "kvtransport p2p", "flavor": ..., "value": <MB/s>, ...}``.

Usage: python benchmarks/kvtransport_bench.py [--size-mb 64] [--iters 3]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def worker(pid: int, nproc: int, port: str, size_mb: int, iters: int):
    # The parent's Popen env (see main) already forces the CPU platform;
    # the in-process config update is the belt to that suspenders.
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    import numpy as np

    from chainermn_tpu.communicators import create_communicator

    comm = create_communicator("naive")
    nbytes = size_mb << 20
    arr = np.random.RandomState(0).randn(nbytes // 8).astype(np.float64)
    blob = arr.tobytes()

    for flavor, payload in (("ndarray", arr), ("bytes", blob)):
        comm.barrier()
        # Warmup round (first-use key churn, pool spin-up).
        if pid == 0:
            comm.send_obj(payload, dest=1, tag=1)
            comm.recv_obj(source=1, tag=2)
        else:
            got = comm.recv_obj(source=0, tag=1)
            comm.send_obj("ack", dest=0, tag=2)
        comm.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            if pid == 0:
                comm.send_obj(payload, dest=1, tag=1)
                comm.recv_obj(source=1, tag=2)
            else:
                got = comm.recv_obj(source=0, tag=1)
                comm.send_obj("ack", dest=0, tag=2)
        dt = (time.perf_counter() - t0) / iters
        if pid == 1:
            # Correctness while we're here.
            if flavor == "ndarray":
                assert isinstance(got, np.ndarray)
                np.testing.assert_array_equal(got, arr)
            else:
                assert got == blob
        if pid == 0:
            print(
                json.dumps(
                    {
                        "metric": "kvtransport p2p round-trip",
                        "plane": (
                            "socket"
                            if os.environ.get(
                                "CHAINERMN_TPU_SOCKET_P2P", "1"
                            ) != "0"
                            else "kv"
                        ),
                        "flavor": flavor,
                        "value": round(size_mb / dt, 1),
                        "unit": "MB/s",
                        "size_mb": size_mb,
                        "sec_per_transfer": round(dt, 3),
                    }
                ),
                flush=True,
            )
    comm.barrier()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=int, default=64)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument(
        "--plane", choices=("socket", "kv"), default="socket",
        help="p2p data plane: direct TCP (default) or the KV chunk path",
    )
    ap.add_argument("--worker", nargs=3, metavar=("PID", "NPROC", "PORT"))
    args = ap.parse_args()
    os.environ["CHAINERMN_TPU_SOCKET_P2P"] = (
        "1" if args.plane == "socket" else "0"
    )
    if args.worker:
        worker(
            int(args.worker[0]), int(args.worker[1]), args.worker[2],
            args.size_mb, args.iters,
        )
        return
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--size-mb", str(args.size_mb), "--iters", str(args.iters),
                "--plane", args.plane,
                "--worker", str(pid), "2", port,
            ],
            env={
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": " ".join(
                    [
                        f
                        for f in os.environ.get("XLA_FLAGS", "").split()
                        if "host_platform_device_count" not in f
                    ]
                    + ["--xla_force_host_platform_device_count=1"]
                ),
                "PYTHONPATH": os.pathsep.join(
                    p
                    for p in (
                        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        os.environ.get("PYTHONPATH"),
                    )
                    if p
                ),
            },
        )
        for pid in range(2)
    ]
    rc = [p.wait() for p in procs]
    if any(rc):
        raise SystemExit(f"worker exit codes {rc}")


if __name__ == "__main__":
    main()
