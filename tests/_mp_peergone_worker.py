"""Worker for the cross-process peer-death churn test.

Run as: python _mp_peergone_worker.py <pid> <nproc> <port>

Three REAL processes under one jax.distributed coordinator:

* rank 1 sends one message over the raw SocketPlane, then writes a
  PARTIAL frame (header promising 64 bytes, 10 delivered) and SIGKILLs
  itself — a crashed host mid-send, no cleanup, no FIN ordering
  guarantees beyond the kernel's.
* rank 0 (survivor) must see the intact message, then get ``PeerGone``
  well inside its recv timeout (not hang out the deadline), then accept
  a same-rank REPLACEMENT incarnation and keep talking to the unrelated
  bystander rank — one peer's death must not poison the transport.
* rank 2 (bystander) hosts the replacement: after rank 0 confirms the
  death it constructs ``SocketPlane(1)`` — republishing rank 1's
  endpoint through the REAL coordination-service KV (the
  delete-then-set takeover path) — and resumes rank 1's stream at the
  exact seq the partial frame failed to deliver.

Prints ``MP_PEERGONE_OK <pid>`` from each surviving rank; rank 1's exit
is the SIGKILL itself.
"""

import os
import struct
import sys
import time


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )

    from chainermn_tpu.communicators import kvtransport as kv

    if pid == 1:
        plane = kv.SocketPlane(1)
        plane.send("churn", 0, 0, 0, "alive")
        sock = plane._send_socks[0]
        hdr = (
            b'{"kind": "pkl", "nbytes": 64, "ns": "churn", '
            b'"src": 1, "tag": 0, "seq": 1}'
        )
        sock.sendall(struct.pack("<I", len(hdr)) + hdr + b"\x00" * 10)
        # Die NOW, 54 bytes short of the header's promise.  SIGKILL: no
        # atexit, no socket shutdown handshake from userspace.
        os.kill(os.getpid(), 9)
        return  # unreachable

    if pid == 0:
        plane = kv.SocketPlane(0)
        assert plane.recv("churn", 1, 0, 0, timeout_ms=60_000) == "alive"
        t0 = time.monotonic()
        try:
            plane.recv("churn", 1, 0, 1, timeout_ms=120_000)
            raise AssertionError("recv from the corpse returned?!")
        except kv.PeerGone as e:
            took = time.monotonic() - t0
            assert took < 60, f"PeerGone took {took:.1f}s"
            assert e.peer == 1
        # Tell the bystander it may stand up the replacement.
        plane.send("churn", 2, 1, 0, "gone_seen")
        got = kv.retry_backoff(
            lambda: plane.recv("churn", 1, 0, 1, timeout_ms=5_000),
            retries=10, base_s=0.1,
        )
        assert got == "replacement", got
        # Rank 2 is still alive here (blocked on our ack), so the
        # replacement's connection is up: rank 1 reads as revived.
        assert plane.peer_gone(1) is None
        assert plane.recv("churn", 2, 2, 0, timeout_ms=60_000) == "bystander"
        plane.send("churn", 2, 3, 0, "ack")
        print(f"MP_PEERGONE_OK {pid}")
        # Skip jax's atexit shutdown barrier: it would block on the
        # SIGKILLed rank until the coordination service aborts us.
        sys.stdout.flush()
        os._exit(0)

    # pid == 2: bystander + replacement host
    plane = kv.SocketPlane(2)
    assert plane.recv("churn", 0, 1, 0, timeout_ms=120_000) == "gone_seen"
    rep1 = kv.SocketPlane(1)  # same-rank takeover, real KV republish
    rep1.send("churn", 0, 0, 1, "replacement")
    plane.send("churn", 0, 2, 0, "bystander")
    # Stay alive until rank 0 has finished asserting the revival (our
    # exit would EOF the replacement's connection and re-mark it gone).
    assert plane.recv("churn", 0, 3, 0, timeout_ms=60_000) == "ack"
    print(f"MP_PEERGONE_OK {pid}")
    sys.stdout.flush()
    os._exit(0)  # see rank 0: no shutdown barrier with a corpse in it


if __name__ == "__main__":
    main()
