"""Worker for the kill -9 fault-tolerance test (VERDICT r3 item #1).

Run as: python _mp_resume_worker.py <pid> <nproc> <port> <ckpt_dir> <crash_after>

Runs the REAL examples/imagenet training CLI (tiny config) under a
2-process jax.distributed world.  With ``crash_after > 0`` the process
hard-kills itself (SIGKILL — no atexit, no flushing, exactly a crash)
once a consistent checkpoint generation >= crash_after exists on disk;
with ``crash_after == 0`` it runs to completion and the example prints
``final gstep N params_digest XXXXXXXX``.  The test asserts a relaunch
resumes mid-run and reproduces the uninterrupted run's digest
bit-for-bit (reference behavior: REF:chainermn/extensions/checkpoint.py
maybe_load, SURVEY §5.3-§5.4).
"""

import os
import sys


def main():
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    port, ckpt_dir = sys.argv[3], sys.argv[4]
    crash_after = int(sys.argv[5])

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=2")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )

    if crash_after > 0:
        import re
        import signal
        import time

        from chainermn_tpu.extensions import checkpoint as ckpt_mod

        orig_save = ckpt_mod.MultiNodeCheckpointer.save

        def save_then_maybe_die(self, state, iteration, block=True):
            orig_save(self, state, iteration, block=block)
            if iteration < crash_after:
                return
            self.wait()  # our own generation committed
            pat = re.compile(r"done_iter_(\d+)\.rank(\d+)$")
            deadline = time.time() + 60
            while time.time() < deadline:
                gens = {}
                for fn in os.listdir(self.dir):
                    m = pat.match(fn)
                    if m:
                        gens.setdefault(int(m.group(1)), set()).add(
                            int(m.group(2))
                        )
                if any(
                    it >= crash_after and len(ranks) >= self.comm.size
                    for it, ranks in gens.items()
                ):
                    os.kill(os.getpid(), signal.SIGKILL)  # CRASH.
                time.sleep(0.05)
            raise RuntimeError("consistent generation never appeared")

        ckpt_mod.MultiNodeCheckpointer.save = save_then_maybe_die

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "examples", "imagenet",
        ),
    )
    import train_imagenet

    train_imagenet.main([
        "--communicator", "naive", "--arch", "nin", "--image-size", "64",
        "--num-classes", "10", "--batchsize", "32", "--train-size", "128",
        "--val-size", "32", "--epochs", "2", "--warmup-steps", "4",
        "--prefetch", "0",
        "--checkpoint-dir", ckpt_dir, "--checkpoint-every", "1",
    ])
    print(f"RESUME_WORKER_DONE {pid}", flush=True)


if __name__ == "__main__":
    main()
