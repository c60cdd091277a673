#!/usr/bin/env python
"""Data-parallel MNIST MLP — the minimum end-to-end slice.

Reference: REF:examples/mnist/train_mnist.py — the canonical ChainerMN
usage pattern: ``create_communicator`` → ``scatter_dataset`` →
``create_multi_node_optimizer`` → trainer + ``create_multi_node_evaluator``,
with a flag-selectable communicator (CPU-capable with ``naive``).

TPU-native differences: there is one process per *host* (not per chip);
the per-step batch is a global array whose leading axis the jitted step
shards over the device mesh, and the gradient allreduce is traced into the
step by the multi-node optimizer.

Run (single host, any backend):
    python examples/mnist/train_mnist.py --communicator xla_ici
CPU-mesh smoke run (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/mnist/train_mnist.py --communicator naive --epochs 2

Elastic run under the supervisor (docs/fault_tolerance.md):
    python -m chainermn_tpu.tools.elastic --nproc 2 -- \
        python examples/mnist/train_mnist.py --communicator naive \
        --elastic --checkpoint-dir ckpt --checkpoint-every 1
"""

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import optax

import chainermn_tpu
from chainermn_tpu.utils.profiling import sync
from chainermn_tpu.datasets.toy import SyntheticImageDataset, batch_iterator
from chainermn_tpu.extensions import Evaluator
from chainermn_tpu.models import MLP
from chainermn_tpu.observability import startup


def main(argv=None):
    p = argparse.ArgumentParser(description="chainermn_tpu MNIST example")
    p.add_argument("--communicator", default="xla_ici")
    p.add_argument("--bucket-bytes", type=int, default=None,
                   help="gradient-allreduce bucket cap in bytes "
                        "(0 disables bucketing; default: 4 MiB / "
                        "CHAINERMN_TPU_BUCKET_BYTES — docs/performance.md)")
    p.add_argument("--batchsize", type=int, default=256, help="global batch size")
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--unit", type=int, default=1000)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--double-buffering", action="store_true")
    p.add_argument("--zero-stage", type=int, default=0, choices=(0, 1, 2, 3),
                   help="ZeRO sharding stage (composes with "
                        "--double-buffering)")
    p.add_argument("--train-size", type=int, default=8192)
    p.add_argument("--val-size", type=int, default=1024)
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable fault tolerance: multi-node checkpointer "
                   "saves here and auto-resumes from the newest consistent "
                   "generation on relaunch")
    p.add_argument("--checkpoint-every", type=int, default=10,
                   help="save a generation every N global steps")
    p.add_argument("--checkpoint-name", default="mnist",
                   help="checkpoint set name under --checkpoint-dir")
    p.add_argument("--elastic", action="store_true",
                   help="join the elastic supervisor's world "
                   "(CHAINERMN_TPU_ELASTIC_* env): heartbeats, chaos "
                   "faults, SIGTERM-as-preemption, and plan-driven "
                   "resharding on rescale.  A no-op outside a "
                   "supervised run.")
    p.add_argument("--step-log", default=None, metavar="PATH",
                   help="write a JSONL step-event log (per-step loss, "
                        "timing, compile events, one hlo_audit row); "
                        "summarize with `python -m chainermn_tpu.tools.obs "
                        "summarize PATH`.  Multi-process runs should "
                        "point each rank at its own file.")
    args = p.parse_args(argv)

    ctx = None
    if args.elastic:
        from chainermn_tpu import elastic

        # Joins jax.distributed BEFORE the backend initializes below;
        # returns None when not running under the supervisor.
        ctx = elastic.init_from_env()

    comm = chainermn_tpu.create_communicator(
        args.communicator, bucket_bytes=args.bucket_bytes
    )
    if comm.rank == 0:  # reference pattern: only rank 0 logs
        print(f"communicator: {comm!r}")
        print(f"global batch {args.batchsize} over {comm.device_size} devices")

    train = SyntheticImageDataset(n=args.train_size, seed=0)
    val = SyntheticImageDataset(n=args.val_size, seed=1)
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=42)
    val = chainermn_tpu.scatter_dataset(val, comm)

    model = MLP(n_units=args.unit, n_out=10)
    with startup.phase("weights"):
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    def metric_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return {
            "val/loss": optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean(),
            "val/accuracy": (logits.argmax(-1) == y).mean(),
        }

    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(args.lr), comm, double_buffering=args.double_buffering,
        zero_stage=args.zero_stage,
    )
    state = opt.init(params)
    if args.zero_stage == 3:
        # Stage 3: the step trades in the flat sharded master buffer.
        params = opt.shard_params(params)
    step = opt.make_train_step(loss_fn)
    evaluator = Evaluator(metric_fn, comm)

    # --step-log: install a Reporter + StepRecorder for the whole run.
    # The instrumented step and the evaluator publish into them; the
    # per-step float(loss) readback below is the example's choice of
    # fidelity over async dispatch.
    telemetry = contextlib.ExitStack()
    reporter = recorder = None
    if args.step_log:
        from chainermn_tpu import observability as obs

        reporter = obs.Reporter()
        telemetry.enter_context(obs.scope(reporter))
        recorder = telemetry.enter_context(
            obs.StepRecorder(args.step_log, rank=comm.rank)
        )

    # Fault tolerance: a crashed/killed/preempted run relaunched with
    # the same command line resumes from the newest consistent
    # generation — mid-epoch, at the exact step.
    ckpt = None
    start_epoch = start_step = gstep = 0
    if args.checkpoint_dir:
        from chainermn_tpu.extensions import create_multi_node_checkpointer
        from chainermn_tpu.global_except_hook import add_hook

        add_hook()
        ckpt = create_multi_node_checkpointer(
            args.checkpoint_name, comm, path=args.checkpoint_dir
        )
        if ctx is not None:
            ctx.attach_checkpointer(ckpt)  # arm ckpt_* chaos faults
        template = {"params": params, "state": state, "epoch": 0, "step": 0}
        loaded, it = ckpt.maybe_load(template)
        if it is not None:
            params, state = loaded["params"], loaded["state"]
            start_epoch, start_step = int(loaded["epoch"]), int(loaded["step"])
            gstep = it
            if comm.rank == 0:
                print(
                    f"resumed from iteration {it} "
                    f"(epoch {start_epoch}, step {start_step})"
                )
            if ctx is not None and args.zero_stage == 0:
                # Rescale-ready restore: re-place params and moments for
                # the CURRENT mesh through the sharding-plan registry —
                # an N→M restart is plan.resolve on a different mesh.
                params, state, plan_report = ctx.reshard(
                    params, state, comm, plan="dp"
                )
                if comm.rank == 0:
                    print(
                        f"elastic_reshard plan=dp ok={plan_report.ok} "
                        f"leaves={plan_report.n_leaves} world={comm.size}"
                    )

    # Each process draws a LOCAL slice of the global batch from its
    # scattered shard and comm.global_batch assembles the device-global
    # arrays, sharded over the world (single-process: local slice ==
    # global batch).
    if args.batchsize % comm.size:
        raise SystemExit(
            f"--batchsize {args.batchsize} must divide by the process "
            f"count {comm.size}"
        )
    local_bs = args.batchsize // comm.size

    metrics = {}
    for epoch in range(start_epoch, args.epochs):
        t0 = time.perf_counter()
        n_seen = 0
        n_steps = 0
        last_loss = float("nan")
        # Resuming into this epoch: replay the iterator (same epoch seed
        # → same permutation) and drop the batches already trained on.
        skip = start_step if epoch == start_epoch else 0
        start_step = 0
        for batch in batch_iterator(train, local_bs, seed=epoch):
            if skip > 0:
                skip -= 1
                n_steps += 1
                if ctx is not None:
                    ctx.beat(gstep)  # liveness during replay
                continue
            if ctx is not None:
                ctx.beat(gstep)  # chaos faults fire here, deterministically
                if ckpt is not None and ctx.check_preemption(comm):
                    # Grace-window synchronous checkpoint: every rank
                    # arrives here at the same step, saves, and exits
                    # with the preemption code (not a crash).
                    ckpt.save(
                        {"params": params, "state": state,
                         "epoch": epoch, "step": n_steps},
                        gstep, block=True,
                    )
                    if comm.rank == 0:
                        print(f"preempted: checkpoint saved at "
                              f"iteration {gstep}")
                    ctx.exit_preempted()
            gb = comm.global_batch((batch[0], batch[1]))
            if recorder is not None and gstep == 0:
                from chainermn_tpu import observability as obs

                # Audit the unwrapped jitted step once: the collective
                # census of the program the whole run executes.
                a = obs.audit_fn(getattr(step, "__wrapped__", step),
                                 params, state, gb)
                recorder.record("hlo_audit", counts=a.counts,
                                bytes_per_axis=a.bytes_per_axis)
            params, state, loss = step(params, state, gb)
            n_seen += gb[0].shape[0]
            n_steps += 1
            gstep += 1
            last_loss = loss
            if recorder is not None:
                recorder.step(step=gstep - 1, items=gb[0].shape[0],
                              loss=float(loss), epoch=epoch)
            if ckpt is not None and gstep % args.checkpoint_every == 0:
                ckpt.save(
                    {"params": params, "state": state,
                     "epoch": epoch, "step": n_steps},
                    gstep, block=False,
                )
        sync(last_loss)  # host readback: honest timing on all backends
        dt = time.perf_counter() - t0

        eval_params = (
            opt.materialize(params) if args.zero_stage == 3 else params
        )
        metrics = evaluator.evaluate(
            eval_params, batch_iterator(val, local_bs, shuffle=False)
        )
        if comm.rank == 0:
            ips = n_seen / dt
            print(
                f"epoch {epoch}: train/loss {float(last_loss):.4f}  "
                + "  ".join(f"{k} {v:.4f}" for k, v in metrics.items())
                + f"  ({ips:,.0f} img/s)"
            )
    if ckpt is not None:
        ckpt.wait()
        from chainermn_tpu.utils.native import tree_digest

        digest_params = (
            opt.materialize(params) if args.zero_stage == 3 else params
        )
        if comm.rank == 0:
            print(
                f"final gstep {gstep} "
                f"params_digest {tree_digest(digest_params):08x}"
            )
    if reporter is not None:
        agg = reporter.aggregate(comm)
        if comm.rank == 0:
            print("telemetry: " + json.dumps(agg))
    telemetry.close()
    return params, metrics


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
