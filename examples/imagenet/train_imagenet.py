#!/usr/bin/env python
"""Data-parallel ImageNet ResNet — the throughput configuration.

Reference: REF:examples/imagenet/train_imagenet.py — per-rank
MultiprocessIterator feeding a ResNet-50, hierarchical/pure_nccl
communicators, linear LR scaling with warmup.  This is BASELINE config #2
and the source of the ``images/sec/chip`` headline metric.

TPU-native shape: bf16 NHWC ResNet, global-batch arrays sharded over the
mesh by the jitted step, BatchNorm statistics pmean-synced across replicas,
SGD+momentum with the linear-scaling warmup schedule of the large-minibatch
papers the reference stack pioneered (arXiv:1711.04325).

Data: zero-egress environment → synthetic ImageNet-shaped dataset by
default; pass ``--data-npz`` with ``images``/``labels`` arrays for real
data.
"""

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.utils.profiling import sync
from chainermn_tpu.datasets.toy import SyntheticImageDataset, batch_iterator
from chainermn_tpu.extensions import Evaluator
from chainermn_tpu.models.convnets import AlexNet, GoogLeNet, NiN
from chainermn_tpu.models.resnet import ResNet18, ResNet50
from chainermn_tpu.observability import startup


def main(argv=None):
    p = argparse.ArgumentParser(description="chainermn_tpu ImageNet example")
    p.add_argument("--communicator", default="xla_ici")
    p.add_argument("--bucket-bytes", type=int, default=None,
                   help="gradient-allreduce bucket cap in bytes "
                        "(0 disables bucketing; default: 4 MiB / "
                        "CHAINERMN_TPU_BUCKET_BYTES — docs/performance.md)")
    p.add_argument("--arch", "--model", dest="arch", default="resnet50",
                   choices=["resnet50", "resnet18", "alex", "nin", "googlenet"],
                   help="model architecture (reference: train_imagenet.py --arch)")
    p.add_argument("--batchsize", type=int, default=256, help="global batch")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--optimizer", choices=["sgd", "lars"], default="sgd",
                   help="lars = layer-wise adaptive rates for very large "
                   "global batches")
    p.add_argument("--warmup-steps", type=int, default=100)
    p.add_argument("--train-size", type=int, default=4096)
    p.add_argument("--val-size", type=int, default=512)
    p.add_argument("--steps", type=int, default=None, help="cap steps/epoch")
    p.add_argument("--data-npz", default=None)
    p.add_argument("--prefetch", type=int, default=2,
                   help="device-prefetch queue depth (0 disables) — the "
                   "reference's MultiprocessIterator overlap")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable fault tolerance: multi-node checkpointer "
                   "saves here and auto-resumes from the newest consistent "
                   "generation on relaunch (reference: "
                   "create_multi_node_checkpointer + maybe_load)")
    p.add_argument("--checkpoint-every", type=int, default=50,
                   help="save a generation every N global steps")
    p.add_argument("--checkpoint-name", default="imagenet",
                   help="checkpoint set name under --checkpoint-dir")
    p.add_argument("--elastic", action="store_true",
                   help="join the elastic supervisor's world "
                   "(CHAINERMN_TPU_ELASTIC_* env): heartbeats, chaos "
                   "faults, SIGTERM-as-preemption, and plan-driven "
                   "resharding on rescale.  A no-op outside a "
                   "supervised run.")
    p.add_argument("--step-log", default=None, metavar="PATH",
                   help="write a JSONL step-event log (per-step timing, "
                        "loss, compile events, device memory, one "
                        "hlo_audit row); summarize with `python -m "
                        "chainermn_tpu.tools.obs summarize PATH`")
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
    if comm.rank == 0:
        print(f"communicator: {comm!r}")

    shape = (args.image_size, args.image_size, 3)
    if args.data_npz:
        raw = np.load(args.data_npz)
        images, labels = raw["images"], raw["labels"]
        train = list(zip(images, labels))
        val = train[: args.val_size]
    else:
        train = SyntheticImageDataset(
            n=args.train_size, shape=shape, n_classes=args.num_classes, seed=0
        )
        val = SyntheticImageDataset(
            n=args.val_size, shape=shape, n_classes=args.num_classes, seed=1
        )
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=42)
    val = chainermn_tpu.scatter_dataset(val, comm)

    archs = {
        "resnet50": ResNet50, "resnet18": ResNet18,
        "alex": AlexNet, "nin": NiN, "googlenet": GoogLeNet,
    }
    model = archs[args.arch](num_classes=args.num_classes)
    has_bn = args.arch.startswith("resnet")
    with startup.phase("weights"):
        variables = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, *shape), jnp.float32),
            train=False,
        )
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    # Linear-scaling rule with warmup (the reference stack's large-batch
    # recipe): lr = base * (global_batch / 256), warmed up from 0.
    # --optimizer lars is the layer-wise adaptive-rate variant the
    # extreme-batch ResNet results (arXiv:1711.04325-era) relied on.
    scaled_lr = args.lr * args.batchsize / 256.0
    sched = optax.linear_schedule(0.0, scaled_lr, args.warmup_steps)
    if args.optimizer == "lars":
        inner = optax.lars(sched, momentum=0.9, weight_decay=1e-4)
    else:
        inner = optax.sgd(sched, momentum=0.9, nesterov=False)
    opt = chainermn_tpu.create_multi_node_optimizer(inner, comm)
    state = opt.init(params)

    if has_bn:
        def loss_fn(params, batch_stats, batch):
            x, y = batch
            logits, updates = model.apply(
                {"params": params, "batch_stats": batch_stats},
                x,
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
            return loss, updates["batch_stats"]

        step = opt.make_train_step_with_state(loss_fn)
    else:
        # Dropout architectures: rng threaded per (step, device) by the
        # optimizer wrapper.
        def rng_loss_fn(params, batch, rng):
            x, y = batch
            logits = model.apply(
                {"params": params}, x, train=True, rngs={"dropout": rng}
            )
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        plain_step = opt.make_train_step(rng_loss_fn, rng=jax.random.PRNGKey(7))

        def step(params, state, batch_stats, batch):
            params, state, loss = plain_step(params, state, batch)
            return params, state, batch_stats, loss

    def metric_fn(params_and_stats, batch):
        params, batch_stats = params_and_stats
        x, y = batch
        variables = {"params": params}
        if has_bn:
            variables["batch_stats"] = batch_stats
        logits = model.apply(variables, x, train=False)
        return {
            "val/loss": optax.softmax_cross_entropy_with_integer_labels(logits, y).mean(),
            "val/accuracy": (logits.argmax(-1) == y).mean(),
        }

    evaluator = Evaluator(metric_fn, comm)

    # --step-log: opt-in telemetry for the whole run.  Note the per-step
    # float(loss) readback below serializes host and device — leave the
    # flag off when chasing headline img/s.
    telemetry = contextlib.ExitStack()
    reporter = recorder = None
    if args.step_log:
        from chainermn_tpu import observability as obs

        reporter = obs.Reporter()
        telemetry.enter_context(obs.scope(reporter))
        recorder = telemetry.enter_context(
            obs.StepRecorder(args.step_log, rank=comm.rank)
        )

    # Fault tolerance (reference: REF:examples' checkpointer usage +
    # REF:chainermn/extensions/checkpoint.py): a crashed/killed run
    # relaunched with the same command line resumes from the newest
    # consistent generation — mid-epoch, at the exact step — and the
    # global except hook turns any rank's uncaught error into a whole-job
    # abort instead of a hang.
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
        template = {
            "params": params, "state": state, "batch_stats": batch_stats,
            "epoch": 0, "step": 0,
        }
        loaded, it = ckpt.maybe_load(template)
        if it is not None:
            params, state = loaded["params"], loaded["state"]
            batch_stats = loaded["batch_stats"]
            start_epoch, start_step = int(loaded["epoch"]), int(loaded["step"])
            gstep = it
            if comm.rank == 0:
                print(
                    f"resumed from iteration {it} "
                    f"(epoch {start_epoch}, step {start_step})"
                )
            if ctx is not None:
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

    # Multi-process deployment (the reference's mpiexec shape): each
    # process draws a LOCAL slice of the global batch from its scattered
    # shard and comm.global_batch assembles the device-global arrays.
    if args.batchsize % comm.size:
        raise SystemExit(
            f"--batchsize {args.batchsize} must divide by the process "
            f"count {comm.size}"
        )
    local_bs = args.batchsize // comm.size

    def host_batches(epoch):
        # Host-side work (cast/augment) runs here — inside the prefetch
        # thread when enabled, overlapped with device compute.
        for batch in batch_iterator(train, local_bs, seed=epoch):
            yield (batch[0].astype(np.float32), batch[1])

    for epoch in range(start_epoch, args.epochs):
        t0, n_seen, last_loss, n_steps = time.perf_counter(), 0, float("nan"), 0
        # Resuming into this epoch: replay the iterator (same epoch seed →
        # same permutation) and drop the batches already trained on.
        skip = start_step if epoch == start_epoch else 0
        start_step = 0
        batches = host_batches(epoch)
        if args.prefetch > 0:
            batches = chainermn_tpu.create_prefetch_iterator(
                batches, size=args.prefetch
            )
        for batch in batches:
            if skip > 0:
                skip -= 1
                n_steps += 1
                if ctx is not None:
                    ctx.beat(gstep)  # liveness during replay
                if args.steps and n_steps >= args.steps:
                    break  # the cap counts replayed steps too
                continue
            if ctx is not None:
                ctx.beat(gstep)  # chaos faults fire here, deterministically
                if ckpt is not None and ctx.check_preemption(comm):
                    # Grace-window synchronous checkpoint: every rank
                    # arrives here at the same step, saves, and exits
                    # with the preemption code (not a crash).
                    ckpt.save(
                        {"params": params, "state": state,
                         "batch_stats": batch_stats,
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

                a = obs.audit_fn(getattr(step, "__wrapped__", step),
                                 params, state, batch_stats, gb)
                recorder.record("hlo_audit", counts=a.counts,
                                bytes_per_axis=a.bytes_per_axis)
            params, state, batch_stats, loss = step(
                params, state, batch_stats, gb
            )
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
                     "batch_stats": batch_stats,
                     "epoch": epoch, "step": n_steps},
                    gstep, block=False,
                )
            if args.steps and n_steps >= args.steps:
                break
        sync(last_loss)  # host readback: honest timing on all backends
        dt = time.perf_counter() - t0

        metrics = evaluator.evaluate(
            (params, batch_stats),
            batch_iterator(val, local_bs, shuffle=False),
        )
        if comm.rank == 0:
            ips = n_seen / dt
            per_chip = ips / comm.device_size
            print(
                f"epoch {epoch}: loss {float(last_loss):.4f}  "
                + "  ".join(f"{k} {v:.4f}" for k, v in metrics.items())
                + f"  {ips:,.1f} img/s ({per_chip:,.1f}/chip)"
            )
    if ckpt is not None:
        ckpt.wait()
        from chainermn_tpu.utils.native import tree_digest

        if comm.rank == 0:
            print(
                f"final gstep {gstep} params_digest {tree_digest(params):08x}"
            )
    if reporter is not None:
        agg = reporter.aggregate(comm)
        if comm.rank == 0:
            print("telemetry: " + json.dumps(agg))
    telemetry.close()
    return params, batch_stats


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
