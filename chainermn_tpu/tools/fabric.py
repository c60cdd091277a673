"""``python -m chainermn_tpu.tools.fabric`` — one-process fabric soak:
an elastic training job and an autoscaled serving fleet trading chips
through the :mod:`chainermn_tpu.fabric` arbiter, under diurnal traffic.

Two modes share this module:

* **driver** (default): builds the whole resource fabric in one
  process — a :class:`~chainermn_tpu.fabric.ledger.ChipLedger` sized to
  the job, an :class:`~chainermn_tpu.elastic.supervisor.
  ElasticSupervisor` running the training plane on a daemon thread
  (ranks are REAL subprocesses of this module's ``--worker`` mode), an
  in-process serving fleet (router + autoscaler + SLO tracer), and the
  :class:`~chainermn_tpu.fabric.arbiter.FabricArbiter` brokering
  between them.  A diurnal :class:`~chainermn_tpu.serving.workload.
  TrafficSpec` replays against the fleet; peaks preempt trainer ranks
  for serving backfill, the post-peak trough drains a replica and
  returns the chips.  The last line is ``FABRIC_REPORT {json}`` with
  the training report (digest included), serve summary, stream oracle
  parity, ledger conservation, and the arbiter's transition counts —
  everything the multi-process soak asserts on.
* **--worker**: the supervised training rank.  Same shape as the
  elastic soak worker (init_from_env, naive communicator, multi-node
  checkpointer, beat / check_preemption / exit_preempted, reshard on
  resume) but the gradient combine is *partition-invariant*: each
  sample's contribution is quantized to int64 fixed point (2^16 scale)
  before summation, so the sum — and therefore every param bit — is
  identical for ANY world size and ANY rank partition.  That is what
  makes "bit-exact training resume across N→M→N′ rescales" a testable
  claim rather than a summation-order accident.

Chaos hook: ``--kill-rank-on-transfer R`` SIGKILLs trainer rank R the
first time a lease transition is in flight — the soak proves an
arbitration interrupted by real process death still converges with the
ledger conserved and the digest bit-exact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


# ---------------------------------------------------------------------
# worker mode: the supervised training rank
# ---------------------------------------------------------------------

_QSCALE = float(2 ** 16)  # fixed-point scale for the int64 combine


def _worker(args) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from chainermn_tpu import elastic

    ctx = elastic.init_from_env()
    assert ctx is not None, "must run under the elastic supervisor"

    import jax

    import chainermn_tpu
    from chainermn_tpu.extensions import create_multi_node_checkpointer
    from chainermn_tpu.utils.native import tree_digest

    comm = chainermn_tpu.create_communicator("naive")
    rank, world = comm.rank, comm.size
    assert args.batch % world == 0
    local = args.batch // world

    f32, f64 = np.float32, np.float64
    params = {"b": np.zeros((), f32), "w": np.zeros(args.dim, f32)}
    moments = {"b": np.zeros((), f32), "w": np.zeros(args.dim, f32)}
    rs = np.random.RandomState(7)
    w_true = rs.randn(args.dim).astype(f32)

    def global_batch(g):
        bs = np.random.RandomState(4242 + g)
        x = bs.randn(args.batch, args.dim).astype(f32)
        y = (x @ w_true + 0.1 * bs.randn(args.batch).astype(f32))
        return x, y.astype(f32)

    def local_int_grads(x, y, lo, hi):
        """Sum of this rank's per-sample SSE-gradient contributions,
        quantized sample-by-sample to int64 fixed point.  Each sample's
        quantized row depends only on (x_i, y_i, params) — never on
        which other samples share the rank — so the int64 totals (and
        the params they update) are bit-identical under ANY partition
        of the batch: the world size is invisible to the math."""
        w64 = params["w"].astype(f64)
        b64 = f64(params["b"])
        acc = np.zeros(args.dim + 2, np.int64)  # [gw..., gb, sse]
        for i in range(lo, hi):
            xi = x[i].astype(f64)
            r = float(xi @ w64 + b64 - f64(y[i]))
            row = np.concatenate([2.0 * r * xi, [2.0 * r], [r * r]])
            acc += np.rint(row * _QSCALE).astype(np.int64)
        return acc

    ckpt = create_multi_node_checkpointer(
        "fabric", comm, path=args.ckpt, keep_last_n=4
    )
    ctx.attach_checkpointer(ckpt)
    state = {"params": params, "opt": moments, "gstep": 0}
    loaded, it = ckpt.maybe_load(state)
    gstep = 0
    if it is not None:
        params, moments = loaded["params"], loaded["opt"]
        gstep = it
        if rank == 0:
            print(f"resumed from iteration {it}", flush=True)
        params, moments, rep = ctx.reshard(
            params, moments, comm, plan="dp", place=(world == 1)
        )
        if rank == 0:
            print(
                f"elastic_reshard plan=dp ok={rep.ok} "
                f"leaves={rep.n_leaves} world={world}",
                flush=True,
            )
        params = jax.tree.map(lambda a: np.asarray(a, f32), params)
        moments = jax.tree.map(lambda a: np.asarray(a, f32), moments)

    lr, mu = f32(args.lr), f32(0.9)
    for g in range(gstep, args.steps):
        ctx.beat(g)
        if ctx.check_preemption(comm):
            ckpt.save(
                {"params": params, "opt": moments, "gstep": g},
                g, block=True,
            )
            if rank == 0:
                print(f"preempted: checkpoint saved at iteration {g}",
                      flush=True)
            ctx.exit_preempted()
        if args.step_sleep > 0:
            time.sleep(args.step_sleep)
        x, y = global_batch(g)
        acc = local_int_grads(x, y, rank * local, (rank + 1) * local)
        if world > 1:
            acc = np.asarray(comm.allreduce_obj(acc), np.int64)
        deq = acc.astype(f64) / _QSCALE / f64(args.batch)
        gw = deq[:args.dim].astype(f32)
        gb = f32(deq[args.dim])
        loss = float(deq[args.dim + 1])
        moments["w"] = mu * moments["w"] + gw
        moments["b"] = mu * moments["b"] + gb
        params["w"] = params["w"] - lr * moments["w"]
        params["b"] = params["b"] - lr * moments["b"]
        gstep = g + 1
        if rank == 0:
            print(f"step {g} loss {loss:.6f}", flush=True)
        ckpt.save(
            {"params": params, "opt": moments, "gstep": gstep},
            gstep, block=False,
        )
    ckpt.wait()
    if rank == 0:
        print(
            f"final gstep {gstep} params_digest {tree_digest(params):08x}",
            flush=True,
        )
    print(f"ELASTIC_TRAIN_OK {rank}", flush=True)
    return 0


# ---------------------------------------------------------------------
# driver mode: both planes + the arbiter in one process
# ---------------------------------------------------------------------

def _driver(args) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.elastic.supervisor import (
        ElasticSupervisor,
        SupervisorConfig,
    )
    from chainermn_tpu.fabric import (
        ChipLedger,
        FabricArbiter,
        FabricPolicy,
        FabricPolicyConfig,
        TrainerHandle,
    )
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.observability import tracing
    from chainermn_tpu.observability.reporter import Reporter
    from chainermn_tpu.serving import EngineConfig, InferenceEngine
    from chainermn_tpu.serving import workload
    from chainermn_tpu.serving.cluster import (
        Autoscaler,
        AutoscalerConfig,
        HeartbeatMonitor,
        Replica,
        ReplicaRouter,
    )

    workdir = args.workdir or os.path.join(os.getcwd(), "fabric-soak")
    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = os.path.join(workdir, "ckpt")

    spec = workload.TrafficSpec.parse(args.traffic)
    if spec.vocab >= args.lm_vocab:
        raise SystemExit(
            f"--traffic vocab={spec.vocab} must stay below "
            f"--lm-vocab {args.lm_vocab}")
    arrivals = workload.generate(spec)

    reporter = Reporter()
    slo_targets = {}
    for item in (args.slo or "").split(","):
        if "=" in item:
            k, v = item.split("=", 1)
            slo_targets[k.strip()] = float(v)
    tr = None
    if slo_targets:
        tr = tracing.Tracer(
            reporter=reporter,
            slo=tracing.SLOConfig(targets=slo_targets),
        )
        tracing.install(tr)

    # -- serving plane -------------------------------------------------
    model = TransformerLM(
        vocab=args.lm_vocab, d_model=32, n_heads=2, d_ff=64,
        n_layers=1, max_len=args.serve_max_len,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    tenant_weights = spec.tenant_weights()

    def make_engine():
        return InferenceEngine(model, params, EngineConfig(
            block_size=args.serve_block_size,
            n_blocks=args.serve_blocks,
            max_len=args.serve_max_len,
            max_batch=args.serve_batch,
        ))

    def make_replica(rid):
        rep = Replica(rid, make_engine(), role="both",
                      reporter=reporter, max_queue=args.serve_queue)
        if tenant_weights:
            rep.scheduler.set_tenant_weights(tenant_weights)
        return rep

    reps = [make_replica(f"s{i}") for i in range(args.replicas)]
    router = ReplicaRouter(
        reps, reporter=reporter,
        health=HeartbeatMonitor([r.replica_id for r in reps],
                                miss_after_s=30.0),
    )
    # k_down is effectively infinite: under the fabric the ONLY
    # scale-down path is the arbiter's force_drain, so the autoscaler's
    # own trough hysteresis must never race it for the same replica.
    scaler = Autoscaler(
        router, make_replica,
        AutoscalerConfig(
            min_replicas=1,
            max_replicas=(args.replicas if args.no_arbiter else 64),
            k_up=2, k_down=10 ** 6, cooldown_s=0.5,
        ),
        reporter=reporter,
    )

    # -- training plane ------------------------------------------------
    sup = ElasticSupervisor(SupervisorConfig(
        argv=[
            sys.executable, "-m", "chainermn_tpu.tools.fabric",
            "--worker",
            "--ckpt", ckpt_dir,
            "--steps", str(args.train_steps),
            "--batch", str(args.train_batch),
            "--dim", str(args.train_dim),
            "--lr", str(args.lr),
            "--step-sleep", str(args.step_sleep),
        ],
        nproc=args.nproc,
        min_nproc=1,
        max_restarts=4,
        max_preemptions=64,
        heartbeat_timeout_s=args.hb_timeout,
        start_grace_s=120.0,
        grace_s=10.0,
        workdir=os.path.join(workdir, "elastic"),
        echo=bool(args.echo),
        env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        },
        barrier_timeout_s=30.0,
    ))
    sup.set_lease_tag("fabric")
    train_box = {}

    def run_train():
        train_box["report"] = sup.run()

    train_thread = threading.Thread(target=run_train, daemon=True)
    train_thread.start()
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if sup.running and sup.world > 0:
            break
        time.sleep(0.05)

    # -- the fabric ----------------------------------------------------
    total = args.total_chips or (args.nproc + args.replicas)
    ledger = ChipLedger(total)
    arb = None
    if not args.no_arbiter:
        arb = FabricArbiter(
            ledger, TrainerHandle(sup), scaler,
            policy=FabricPolicy(FabricPolicyConfig(
                min_train_ranks=1,
                min_serve_replicas=1,
                k_spike=args.k_spike,
                k_trough=args.k_trough,
                cooldown_s=args.fabric_cooldown,
                # The worker asserts batch % world == 0 for every world
                # it can be respawned at; capping growth at the launch
                # size keeps that divisibility a static property.
                max_train_ranks=args.nproc,
            )),
            reporter=reporter,
        )
        arb.bootstrap()

    kill_state = {"done": False}

    def maybe_kill():
        """--kill-rank-on-transfer: SIGKILL the named trainer rank the
        first time it is catchable while a lease transition is in
        flight — death mid-arbitration, the case the ledger's
        conservation audit and the resume bit-exactness must survive."""
        if args.kill_rank_on_transfer < 0 or kill_state["done"]:
            return
        if arb is None or not arb.events:
            return
        if not any(ev["action"] in ("preempt_start", "drain_start",
                                    "regrow_start")
                   for ev in arb.events):
            return
        with sup._ctl_lock:
            live = list(sup._live_ranks)
        for rk in live:
            if rk.rank == args.kill_rank_on_transfer \
                    and rk.proc.poll() is None:
                try:
                    rk.proc.kill()
                    kill_state["done"] = True
                except OSError:
                    pass

    # The fleet is driven synchronously from the replay pump (no
    # stepping threads): every pump iteration advances every replica a
    # little and THEN samples the watermarks, so a sustained backlog is
    # observed on consecutive polls — the shape the ScaleSignalFilter's
    # consecutive-vote hysteresis expects.  (Threaded stepping samples
    # at GIL-scheduling instants seconds apart under load, and a real
    # streak never forms.)
    def pump():
        router.step()
        scaler.step()
        if arb is not None:
            arb.step()
        maybe_kill()

    def submit(a):
        return router.submit(list(a.prompt), a.max_new_tokens,
                             timeout_s=600.0, priority=a.priority,
                             tenant=a.tenant)

    try:
        report = workload.replay(
            arrivals, submit, pump=pump, speedup=args.speedup,
            drain_timeout_s=600.0,
        )
        # Post-peak trough: traffic is gone, so keep arbitrating until
        # the chips have made a full round trip (or training ended, or
        # the deadline says the day is over).
        phase_deadline = time.monotonic() + args.deadline_s
        while time.monotonic() < phase_deadline:
            pump()
            if arb is None:
                break
            done_round_trip = (
                arb.transitions["preempt_for_serving"] >= 1
                and arb.transitions["return_to_training"] >= 1
                and arb._pending is None
            )
            if done_round_trip:
                break
            if not sup.running and arb._pending is None:
                break
            time.sleep(0.01)
        for _ in range(200):
            if scaler._draining is None:
                break
            pump()
            time.sleep(0.01)
        router.run_until_idle()
    finally:
        if tr is not None:
            tracing.uninstall(tr)
            tr.close()

    train_thread.join(timeout=600.0)
    if arb is not None:
        arb.step()  # collect train_done; the job's lease goes free
    train_report = train_box.get("report") or {"status": "timeout"}

    # -- stream oracle parity ------------------------------------------
    oracle = InferenceEngine(model, params, EngineConfig(
        block_size=args.serve_block_size,
        n_blocks=args.serve_blocks,
        max_len=args.serve_max_len, max_batch=1,
    ))
    mismatches = [
        o.arrival.index for o in report.outcomes if o.finished
        and list(o.handle.tokens) != oracle.generate(
            list(o.arrival.prompt), o.arrival.max_new_tokens)
    ]

    summary = workload.summarize(report)
    dropped = (summary["offered"] - summary["finished"]
               - summary["shed"] - summary["rejected"])
    gauges = reporter.summary().get("gauges", {})
    burn_rates = {
        k.split("/", 2)[2]: round(float(v["value"]), 4)
        for k, v in gauges.items() if k.startswith("slo/burn_rate/")
    }
    tenant_deficits = {
        k.split("/", 2)[2]: round(float(v["value"]), 3)
        for k, v in gauges.items()
        if k.startswith("serve/tenant_deficit/")
    }

    out = {
        "arbiter": not args.no_arbiter,
        "train": train_report,
        "serve": summary,
        "dropped_streams": dropped,
        "parity": {
            "checked": sum(1 for o in report.outcomes if o.finished),
            "mismatches": mismatches,
        },
        "burn_rates": burn_rates,
        "tenant_deficits": tenant_deficits,
        "replicas_final": len(router.replicas),
        "chaos_kill_fired": kill_state["done"],
        "transitions": dict(arb.transitions) if arb is not None else {},
        "fabric_events": (
            [{k: (round(v, 3) if isinstance(v, float) else v)
              for k, v in ev.items() if k != "t"}
             for ev in arb.events] if arb is not None else []
        ),
        "ledger": ledger.as_report() if arb is not None else None,
        "ledger_conserved": (
            ledger.conserved() if arb is not None else True
        ),
    }
    print("FABRIC_REPORT " + json.dumps(out, sort_keys=True), flush=True)
    ok = (
        train_report.get("status") == "ok"
        and not mismatches
        and dropped == 0
        and out["ledger_conserved"]
    )
    return 0 if ok else 1


# ---------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m chainermn_tpu.tools.fabric",
        description="one-process training/serving resource-fabric soak",
    )
    p.add_argument("--worker", action="store_true",
                   help="internal: run as a supervised training rank")
    # worker knobs (also consumed by the driver to build the argv)
    p.add_argument("--ckpt", default=None)
    p.add_argument("--steps", type=int, default=16, dest="steps")
    p.add_argument("--batch", type=int, default=24, dest="batch")
    p.add_argument("--dim", type=int, default=8, dest="dim")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--step-sleep", type=float, default=0.25,
                   help="per-step sleep so the training job spans the "
                        "whole serve day-curve (does not touch the "
                        "math: the digest is sleep-invariant)")
    # driver: planes
    p.add_argument("--nproc", type=int, default=2,
                   help="initial trainer world size")
    p.add_argument("--replicas", type=int, default=2,
                   help="initial serving fleet size")
    p.add_argument("--total-chips", type=int, default=0,
                   help="ledger size (0 = nproc + replicas: no slack)")
    p.add_argument("--train-steps", type=int, default=240)
    p.add_argument("--train-batch", type=int, default=24,
                   help="global batch; must divide by every reachable "
                        "world size")
    p.add_argument("--train-dim", type=int, default=8)
    p.add_argument("--hb-timeout", type=float, default=60.0)
    p.add_argument("--echo", action="store_true",
                   help="prefix-echo trainer rank output")
    # driver: traffic + serving geometry
    p.add_argument("--traffic",
                   default="requests=110,rate=26,burst=3,diurnal=0.6,"
                           "diurnal_period_s=8,tenants=2,vocab=24")
    p.add_argument("--speedup", type=float, default=1.0)
    p.add_argument("--lm-vocab", type=int, default=48)
    p.add_argument("--serve-block-size", type=int, default=8)
    p.add_argument("--serve-blocks", type=int, default=48)
    p.add_argument("--serve-max-len", type=int, default=160)
    p.add_argument("--serve-batch", type=int, default=4)
    p.add_argument("--serve-queue", type=int, default=6)
    p.add_argument("--slo", default="queue=30,decode=30")
    # driver: fabric policy
    p.add_argument("--k-spike", type=int, default=3)
    p.add_argument("--k-trough", type=int, default=4)
    p.add_argument("--fabric-cooldown", type=float, default=0.75)
    p.add_argument("--deadline-s", type=float, default=120.0,
                   help="post-replay arbitration budget")
    p.add_argument("--no-arbiter", action="store_true",
                   help="oracle baseline: fixed fleet, untouched "
                        "training, no ledger")
    p.add_argument("--kill-rank-on-transfer", type=int, default=-1,
                   help="SIGKILL this trainer rank during the first "
                        "in-flight lease transition (chaos)")
    p.add_argument("--workdir", default=None)
    args = p.parse_args(argv)

    if args.worker:
        if not args.ckpt:
            p.error("--worker requires --ckpt")
        return _worker(args)
    return _driver(args)


if __name__ == "__main__":
    sys.exit(main())
