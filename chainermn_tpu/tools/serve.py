"""Multi-replica serving CLI.

Launches the serving cluster tier from the shell in either of two
shapes:

``--role local`` (default)
    Everything in this process: N in-process replicas behind a
    :class:`~chainermn_tpu.serving.cluster.ReplicaRouter`, threaded
    per-replica stepping, synthetic request traffic, one JSON report on
    stdout.  ``--verify`` additionally replays every prompt through a
    sequential single-engine oracle and asserts the routed streams are
    bit-identical — the smoke test CI runs.

``--role router`` / ``--role replica``
    One process per role over the host object plane (the
    :mod:`~chainermn_tpu.serving.cluster.service` wire protocol).
    Every process first joins the same ``jax.distributed`` coordinator
    (``--coordinator host:port --num-processes N --process-id i``);
    process 0 must be the router.  The router drives the synthetic
    traffic and prints the same JSON report shape.

Usage::

    # in-process smoke: 2 replicas, oracle parity check
    python -m chainermn_tpu.tools.serve --replicas 2 --verify

    # heavy-tailed traffic + SLO-guarded autoscaling + timed chaos
    python -m chainermn_tpu.tools.serve --replicas 2 --autoscale \
        --traffic "rate=120,requests=32,abusive_frac=0.2" \
        --chaos "kill:replica=1:at=0.5" --verify

    # same, with a Chrome/Perfetto trace of every request
    python -m chainermn_tpu.tools.serve --replicas 2 \
        --roles prefill,decode --prefill-threshold 8 \
        --trace-out /tmp/serve_trace.json

    # disaggregated roles: replica 0 prefills, replica 1 decodes
    python -m chainermn_tpu.tools.serve --replicas 2 \
        --roles prefill,decode --prefill-threshold 16

    # multi-process (three shells):
    python -m chainermn_tpu.tools.serve --role router \
        --coordinator 127.0.0.1:9123 --num-processes 3 --process-id 0
    python -m chainermn_tpu.tools.serve --role replica \
        --coordinator 127.0.0.1:9123 --num-processes 3 --process-id 1
    python -m chainermn_tpu.tools.serve --role replica \
        --coordinator 127.0.0.1:9123 --num-processes 3 --process-id 2

    # tensor-parallel shard groups, spawned locally: one router + one
    # group of 2 shard processes; parity against the single-process
    # oracle under BOTH greedy and sampled decoding
    python -m chainermn_tpu.tools.serve --tp 2 --verify

    # two tp=2 groups with pipelined decode microbatching (pp=2 per
    # group -> 4 processes per group)
    python -m chainermn_tpu.tools.serve --tp 2 --pp 2 --groups 2

The model is the repo's own TransformerLM with randomly initialized
parameters (geometry from the ``--vocab``/``--d-model``/... flags);
every process derives identical params from ``--seed``, which is what
makes cross-replica migration and the oracle parity check meaningful.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional


def _build_model(args):
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.observability import startup

    model = TransformerLM(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.heads,
        d_ff=args.d_ff, n_layers=args.layers, max_len=args.max_len,
    )
    with startup.phase("weights"):
        params = model.init(
            jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
        )
    return model, params


def _engine_factory(args):
    from chainermn_tpu.serving import EngineConfig, InferenceEngine

    model, params = _build_model(args)

    def factory():
        return InferenceEngine(model, params, EngineConfig(
            block_size=args.block_size, n_blocks=args.n_blocks,
            max_len=args.max_len, max_batch=args.max_batch,
            draft=args.draft,
            draft_layers=args.draft_layers,
            prefill_chunk=args.prefill_chunk,
            sp=args.sp,
            max_len_growth=args.max_len_growth,
        ))

    return factory


def _synthetic_prompts(args) -> List[List[int]]:
    import numpy as np

    rng = np.random.default_rng(args.seed)
    lens = rng.integers(
        max(1, args.prompt_len // 2), args.prompt_len + 1,
        size=args.requests,
    )
    return [
        [int(t) for t in rng.integers(1, args.vocab, size=int(n))]
        for n in lens
    ]


def _parse_roles(spec: Optional[str], n: int) -> List[str]:
    from chainermn_tpu.serving.cluster.replica import ROLES

    if not spec:
        return ["both"] * n
    roles = [r.strip() for r in spec.split(",")]
    if len(roles) != n:
        raise SystemExit(
            f"--roles names {len(roles)} roles for {n} replicas"
        )
    for r in roles:
        if r not in ROLES:
            raise SystemExit(f"unknown role {r!r} (choose from {ROLES})")
    return roles


def _report(args, results: dict, wall: float, extra: dict) -> dict:
    tokens = sum(len(r["tokens"]) for r in results.values())
    statuses: dict = {}
    for r in results.values():
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    report = {
        "mode": args.role,
        "replicas": args.replicas,
        "requests": len(results),
        "statuses": statuses,
        "tokens": tokens,
        "tokens_per_sec": round(tokens / wall, 1) if wall > 0 else None,
        "failovers": sum(r["failovers"] for r in results.values()),
        "config": {
            "vocab": args.vocab, "d_model": args.d_model,
            "n_layers": args.layers, "max_len": args.max_len,
            "block_size": args.block_size, "n_blocks": args.n_blocks,
            "max_batch": args.max_batch, "max_queue": args.max_queue,
            "watermark_blocks": args.watermark,
            "prefill_threshold": args.prefill_threshold,
            "draft": args.draft, "draft_layers": args.draft_layers,
            "prefill_chunk": args.prefill_chunk,
            "sp": args.sp, "max_len_growth": args.max_len_growth,
        },
    }
    report.update(extra)
    return report


def _oracle_streams(args, prompts, samplings=None) -> List[List[int]]:
    """Sequential single-engine reference streams (one fresh engine so
    cache state can't leak between the oracle and the cluster).
    ``samplings`` — optional per-prompt sampling dicts ({} = greedy),
    so sampled-decode legs verify against the same counter-based RNG."""
    from chainermn_tpu.serving import SamplingParams

    eng = _engine_factory(args)()
    samplings = samplings or [{}] * len(prompts)
    return [
        eng.generate(p, args.new_tokens,
                     sampling=SamplingParams(**s) if s else None)
        for p, s in zip(prompts, samplings)
    ]


def _request_samplings(args, n: int) -> List[dict]:
    """Per-request sampling policies: greedy everywhere, except
    ``--sampled`` makes every odd request temperature/top-k sampled —
    so one sweep exercises BOTH decode paths and ``--verify`` proves
    each against the oracle's identical counter-based RNG."""
    if not args.sampled:
        return [{}] * n
    return [
        {} if i % 2 == 0
        else {"temperature": 0.8, "top_k": 8, "seed": 1000 + i}
        for i in range(n)
    ]


def _parse_slo(text: Optional[str]):
    """``"queue=2.0,decode=1.0"`` → SLOConfig, None when unset."""
    if not text:
        return None
    from chainermn_tpu.observability.tracing import SLOConfig

    targets = {}
    for item in text.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" not in item:
            raise SystemExit(
                f"--slo expects stage=seconds, got {item!r}"
            )
        k, v = item.split("=", 1)
        targets[k.strip()] = float(v)
    return SLOConfig(targets=targets)


def _install_tracer(args, reporter=None, slo=None):
    """Install a process-wide tracer when --trace-out/--flight-dir asks
    for one (or an SLO config needs burn-rate gauges).  Returns
    (tracer, uninstall_cb); (None, noop) untraced."""
    import os

    from chainermn_tpu.observability import tracing

    if not (args.trace_out or args.flight_dir or slo is not None):
        return None, lambda: None
    flight = None
    if args.flight_dir:
        os.makedirs(args.flight_dir, exist_ok=True)
        flight = tracing.FlightRecorder(
            os.path.join(args.flight_dir, "flight_local.jsonl")
        )
    tr = tracing.Tracer(flight=flight, reporter=reporter, slo=slo)
    tracing.install(tr)

    def done():
        tracing.uninstall(tr)
        tr.close()

    return tr, done


def _export_trace(args, tr, extra: dict) -> None:
    """Write the Chrome trace to --trace-out and fold per-stage
    percentiles into the report."""
    import json as _json

    from chainermn_tpu.observability import tracing

    recs = tr.records()
    if args.trace_out:
        with open(args.trace_out, "w") as f:
            _json.dump(tracing.to_chrome_trace(recs), f)
    stages = tracing.stage_percentiles(recs)
    extra["trace_stages"] = {
        name: {"count": st["count"], "p50_s": st["p50_s"],
               "p99_s": st["p99_s"]}
        for name, st in sorted(stages.items())
    }
    extra["traces"] = len({
        r.get("trace") for r in recs if r.get("trace")
    })


def run_local_traffic(args) -> int:
    """``--traffic`` mode: replay a seeded heavy-tailed workload over
    the fleet, optionally with the SLO-guarded autoscaler closing the
    loop (``--autoscale``) and timed chaos faults (``--chaos``)."""
    from chainermn_tpu.elastic.chaos import ChaosSchedule, TimedChaos
    from chainermn_tpu.observability.reporter import Reporter
    from chainermn_tpu.serving import workload
    from chainermn_tpu.serving.cluster import (
        Autoscaler,
        AutoscalerConfig,
        HeartbeatMonitor,
        Replica,
        ReplicaRouter,
        ThreadedClusterDriver,
    )

    factory = _engine_factory(args)
    roles = _parse_roles(args.roles, args.replicas)
    reporter = Reporter()
    tr, tr_done = _install_tracer(
        args, reporter=reporter, slo=_parse_slo(args.slo)
    )
    spec = workload.TrafficSpec.parse(args.traffic)
    if spec.vocab >= args.vocab:
        raise SystemExit(
            f"--traffic vocab={spec.vocab} must stay below the model's "
            f"--vocab {args.vocab}"
        )

    def replica_factory(rid):
        return Replica(
            rid, factory(), role="both", reporter=reporter,
            watermark_blocks=args.watermark, max_queue=args.max_queue,
            spec_tokens=args.spec_tokens,
        )

    replicas = [
        Replica(
            i, factory(), role=roles[i], reporter=reporter,
            watermark_blocks=args.watermark, max_queue=args.max_queue,
            spec_tokens=args.spec_tokens,
        )
        for i in range(args.replicas)
    ]
    router = ReplicaRouter(
        replicas,
        prefill_threshold=args.prefill_threshold,
        reporter=reporter,
        health=HeartbeatMonitor(
            [r.replica_id for r in replicas], miss_after_s=30.0
        ),
    )
    autoscaler = None
    if args.autoscale:
        autoscaler = Autoscaler(
            router, replica_factory,
            AutoscalerConfig(
                min_replicas=args.replicas,
                max_replicas=args.max_replicas or args.replicas + 2,
            ),
            reporter=reporter,
        )
    exporter = _start_exporter(args, router.fleet_view)
    chaos = None
    if args.chaos:
        chaos = TimedChaos(ChaosSchedule.parse(args.chaos))

    arrivals = workload.generate(spec)

    def fire(fault) -> None:
        rid = fault.replica
        if rid is None:
            alive = [r.replica_id for r in router.replicas.values()
                     if r.alive]
            rid = alive[0] if alive else None
        if rid is None or rid not in router.replicas:
            return
        if fault.kind == "kill":
            router.fail_replica(rid, reason="chaos kill")
        elif fault.kind == "term":
            router.drain(rid)

    t0 = time.perf_counter()
    with ThreadedClusterDriver(router) as drv:
        def pump():
            drv.ensure_threads()
            router.step(drive_replicas=False)
            if autoscaler is not None:
                autoscaler.step()
            if chaos is not None:
                for f in chaos.due():
                    fire(f)

        report = workload.replay(
            arrivals,
            lambda a: router.submit(
                list(a.prompt), a.max_new_tokens,
                timeout_s=args.timeout_s, priority=a.priority,
                tenant=a.tenant,
            ),
            pump=pump, drain_timeout_s=args.timeout_s,
        )
        drv.run_until_idle(timeout_s=args.timeout_s)
    wall = time.perf_counter() - t0

    traffic = workload.summarize(report)
    traffic["spec"] = spec.format()
    if autoscaler is not None:
        traffic["autoscaler_events"] = [
            {k: (round(v, 3) if isinstance(v, float) else v)
             for k, v in ev.items() if k != "t"}
            for ev in autoscaler.events
        ]
        traffic["replicas_final"] = len(router.replicas)
    gauges = reporter.summary().get("gauges", {})
    traffic["burn_rates"] = {
        k.split("/", 2)[2]: round(float(v["value"]), 4)
        for k, v in gauges.items()
        if k.startswith("slo/burn_rate/")
    }
    counters = reporter.summary().get("counters", {})
    traffic["shed_counters"] = {
        k: v for k, v in sorted(counters.items())
        if k.startswith(("serve/shed/", "serve/admit/",
                         "serve/rejected/"))
    }

    finished = [o for o in report.outcomes if o.finished]
    results = {
        o.arrival.index: {
            "tokens": list(o.handle.tokens), "status": o.handle.status,
            "failovers": o.handle.failovers,
        }
        for o in report.outcomes if o.handle is not None
    }
    extra = {"roles": roles, "traffic": traffic}
    if args.verify:
        eng = _engine_factory(args)()
        mismatches = [
            o.arrival.index for o in finished
            if list(o.handle.tokens) != eng.generate(
                list(o.arrival.prompt), o.arrival.max_new_tokens
            )
        ]
        extra["parity"] = "ok" if not mismatches else "FAIL"
        extra["parity_mismatches"] = mismatches
    if tr is not None:
        _export_trace(args, tr, extra)
    tr_done()
    if exporter is not None:
        extra["metrics_url"] = exporter.url
        exporter.stop()
    print(json.dumps(_report(args, results, wall, extra)))
    if args.verify and extra["parity"] != "ok":
        return 1
    return 0


def run_local(args) -> int:
    from chainermn_tpu.observability.reporter import Reporter
    from chainermn_tpu.serving.cluster import (
        HeartbeatMonitor,
        Replica,
        ReplicaRouter,
        ThreadedClusterDriver,
    )

    tr, tr_done = _install_tracer(args)
    factory = _engine_factory(args)
    roles = _parse_roles(args.roles, args.replicas)
    # A metrics endpoint needs a registry to serve: one shared Reporter
    # across replicas + router (in-process, so the shared registry IS
    # the fleet view).
    reporter = Reporter() if args.metrics_port is not None else None
    replicas = [
        Replica(
            i, factory(), role=roles[i], reporter=reporter,
            watermark_blocks=args.watermark, max_queue=args.max_queue,
            spec_tokens=args.spec_tokens,
        )
        for i in range(args.replicas)
    ]
    router = ReplicaRouter(
        replicas,
        prefill_threshold=args.prefill_threshold,
        reporter=reporter,
        health=HeartbeatMonitor(
            [r.replica_id for r in replicas], miss_after_s=30.0
        ),
    )
    exporter = _start_exporter(args, router.fleet_view)
    prompts = _synthetic_prompts(args)

    t0 = time.perf_counter()
    with ThreadedClusterDriver(router) as drv:
        handles = [
            router.submit(p, args.new_tokens, timeout_s=args.timeout_s)
            for p in prompts
        ]
        drv.run_until_idle(timeout_s=args.timeout_s)
    wall = time.perf_counter() - t0

    results = {
        h.request_id: {
            "tokens": list(h.tokens), "status": h.status,
            "failovers": h.failovers,
        }
        for h in handles
    }
    extra = {
        "roles": roles,
        "replicas_used": sorted(
            {repr(h.replica_id) for h in handles
             if h.replica_id is not None}
        ),
    }
    if args.verify:
        oracle = _oracle_streams(args, prompts)
        mismatches = [
            i for i, (h, o) in enumerate(zip(handles, oracle))
            if h.tokens != o
        ]
        extra["parity"] = "ok" if not mismatches else "FAIL"
        extra["parity_mismatches"] = mismatches
    if tr is not None:
        _export_trace(args, tr, extra)
    tr_done()
    if exporter is not None:
        extra["metrics_url"] = exporter.url
        exporter.stop()
    print(json.dumps(_report(args, results, wall, extra)))
    if args.verify and extra["parity"] != "ok":
        return 1
    if any(r["status"] != "finished" for r in results.values()):
        return 1
    return 0


def _start_exporter(args, source):
    """Start a /metrics scrape endpoint over ``source`` when
    --metrics-port asks for one.  Returns the running exporter or
    None."""
    if args.metrics_port is None:
        return None
    from chainermn_tpu.observability import MetricsExporter

    exporter = MetricsExporter(source, port=args.metrics_port)
    exporter.start()
    return exporter


def _init_distributed(args) -> None:
    import jax

    from chainermn_tpu.observability import startup

    if not args.coordinator:
        raise SystemExit(
            "--role router/replica needs --coordinator host:port"
        )
    jax.distributed.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    # Force backend creation NOW, on every rank: the global topology
    # exchange blocks until all processes join, and a router that never
    # touches jax would otherwise deadlock the whole cluster.
    with startup.phase("backend"):
        jax.devices()


def _flight_path(args) -> Optional[str]:
    import os

    if not args.flight_dir:
        return None
    os.makedirs(args.flight_dir, exist_ok=True)
    name = ("flight_router.jsonl" if args.role == "router"
            else f"flight_{args.process_id}.jsonl")
    return os.path.join(args.flight_dir, name)


def run_multiprocess(args) -> int:
    from chainermn_tpu.serving.cluster import service
    from chainermn_tpu.serving.cluster.shard_group import plan_groups

    _init_distributed(args)
    size = args.num_processes
    # Shard-group topology (identity when --tp/--pp are 1): replica
    # ranks partition into consecutive leader+followers runs; the
    # router only ever talks to leaders.
    groups = plan_groups(size, args.tp, args.pp)
    if args.role == "replica":
        group = None
        if args.tp * args.pp > 1:
            group = next(
                g for g in groups if args.process_id in g.ranks
            )
        role = (args.replica_role or "both")
        out = service.run_replica(
            args.process_id, size, _engine_factory(args),
            role=role, max_queue=args.max_queue,
            watermark_blocks=args.watermark,
            flight_path=_flight_path(args),
            metrics_port=args.metrics_port,
            group=group,
        )
        print(json.dumps({"mode": "replica", "rank": args.process_id,
                          **out}))
        return 0

    if args.process_id != 0:
        raise SystemExit("--role router must be --process-id 0")
    args.replicas = len(groups)
    prompts = _synthetic_prompts(args)
    samplings = _request_samplings(args, len(prompts))
    requests = [
        {"prompt": p, "max_new_tokens": args.new_tokens,
         "timeout_s": args.timeout_s, "sampling": s}
        for p, s in zip(prompts, samplings)
    ]
    t0 = time.perf_counter()
    results = service.run_router(
        size, requests,
        prefill_threshold=args.prefill_threshold,
        # Cold jit compiles stall a replica for seconds on CPU; real
        # deaths are detected much faster via socket EOF -> PeerGone.
        miss_after_s=args.miss_after_s,
        timeout_s=args.timeout_s,
        flight_path=_flight_path(args),
        metrics_port=args.metrics_port,
        metrics_port_file=args.metrics_port_file,
        group_size=args.tp,
        pp_stages=args.pp,
    )
    wall = time.perf_counter() - t0
    extra = {}
    if args.trace_out and args.flight_dir:
        # Stitch every process's flight log (shared filesystem) into
        # one Chrome trace — works after crashes too, that's the point.
        import os

        from chainermn_tpu.observability import tracing

        recs = tracing.read_flight_dir(
            os.path.join(args.flight_dir, "flight_*.jsonl")
        )
        with open(args.trace_out, "w") as f:
            json.dump(tracing.to_chrome_trace(recs), f)
        extra["trace_stages"] = {
            name: {"count": st["count"], "p50_s": st["p50_s"],
                   "p99_s": st["p99_s"]}
            for name, st in sorted(
                tracing.stage_percentiles(recs).items()
            )
        }
    if args.verify:
        oracle = _oracle_streams(args, prompts, samplings)
        mismatches = [
            g for g, o in enumerate(oracle)
            if results[g]["tokens"] != o
        ]
        extra["parity"] = "ok" if not mismatches else "FAIL"
        extra["parity_mismatches"] = mismatches
        extra["parity_sampled"] = sum(1 for s in samplings if s)
    if args.tp * args.pp > 1:
        extra["tp"] = args.tp
        extra["pp"] = args.pp
        extra["groups"] = len(groups)
    print(json.dumps(_report(args, results, wall, extra)))
    if extra.get("parity") == "FAIL":
        return 1
    if any(r["status"] != "finished" for r in results.values()):
        return 1
    return 0


def run_shard_groups(args) -> int:
    """``--tp K [--pp S] [--groups G]`` local launcher: spawn the whole
    shard-group cluster from one shell — this process becomes the
    router (process 0), plus ``G x K x S`` replica shard processes as
    children of this one, all joined to an ephemeral jax.distributed
    coordinator.  ``--verify`` turns on the sampled request legs too,
    so parity covers greedy AND temperature/top-k decoding."""
    import os
    import socket
    import subprocess

    from chainermn_tpu.elastic.supervisor import refuse_shared_chips

    if args.verify:
        args.sampled = True
    size = 1 + args.groups * args.tp * args.pp
    # The router (this process) joins the jax.distributed world and
    # touches the backend too, so every one of the ``size`` processes
    # would hold chips.
    refuse_shared_chips(size, os.environ, "tools.serve --tp/--pp")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    forward = [
        "--tp", str(args.tp), "--pp", str(args.pp),
        "--vocab", str(args.vocab), "--d-model", str(args.d_model),
        "--heads", str(args.heads), "--d-ff", str(args.d_ff),
        "--layers", str(args.layers), "--max-len", str(args.max_len),
        "--block-size", str(args.block_size),
        "--n-blocks", str(args.n_blocks),
        "--max-batch", str(args.max_batch),
        "--max-queue", str(args.max_queue),
        "--seed", str(args.seed),
        "--spec-tokens", str(args.spec_tokens),
        "--timeout-s", str(args.timeout_s),
    ]
    if args.prefill_chunk is not None:
        forward += ["--prefill-chunk", str(args.prefill_chunk)]
    if args.watermark is not None:
        forward += ["--watermark", str(args.watermark)]
    if not args.max_len_growth:
        forward += ["--no-max-len-growth"]
    procs = []
    rc = 1
    try:
        for pid in range(1, size):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "chainermn_tpu.tools.serve",
                 "--role", "replica", "--coordinator", coord,
                 "--num-processes", str(size), "--process-id", str(pid),
                 ] + forward,
                stdout=subprocess.DEVNULL,  # one JSON report: ours
                env=dict(os.environ),
            ))
        args.role = "router"
        args.coordinator = coord
        args.num_processes = size
        args.process_id = 0
        rc = run_multiprocess(args)
        return rc
    finally:
        deadline = time.perf_counter() + 30
        killed = False
        for p in procs:
            try:
                p.wait(timeout=max(
                    0.1, deadline - time.perf_counter()
                ))
            except Exception:
                p.kill()
                killed = True
        if killed:
            # With a killed shard in the world, jax.distributed's
            # atexit shutdown barrier would hang this (coordinator)
            # process forever — skip it, the report is already out.
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(rc)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m chainermn_tpu.tools.serve",
        description="Run the multi-replica serving tier on synthetic "
                    "traffic (in-process or one process per role).",
    )
    ap.add_argument("--role", choices=["local", "router", "replica"],
                    default="local")
    ap.add_argument("--replicas", type=int, default=2,
                    help="replica count for --role local")
    ap.add_argument("--roles", default=None,
                    help="comma-separated per-replica roles for --role "
                         "local (prefill|decode|both; default all both)")
    ap.add_argument("--replica-role", default=None,
                    choices=["prefill", "decode", "both"],
                    help="this process's role for --role replica")
    ap.add_argument("--prefill-threshold", type=int, default=None,
                    help="prompts at least this long go to a "
                         "prefill-role replica first (disaggregation)")
    ap.add_argument("--watermark", type=int, default=None,
                    help="free-page admission watermark per replica")
    ap.add_argument("--draft", choices=["ngram", "model"], default=None,
                    help="speculative draft source (with --spec-tokens):"
                         " n-gram prompt lookup or the layer-truncated "
                         "self-draft model (default: engine resolution "
                         "— env, then ngram)")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="self-draft depth (--draft model; default: "
                         "half the target's layers)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill slice size in tokens (0 = "
                         "monolithic prefill; prompts longer than the "
                         "slice prefill incrementally between decode "
                         "steps — either way, --verify proves streams)")
    ap.add_argument("--sp", type=int, default=0,
                    help="sequence-shard chunked prefill over this many "
                         "devices (power of two; 0 disables; decode "
                         "stays collective-free and streams stay "
                         "bit-exact — --verify proves it)")
    ap.add_argument("--max-len-growth",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="let each replica's context-bucket ladder grow "
                         "lazily past its seed buckets (prompts beyond "
                         "the largest bucket compile one new bucket "
                         "instead of being rejected); "
                         "--no-max-len-growth pins the seed ladder")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative draft length per decode step "
                         "(0 disables; streams are bit-exact either "
                         "way, --verify proves it)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shard-group width: each "
                         "replica becomes a leader + tp-1 follower "
                         "shard processes in lockstep (--role local "
                         "spawns the whole cluster; router/replica "
                         "roles must all agree on --tp/--pp)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages per shard group: decode "
                         "batches split into per-stage microbatches "
                         "(bit-exact; group spans tp*pp processes)")
    ap.add_argument("--groups", type=int, default=1,
                    help="shard-group count for the --tp local "
                         "launcher (total processes = 1 + "
                         "groups*tp*pp)")
    ap.add_argument("--sampled", action="store_true",
                    help="make every odd request temperature/top-k "
                         "sampled instead of greedy (multi-process "
                         "roles; --tp --verify implies it) so parity "
                         "covers both decode paths")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="bounded frontend queue size per replica")
    ap.add_argument("--verify", action="store_true",
                    help="replay through a sequential oracle and fail "
                         "unless streams are bit-identical")
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--miss-after-s", type=float, default=30.0,
                    help="multi-process router: declare a replica dead "
                         "after this long without a heartbeat (generous "
                         "default tolerates cold jit compiles on CPU; "
                         "real deaths surface faster via socket EOF)")
    # autoscaling + generated traffic (local role only)
    ap.add_argument("--traffic", default=None, metavar="SPEC",
                    help="replay a seeded heavy-tailed workload instead "
                         "of the fixed prompt sweep; SPEC is "
                         "'key=value,...' (or 'default'), see "
                         "serving.workload.TrafficSpec")
    ap.add_argument("--autoscale", action="store_true",
                    help="run the SLO-guarded autoscaler during "
                         "--traffic replay: spawn on pressure, "
                         "drain+migrate+retire on idleness")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscaler ceiling (default: --replicas + 2)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="timed fault schedule for --traffic, e.g. "
                         "'kill:replica=1:at=0.5' (seconds since "
                         "replay start; see elastic.chaos)")
    ap.add_argument("--slo", default=None, metavar="TARGETS",
                    help="per-stage latency targets 'stage=seconds,...' "
                         "(e.g. 'queue=5,decode=2'); installs a tracer "
                         "so slo/burn_rate/<stage> gauges populate")
    # observability
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace JSON of every "
                         "request's span tree to this path")
    ap.add_argument("--flight-dir", default=None,
                    help="directory for crash-surviving flight-recorder "
                         "logs (one JSONL per process; enables tracing)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve a Prometheus /metrics scrape endpoint "
                         "on this port (0 = ephemeral).  Local roles "
                         "export the fleet view; --role router the "
                         "heartbeat-merged fleet view; --role replica "
                         "its own registry")
    ap.add_argument("--metrics-port-file", default=None,
                    help="write the bound metrics port to this file "
                         "(--role router; implies an ephemeral port "
                         "when --metrics-port is unset)")
    # traffic
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="max synthetic prompt length (min is half)")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # model geometry
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=128)
    # engine
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--n-blocks", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=4)
    # multi-process wiring
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator host:port")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args(argv)

    if args.tp < 1 or args.pp < 1 or args.groups < 1:
        raise SystemExit("--tp/--pp/--groups must be >= 1")
    if args.role == "local":
        if args.tp * args.pp > 1 or args.groups > 1:
            return run_shard_groups(args)
        if args.traffic:
            return run_local_traffic(args)
        return run_local(args)
    return run_multiprocess(args)


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    sys.exit(main())
