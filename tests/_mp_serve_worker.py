"""Worker for the multi-process serving soak test.

Run as: python _mp_serve_worker.py <pid> <nproc> <port> <kill_after> \
            [flight_dir]

A REAL serving fleet under one jax.distributed coordinator: rank 0 runs
the service-loop router (:func:`service.run_router`), every other rank a
replica (:func:`service.run_replica`).  With ``kill_after > 0`` the
HIGHEST rank SIGKILLs itself after streaming that many tokens —
mid-request, sequences live in its page pool, no cleanup — and the
router must detect the death (socket EOF → PeerGone, or missed
heartbeats), re-place the orphaned requests on the survivor with their
committed token prefix, and still return every stream BIT-IDENTICAL to
a sequential single-engine oracle.  The survivor's page pool passes
``assert_consistent`` on clean stop (checked inside run_replica).

Rank 0 prints ``SERVE_SOAK_OK`` after verifying all streams; surviving
replicas print ``SERVE_REPLICA_OK <pid>``.  The killed rank's "output"
is its -9 exit status.

With a ``flight_dir`` argument every rank records its trace spans to a
crash-surviving flight file (``flight_<rank>.jsonl``) — the SIGKILLed
rank's stage spans survive on disk and the host test stitches them into
the router's root spans for the failover postmortem.

With the literal argument ``traffic`` instead of a flight dir, the
router drives a seeded heavy-tailed workload (serving.workload — MMPP
bursts, Zipf shared prefixes, mixed length buckets) under an SLO-wired
tracer: the SIGKILL lands at peak generated load, and rank 0
additionally asserts every ``slo/burn_rate/*`` gauge stayed below 1.0
before printing ``SERVE_TRAFFIC_OK burn_max=<x>``.

With the literal argument ``gossip`` the fleet (router + 3 replicas)
runs model-based speculative decode with chunked prefill, and the
workload arrives in two waves to exercise the cluster-global prefix
index: wave 1 seeds exactly one replica with a 3-page template prompt
(plus decoy prompts elsewhere) while rank 1 — the cold-start placement
favorite — SIGKILLs itself mid-stream, so the template's pages end up
on a survivor the router only knows about through gossiped digests;
wave 2 (held back via ``after_gids`` until wave 1 is done) sends
template-prefixed prompts the router has never placed, and they must
route to whichever survivor actually holds the template.  Rank 0
prints ``SERVE_GOSSIP_OK holder=<rank>`` before ``SERVE_SOAK_OK``.

With the literal argument ``longctx`` the fleet (router + 2 replicas)
exercises STREAMING prefix registration over the wire: a long document
chunk-prefills on the cold-start favorite, each completed slice's
pages registering in the prefix index immediately and their digests
riding the next load beat; a follower request sharing the document is
gated on that gossip view (``after_index_pages``), so it arrives while
the document is STILL MID-PREFILL and must route to the warm replica —
which the router only knows is warm through the gossiped partial
prefix.  Rank 0 prints ``SERVE_LONGCTX_OK holder=<rank>`` before
``SERVE_SOAK_OK``.

With the literal argument ``tpgroup`` the fleet runs TWO tensor-
parallel shard groups (router + 2 groups x 2 shard processes: leaders
at ranks 1 and 3, followers at 2 and 4) and the doomed process is a
*follower* shard: rank 2 SIGKILLs itself after replaying ``kill_after``
mirrored device steps — mid-stream, lockstep state live.  The leader's
next mirror fan-out (or beat poll) raises PeerGone, it exits its serve
loop, the router sees the GROUP die on the leader's event edge, and the
orphaned streams re-place on the survivor group — every stream still
bit-identical to the sequential oracle, the survivor leader's pool
passing assert_consistent on clean stop.  Rank 0 prints
``SERVE_TPGROUP_OK survivor=<leader>`` before ``SERVE_SOAK_OK``.

With the argument ``metrics:<dir>`` the default kill9 soak additionally
exercises the fleet observability plane over the wire: every request
carries a tenant id, the router serves its merged fleet view at a live
``/metrics`` endpoint (port written to ``<dir>/router_metrics_port``),
and a rank-0 background thread scrapes it throughout the run.  After
the streams verify, rank 0 asserts the scrape series: the SIGKILLed
replica's per-replica series were present while it lived and are GONE
from the final view, fleet counters stayed monotone on either side of
the one step-down where the dead snapshot left the merge, and the
per-tenant token counters survived the failover.  Prints
``SERVE_METRICS_OK scrapes=<n>`` before ``SERVE_SOAK_OK``.
"""

import os
import sys


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    kill_after = int(sys.argv[4])
    flight_dir = sys.argv[5] if len(sys.argv) > 5 else None
    metrics_dir = None
    if flight_dir and flight_dir.startswith("metrics:"):
        metrics_dir = flight_dir.split(":", 1)[1]
        flight_dir = None
    traffic = flight_dir == "traffic"
    gossip = flight_dir == "gossip"
    longctx = flight_dir == "longctx"
    tpgroup = flight_dir == "tpgroup"
    flight_path = None
    if flight_dir and not traffic and not gossip and not longctx \
            and not tpgroup:
        flight_path = os.path.join(flight_dir, f"flight_{pid}.jsonl")

    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    # Force backend init NOW on every rank: the CPU client's global
    # topology exchange blocks until all processes join, and the router
    # rank would otherwise never touch jax before its oracle check.
    jax.devices()

    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import EngineConfig, InferenceEngine
    from chainermn_tpu.serving.cluster import service

    # The gossip soak runs the full speculative stack over the wire:
    # layer-truncated self-draft + chunked prefill, verified bit-exact
    # against the same factory's sequential oracle.
    extra_cfg = {"draft": "model", "prefill_chunk": 8} if gossip else {}
    if longctx:
        # Tiny chunks stretch the document's prefill across many steps
        # so the gated follower genuinely lands mid-prefill.
        extra_cfg = {"prefill_chunk": 4}

    def engine_factory():
        lm = TransformerLM(vocab=32, d_model=16, n_heads=2, d_ff=32,
                           n_layers=2, max_len=64)
        params = lm.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))
        return InferenceEngine(lm, params, EngineConfig(
            block_size=4, n_blocks=64, max_len=64, max_batch=2,
            **extra_cfg,
        ))

    if traffic:
        # Heavy-tailed generated load: the kill lands mid-burst, with
        # Zipf-shared prefix pages live in both replica pools.  Length
        # buckets are capped so prompt + output fits max_len=64.
        from chainermn_tpu.serving import TrafficSpec, workload

        spec = TrafficSpec(
            seed=5, requests=10, rate=200.0, burst=6.0, p_burst=0.3,
            prefix_len=8, templates=4,
            prompt_buckets=((4, 12, 0.7), (14, 20, 0.3)),
            output_buckets=((4, 8, 0.8), (10, 12, 0.2)),
            vocab=32,
        )
        arrivals = workload.generate(spec)
        prompts = [list(a.prompt) for a in arrivals]
        news = [a.max_new_tokens for a in arrivals]
    elif gossip:
        # Wave 1 (gids 0-5): one 3-page template prompt plus five decoy
        # prompts.  kill_after=6 < max_new=8 guarantees rank 1 (cold-
        # start favorite, so it owns gid 0) dies before the template
        # request can finish there — the adopting survivor re-prefills
        # it and registers the template pages, and only gossip can tell
        # the router which survivor that was.  Wave 2 (gids 6-7):
        # template-prefixed prompts, gated on wave 1 via after_gids.
        rng = np.random.default_rng(29)
        template = [int(t) for t in rng.integers(0, 32, size=12)]
        prompts = [template] + [
            [int(t) for t in rng.integers(0, 32, size=int(n))]
            for n in rng.integers(4, 11, size=5)
        ]
        news = [8] * 6
        prompts += [
            template + [int(t) for t in rng.integers(0, 32, size=6)]
            for _ in range(2)
        ]
        news += [6, 6]
    elif longctx:
        # One long document (10 pages, 10 prefill slices at chunk=4)
        # plus ONE doc-prefixed follower.  The follower is gated on the
        # gossiped partial-prefix view (after_index_pages=6, set on the
        # request below): it is released while the document is still
        # mid-prefill, and only the streamed page registrations — the
        # digests ride each load beat — can tell the router which
        # replica is warm.  Exactly one follower: a second would eat
        # queue/batch penalties on the busy warm replica and tie-break
        # away to the idle one.
        rng = np.random.default_rng(31)
        doc = [int(t) for t in rng.integers(0, 32, size=40)]
        prompts = [list(doc)]
        news = [6]
        prompts += [doc + [int(t) for t in rng.integers(0, 32, size=4)]]
        news += [5]
    else:
        rng = np.random.default_rng(13)
        prompts = [
            [int(t) for t in rng.integers(0, 32, size=int(n))]
            for n in rng.integers(4, 11, size=6)
        ]
        # Half the fleet's traffic shares a 2-page prefix: the kill
        # lands while refcounted/index-registered pages are live in the
        # victim's and survivor's pools, and the survivor's clean-stop
        # assert_consistent proves no page leaked or double-freed.
        shared = [int(t) for t in rng.integers(0, 32, size=8)]
        prompts = [shared + p if i % 2 == 0 else p
                   for i, p in enumerate(prompts)]
        news = [8] * len(prompts)

    if pid == 0:
        requests = [
            {"prompt": p, "max_new_tokens": n}
            for p, n in zip(prompts, news)
        ]
        if gossip:
            for r in requests[6:]:
                r["after_gids"] = list(range(6))
        if longctx:
            requests[1]["after_index_pages"] = 6
        metrics_port_file = None
        scrapes = []
        scraper = None
        stop_scraping = None
        if metrics_dir is not None:
            import threading
            import time
            import urllib.request

            for gid, r in enumerate(requests):
                r["tenant"] = f"t{gid % 2}"
            metrics_port_file = os.path.join(metrics_dir,
                                             "router_metrics_port")
            stop_scraping = threading.Event()

            def _scrape_loop():
                while not stop_scraping.is_set():
                    if os.path.exists(metrics_port_file):
                        break
                    time.sleep(0.05)
                else:
                    return
                with open(metrics_port_file) as f:
                    mport = int(f.read().strip())
                url = f"http://127.0.0.1:{mport}/metrics"
                while not stop_scraping.is_set():
                    try:
                        with urllib.request.urlopen(url, timeout=5) as rs:
                            scrapes.append(rs.read().decode())
                    except OSError:
                        pass
                    time.sleep(0.1)

            scraper = threading.Thread(target=_scrape_loop, daemon=True)
            scraper.start()
        reporter = slo = None
        if traffic:
            from chainermn_tpu.observability.reporter import Reporter
            from chainermn_tpu.observability.tracing import SLOConfig

            reporter = Reporter()
            # Router-visible stages; lenient targets sized for CPU
            # compile stalls — burn < 1.0 is the green-SLO assertion.
            slo = SLOConfig(targets={"request": 120.0,
                                     "placement": 60.0})
        # miss_after_s must tolerate a replica stalled in a cold jit
        # compile (seconds on CPU); REAL deaths are detected much
        # faster via socket EOF -> PeerGone on the event edge.
        results = service.run_router(
            nproc, requests, miss_after_s=30.0, timeout_s=180.0,
            flight_path=flight_path, reporter=reporter, slo=slo,
            metrics_port_file=metrics_port_file,
            group_size=2 if tpgroup else 1,
        )
        if scraper is not None:
            stop_scraping.set()
            scraper.join(timeout=10)
        try:
            oracle = engine_factory()
            failovers = 0
            for gid, (p, n) in enumerate(zip(prompts, news)):
                rr = results[gid]
                assert rr["status"] == "finished", (gid, rr)
                want = oracle.generate(p, n)
                assert rr["tokens"] == want, (gid, rr["tokens"], want)
                failovers += rr["failovers"]
            if kill_after > 0:
                assert failovers > 0, "nobody failed over despite kill"
            if tpgroup:
                # The follower-shard kill must have collapsed the WHOLE
                # group led by rank 1: every stream that failed over
                # finished on the survivor group's leader (rank 3), and
                # the survivor leader's clean-stop assert_consistent
                # (inside run_replica) proves its pool absorbed the
                # orphans without leaking a page.
                moved = [g for g, _ in enumerate(prompts)
                         if results[g]["failovers"] > 0]
                assert moved, results
                for g in moved:
                    assert results[g]["replica"] == 3, (g, results[g])
                print("SERVE_TPGROUP_OK survivor=3")
            if gossip:
                # The template request must have outlived rank 1's
                # SIGKILL on a survivor, and BOTH gated wave-2 requests
                # must have routed to that exact survivor — the router
                # never placed the template there itself, so only the
                # gossiped digest view can have told it.
                holder = results[0]["replica"]
                assert holder in (2, 3), results[0]
                routed = [results[6]["replica"], results[7]["replica"]]
                assert routed == [holder, holder], (holder, routed)
                print(f"SERVE_GOSSIP_OK holder={holder}")
            if longctx:
                # The follower was released by gossiped STREAMING page
                # registrations while the document was still prefilling
                # — it must have landed on the replica mid-prefill, not
                # the idle one (whose free/queue score would otherwise
                # win for a never-seen prompt).
                holder = results[0]["replica"]
                assert results[1]["replica"] == holder, results
                print(f"SERVE_LONGCTX_OK holder={holder}")
            if traffic:
                gauges = reporter.summary()["gauges"]
                burns = {
                    k.split("/", 2)[2]: g["value"]
                    for k, g in gauges.items()
                    if k.startswith("slo/burn_rate/")
                }
                assert burns, "no SLO burn gauges populated"
                burn_max = max(burns.values())
                assert burn_max < 1.0, f"SLO burned red: {burns}"
                print(f"SERVE_TRAFFIC_OK burn_max={burn_max:.4f}")
            if metrics_dir is not None:
                import re

                assert len(scrapes) >= 3, f"only {len(scrapes)} scrapes"
                dead = f'replica="{nproc - 1}"'
                lived = [i for i, s in enumerate(scrapes) if dead in s]
                assert lived, "dead replica's series never scraped alive"
                assert dead not in scrapes[-1], \
                    "dead replica's series survived its forget"
                # Per-tenant token accounting survived the failover: the
                # orphaned requests re-bill on the adopting survivor.
                ctr_re = re.compile(
                    r'chainermn_tpu_counter_total\{name="([^"]+)"\} (\S+)')
                final = {m.group(1): float(m.group(2))
                         for m in ctr_re.finditer(scrapes[-1])}
                for t in ("t0", "t1"):
                    for which in ("tokens_in", "tokens_out"):
                        name = f"tenant/{t}/{which}"
                        assert final.get(name, 0.0) > 0, (name, final)
                # Fleet counters are monotone except for the ONE step
                # where the dead replica's snapshot leaves the merge —
                # split there and each segment must be nondecreasing.
                cut = lived[-1] + 1
                for seg in (scrapes[:cut], scrapes[cut:]):
                    prev = {}
                    for s in seg:
                        cur = {m.group(1): float(m.group(2))
                               for m in ctr_re.finditer(s)}
                        for k, v in prev.items():
                            assert cur.get(k, 0.0) >= v, (k, v, cur.get(k))
                        prev = cur
                print(f"SERVE_METRICS_OK scrapes={len(scrapes)}")
        except BaseException:
            import traceback

            traceback.print_exc()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)  # don't hang in the atexit shutdown barrier
        print("SERVE_SOAK_OK")
        # Skip jax's atexit shutdown barrier: with a SIGKILLed rank in
        # the world it blocks until the coordination service aborts us.
        sys.stdout.flush()
        os._exit(0)

    # Replicas.  max_queue=3 forces the router to spread the burst over
    # both replicas (cold-start placement prefers the lowest rank until
    # its queue fills), so the doomed rank is guaranteed live work.  In
    # gossip mode the doomed rank is 1 — the cold-start favorite that
    # owns the template request — and max_queue=2 spreads wave 1 over
    # all three replicas.
    if tpgroup:
        # Two shard groups of 2: leaders 1 and 3, followers 2 and 4.
        # The doomed process is FOLLOWER rank 2 — it dies after
        # replaying kill_after mirrored steps, which must take down the
        # whole group led by rank 1.
        from chainermn_tpu.serving.cluster.shard_group import plan_groups

        group = next(g for g in plan_groups(nproc, 2, 1)
                     if pid in g.ranks)
        out = service.run_replica(
            pid, nproc, engine_factory, max_queue=3, group=group,
            kill_after_ops=kill_after if (kill_after > 0 and pid == 2)
            else None,
        )
        print(f"SERVE_REPLICA_OK {pid} {out['reason']}")
        sys.stdout.flush()
        os._exit(0)
    doomed = kill_after > 0 and pid == (1 if gossip else nproc - 1)
    out = service.run_replica(
        pid, nproc, engine_factory,
        max_queue=2 if gossip else 3,
        kill_after_tokens=kill_after if doomed else None,
        flight_path=flight_path,
        spec_tokens=2 if gossip else 0,
    )
    print(f"SERVE_REPLICA_OK {pid} {out['reason']}")
    sys.stdout.flush()
    os._exit(0)  # see rank 0: no shutdown barrier with a corpse in it


if __name__ == "__main__":
    main()
