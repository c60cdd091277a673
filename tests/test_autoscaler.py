"""SLO-guarded autoscaler + heavy-tailed traffic harness.

The resilience contract on top of the cluster tier
(tests/test_serving_cluster.py):

1. **Deterministic traffic** — :mod:`serving.workload` arrivals are a
   pure function of the :class:`TrafficSpec` (same seed → identical
   MMPP times, Zipf templates, length buckets, priority classes), so
   every curve and soak replays bit-for-bit.
2. **Graceful degradation** — under overload the frontend sheds the
   *cheapest* class first, counts it per class, and jitters its
   retry-after hints so polite clients never synchronize into a retry
   storm.
3. **Debounced control** — raw scale signals flap; the
   :class:`ScaleSignalFilter` only passes K-consecutive votes outside
   a cooldown window, so a bursty batch cannot oscillate the fleet.
4. **Zero-loss scale-down** — drain → migrate live KV pages →
   retire: every stream survives bit-exact, nothing replays from
   scratch, and the retired replica leaves no health residue.
5. **Emergency backfill** — losing a replica below the floor spawns a
   replacement immediately (no hysteresis); failover has already
   requeued the victim's streams from their committed prefixes.

All CPU, in-process.  The cross-process chaos-at-peak-load soak lives
in tests/test_multiprocess.py; the ``tools.serve`` CLI smoke rides the
slow tier here.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.elastic import chaos
from chainermn_tpu.observability.reporter import Reporter
from chainermn_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    QueueFull,
    TrafficSpec,
)
from chainermn_tpu.serving import workload
from chainermn_tpu.serving.cluster import (
    Autoscaler,
    AutoscalerConfig,
    HeartbeatMonitor,
    Replica,
    ReplicaRouter,
    ScaleSignalFilter,
)

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    from chainermn_tpu.models.transformer import TransformerLM

    return TransformerLM(vocab=VOCAB, d_model=16, n_heads=2, d_ff=32,
                         n_layers=2, max_len=64)


@pytest.fixture(scope="module")
def lm_params(lm):
    return lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def make_engine(lm, lm_params, **over):
    cfg = dict(block_size=4, n_blocks=64, max_len=64, max_batch=4)
    cfg.update(over)
    return InferenceEngine(lm, lm_params, EngineConfig(**cfg))


def mk_fleet(lm, lm_params, n=2, max_queue=8, reporter=None,
             **router_kw):
    reps = [
        Replica(i, make_engine(lm, lm_params), role="both",
                reporter=reporter, max_queue=max_queue)
        for i in range(n)
    ]
    router = ReplicaRouter(
        reps, reporter=reporter,
        health=HeartbeatMonitor([r.replica_id for r in reps],
                                miss_after_s=30.0),
        **router_kw,
    )
    return reps, router


# ---------------------------------------------------------------------------
# Traffic generator: determinism, shape, spec round-trip
# ---------------------------------------------------------------------------


def test_traffic_spec_parse_format_roundtrip():
    spec = TrafficSpec.parse(
        "rate=80,requests=48,burst=6,abusive_frac=0.2,"
        "prompt_buckets=4-8:0.6|10-20:0.4,class_weights=0.3/0.7"
    )
    assert spec.rate == 80.0 and spec.requests == 48
    assert spec.prompt_buckets == ((4, 8, 0.6), (10, 20, 0.4))
    assert spec.class_weights == (0.3, 0.7)
    assert TrafficSpec.parse(spec.format()) == spec
    assert TrafficSpec.parse("default") == TrafficSpec()
    assert TrafficSpec.parse("") == TrafficSpec()
    with pytest.raises(ValueError):
        TrafficSpec.parse("no_such_knob=3")
    with pytest.raises(ValueError):
        TrafficSpec.parse("rate")


def test_traffic_spec_scaled_moves_only_rate():
    spec = TrafficSpec(rate=50.0, requests=16)
    double = spec.scaled(2.0)
    assert double.rate == 100.0
    assert double.requests == spec.requests
    assert double.seed == spec.seed


def test_generate_is_deterministic_and_heavy_tailed():
    spec = TrafficSpec(seed=3, requests=200, abusive_frac=0.15)
    a1, a2 = workload.generate(spec), workload.generate(spec)
    assert a1 == a2  # pure function of the spec
    assert workload.generate(TrafficSpec(seed=4, requests=200)) != a1
    # arrival times strictly ordered, lengths within buckets
    ts = [a.t for a in a1]
    assert all(b > a for a, b in zip(ts, ts[1:]))
    lo = min(lo for lo, _, _ in spec.prompt_buckets)
    hi = max(hi for _, hi, _ in spec.prompt_buckets)
    assert all(lo <= len(a.prompt) <= hi for a in a1)
    assert all(t < VOCAB for a in a1 for t in a.prompt)
    # Zipf templates: the most popular template dominates
    counts = np.bincount([a.template for a in a1],
                         minlength=spec.templates)
    assert counts[0] == counts.max() and counts[0] > len(a1) / 4
    # shared prefixes really shared (prefix-cache feedstock)
    by_tmpl = {}
    for a in a1:
        by_tmpl.setdefault(a.template, []).append(a.prompt)
    some = [ps for ps in by_tmpl.values() if len(ps) > 3][0]
    k = min(len(p) for p in some)
    assert len({p[:k] for p in some}) == 1
    # abusive arrivals exist and ride the lowest class
    abusive = [a for a in a1 if a.abusive]
    assert abusive
    assert all(a.priority == len(spec.class_weights) - 1
               for a in abusive)
    # all classes represented
    assert {a.priority for a in a1} == {0, 1, 2}


def test_generate_burst_state_compresses_interarrivals():
    calm = workload.generate(TrafficSpec(
        seed=0, requests=300, burst=1.0, p_burst=0.0))
    bursty = workload.generate(TrafficSpec(
        seed=0, requests=300, burst=8.0, p_burst=0.3, p_calm=0.2))
    # same mean calm rate, but the MMPP's burst state makes the
    # minimum inter-arrival gap collapse
    gaps = lambda arr: np.diff([a.t for a in arr])  # noqa: E731
    assert np.median(gaps(bursty)) < np.median(gaps(calm))


# ---------------------------------------------------------------------------
# Hysteresis filter: a flapping trace must not flap the fleet
# ---------------------------------------------------------------------------


def test_scale_filter_debounces_flapping_trace():
    f = ScaleSignalFilter(k_up=3, k_down=3, cooldown_s=10.0)
    up = {"scale_up": True, "drain_candidate": None}
    quiet = {"scale_up": False, "drain_candidate": None}
    # alternating pressure never reaches k_up consecutive votes
    t = 0.0
    for _ in range(20):
        assert f.update(up, now=t) == {"scale_up": False, "drain": None}
        assert f.update(quiet, now=t) == {"scale_up": False,
                                          "drain": None}
        t += 0.1
    # sustained pressure acts exactly at the Kth observation
    assert not f.update(up, now=t)["scale_up"]
    assert not f.update(up, now=t)["scale_up"]
    assert f.update(up, now=t)["scale_up"]
    # cooldown refuses immediately after a decision...
    for _ in range(5):
        assert not f.update(up, now=t + 1.0)["scale_up"]
    # ...but streaks survive it: pressure still standing when the
    # window expires acts on the next observation past k_up
    out = f.update(up, now=t + 11.0)
    assert out["scale_up"]


def test_scale_filter_drain_candidate_flap_resets_streak():
    f = ScaleSignalFilter(k_up=2, k_down=3, cooldown_s=0.0)
    s = lambda c: {"scale_up": False, "drain_candidate": c}  # noqa: E731
    assert f.update(s(0), now=0.0)["drain"] is None
    assert f.update(s(0), now=0.1)["drain"] is None
    # candidate flips → streak restarts at 1 for the new candidate
    assert f.update(s(1), now=0.2)["drain"] is None
    assert f.update(s(1), now=0.3)["drain"] is None
    assert f.update(s(1), now=0.4)["drain"] == 1
    # a None observation clears the streak entirely
    assert f.update(s(0), now=0.5)["drain"] is None
    assert f.update({"scale_up": False, "drain_candidate": None},
                    now=0.6)["drain"] is None
    assert f.update(s(0), now=0.7)["drain"] is None


def test_scale_filter_rejects_bad_hysteresis():
    with pytest.raises(ValueError):
        ScaleSignalFilter(k_up=0)


# ---------------------------------------------------------------------------
# Priority-aware shedding + jittered backpressure
# ---------------------------------------------------------------------------


def test_shed_evicts_cheapest_class_first(lm, lm_params):
    reporter = Reporter()
    reps, router = mk_fleet(lm, lm_params, n=1, max_queue=3,
                            reporter=reporter)
    p = [1, 2, 3]
    # fill the single queue with the cheapest class
    low = [router.submit(p, 4, priority=2) for _ in range(3)]
    # same class cannot shed its peers → QueueFull, counted rejected
    with pytest.raises(QueueFull):
        router.submit(p, 4, priority=2)
    # a mid class evicts exactly one class-2 victim
    mid = router.submit(p, 4, priority=1)
    # top class evicts the next class-2 victim, never the class-1
    top = router.submit(p, 4, priority=0)
    router.run_until_idle()
    assert mid.status == "finished" and top.status == "finished"
    shed = [h for h in low if h.status == "failed"]
    assert len(shed) == 2
    assert all(h.error.startswith("shed") for h in shed)
    counters = reporter.summary()["counters"]
    assert counters["serve/shed/2"] == 2
    assert counters["serve/rejected/2"] == 1
    assert counters["serve/admit/0"] == 1
    assert counters["serve/admit/1"] == 1
    assert counters["serve/admit/2"] == 3


def test_queue_full_hints_are_jittered(lm, lm_params):
    reps, router = mk_fleet(lm, lm_params, n=1, max_queue=1)
    # a completed stream establishes the throughput the hint is
    # derived from (no observations → no hint)
    router.submit([1, 2], 6)
    router.run_until_idle()
    router.submit([1, 2], 6)  # refill the single queue slot
    hints = []
    for _ in range(6):
        with pytest.raises(QueueFull) as ei:
            router.submit([1, 2], 4)
        hints.append(ei.value.retry_after_s)
    assert all(h is not None and h > 0 for h in hints)
    # jitter actually spreads the herd: not all hints identical
    assert len(set(hints)) > 1
    router.run_until_idle()


def test_replay_polite_clients_honor_hints_abusive_slam():
    """Replay against a fake frontend that rejects the first N attempts:
    polite arrivals wait out the (tiny) hints; abusive ones burn their
    retry cap immediately and are counted rejected."""
    a_polite = workload.Arrival(index=0, t=0.0, prompt=(1,),
                                max_new_tokens=1, priority=1,
                                abusive=False, template=0)
    a_abusive = workload.Arrival(index=1, t=0.0, prompt=(1,),
                                 max_new_tokens=1, priority=2,
                                 abusive=True, template=0)

    class Done:
        status, done, error, tokens = "finished", True, None, [5]

    attempts = {0: 0, 1: 0}

    def submit(a):
        attempts[a.index] += 1
        if attempts[a.index] <= 5:
            raise QueueFull("full", retry_after_s=0.001)
        return Done()

    report = workload.replay([a_polite, a_abusive], submit,
                             drain_timeout_s=5.0)
    polite, abusive = report.outcomes
    assert polite.finished and polite.attempts == 6
    # abusive cap (3 retries) < 5 rejections → never admitted
    assert abusive.rejected and not abusive.finished
    summary = workload.summarize(report)
    assert summary["offered"] == 2
    assert summary["finished"] == 1
    assert summary["rejected"] == 1
    assert summary["retries"] == 5 + 3
    assert summary["per_class"]["2"]["rejected"] == 1


# ---------------------------------------------------------------------------
# Autoscaler: spawn on pressure, burn-rate override, backfill,
# drain → migrate → retire with zero dropped streams
# ---------------------------------------------------------------------------


def test_autoscaler_spawns_on_queue_pressure(lm, lm_params):
    reporter = Reporter()
    reps, router = mk_fleet(lm, lm_params, n=1, max_queue=4,
                            reporter=reporter)

    def factory(rid):
        return Replica(rid, make_engine(lm, lm_params), role="both",
                       reporter=reporter, max_queue=4)

    scaler = Autoscaler(
        router, factory,
        AutoscalerConfig(min_replicas=1, max_replicas=2, k_up=2,
                         cooldown_s=0.0),
        reporter=reporter,
    )
    for _ in range(4):
        router.submit([1, 2, 3], 6)
    assert scaler.step(now=0.0) is None  # first vote: streak == 1
    ev = scaler.step(now=0.1)
    assert ev is not None and ev["action"] == "spawn"
    assert ev["reason"] == "watermark"
    assert "as0" in router.replicas
    # ceiling respected even under sustained pressure
    for i in range(6):
        assert scaler.step(now=0.2 + i * 0.1) is None
    router.run_until_idle()
    counters = reporter.summary()["counters"]
    assert counters["autoscaler/spawn"] == 1
    assert counters["serving/cluster/replicas_added"] == 1


def test_autoscaler_burn_rate_forces_scale_up(lm, lm_params):
    reporter = Reporter()
    reps, router = mk_fleet(lm, lm_params, n=1, reporter=reporter)

    def factory(rid):
        return Replica(rid, make_engine(lm, lm_params), role="both",
                       reporter=reporter)

    scaler = Autoscaler(
        router, factory,
        AutoscalerConfig(min_replicas=1, max_replicas=2, k_up=2,
                         cooldown_s=0.0),
        reporter=reporter,
    )
    # idle fleet, healthy watermarks — but a stage is burning budget
    reporter.gauge("slo/burn_rate/decode", 2.5)
    assert scaler.step(now=0.0) is None
    ev = scaler.step(now=0.1)
    assert ev is not None and ev["action"] == "spawn"
    assert ev["reason"] == "burn_rate"
    gauges = reporter.summary()["gauges"]
    assert gauges["autoscaler/max_burn_rate"]["value"] == 2.5


def test_autoscaler_backfills_below_floor_without_hysteresis(
        lm, lm_params):
    reporter = Reporter()
    reps, router = mk_fleet(lm, lm_params, n=2, reporter=reporter)
    oracle = make_engine(lm, lm_params)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    want = [oracle.generate(p, 8) for p in prompts]

    def factory(rid):
        return Replica(rid, make_engine(lm, lm_params), role="both",
                       reporter=reporter)

    scaler = Autoscaler(
        router, factory,
        AutoscalerConfig(min_replicas=2, max_replicas=3, k_up=50,
                         cooldown_s=1e9),  # hysteresis frozen solid
        reporter=reporter,
    )
    handles = [router.submit(p, 8) for p in prompts]
    for _ in range(3):
        router.step()
    router.fail_replica(0, reason="test kill")
    # backfill fires on the very next step: k_up/cooldown are bypassed
    ev = scaler.step(now=0.0)
    assert ev is not None and ev["action"] == "spawn"
    assert ev["reason"] == "backfill"
    router.run_until_idle()
    for h, w in zip(handles, want):
        assert h.status == "finished"
        assert list(h.tokens) == w  # failover + backfill stay bit-exact


def test_autoscaler_drain_migrate_retire_zero_stream_loss(
        lm, lm_params):
    reporter = Reporter()
    reps, router = mk_fleet(lm, lm_params, n=2, reporter=reporter)
    oracle = make_engine(lm, lm_params)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [10, 11, 12]]
    want = [oracle.generate(p, 10) for p in prompts]

    def factory(rid):  # pragma: no cover - never called here
        raise AssertionError("scale-down must not spawn")

    scaler = Autoscaler(
        router, factory,
        AutoscalerConfig(min_replicas=1, max_replicas=2,
                         cooldown_s=0.0),
        reporter=reporter,
    )
    handles = [router.submit(p, 10) for p in prompts]
    # commit a few tokens so replica 0 holds LIVE KV pages mid-decode
    for _ in range(5):
        router.step()
    assert any(len(h.tokens) > 0 for h in handles)
    assert scaler.force_drain(0, now=0.0)
    assert not scaler.force_drain(1, now=0.0)  # one drain at a time
    # step() progresses migrate → retire; survivors keep decoding
    for i in range(50):
        scaler.step(now=0.1 * i)
        router.step()
        if 0 not in router.replicas:
            break
    assert 0 not in router.replicas
    actions = [ev["action"] for ev in scaler.events]
    assert actions == ["drain", "retire"]
    router.run_until_idle()
    for h, w in zip(handles, want):
        assert h.status == "finished"
        assert list(h.tokens) == w  # migrated mid-stream, bit-exact
    # migration really moved live sequences (not replay-from-scratch)
    assert sum(h.migrations for h in handles) >= 1
    assert sum(h.failovers for h in handles) == 0
    reps[1].engine.kv.assert_consistent()
    # retired replica leaves no health residue: its silence must never
    # read as a death and re-fire failover
    assert 0 not in router.health.check(now=1e9)
    counters = reporter.summary()["counters"]
    assert counters["serving/cluster/replicas_retired"] == 1
    assert counters["autoscaler/drain"] == 1
    assert counters["autoscaler/retire"] == 1


def test_force_drain_refuses_below_floor(lm, lm_params):
    reps, router = mk_fleet(lm, lm_params, n=1)
    scaler = Autoscaler(router, lambda rid: None,
                        AutoscalerConfig(min_replicas=1),
                        reporter=Reporter())
    assert not scaler.force_drain(0)
    assert not scaler.force_drain("nope")


# ---------------------------------------------------------------------------
# Chaos grammar: serving coordinates + timed firing
# ---------------------------------------------------------------------------


def test_chaos_grammar_replica_time_coordinates():
    sched = chaos.ChaosSchedule.parse("kill:replica=1:at=0.25")
    (f,) = sched.faults
    assert f.kind == "kill" and f.replica == 1 and f.at == 0.25
    # round-trips through format() → parse()
    again = chaos.ChaosSchedule.parse(sched.format())
    assert again.faults == sched.faults
    # step-coordinate schedules still parse (training grammar intact)
    chaos.ChaosSchedule.parse("kill:rank=1:step=5")
    with pytest.raises(ValueError):
        chaos.ChaosSchedule.parse("kill:replica=1")  # no step/at
    assert chaos.validate_grammar() == []


def test_timed_chaos_fires_in_order_exactly_once():
    sched = chaos.ChaosSchedule.parse(
        "kill:replica=0:at=0.5;term:replica=1:at=0.2")
    now = [100.0]
    tc = chaos.TimedChaos(sched, clock=lambda: now[0])
    tc.start()
    assert tc.pending == 2
    assert tc.due() == ()
    now[0] = 100.3
    fired = tc.due()
    assert [f.kind for f in fired] == ["term"]
    now[0] = 101.0
    fired = tc.due()
    assert [(f.kind, f.replica) for f in fired] == [("kill", 0)]
    assert tc.pending == 0
    assert tc.due() == ()


# ---------------------------------------------------------------------------
# End-to-end replay over a real fleet (small, in-process)
# ---------------------------------------------------------------------------


def test_traffic_replay_over_fleet_is_bit_exact(lm, lm_params):
    spec = TrafficSpec(seed=11, requests=10, rate=500.0,
                       prompt_buckets=((3, 8, 1.0),),
                       output_buckets=((3, 6, 1.0),),
                       prefix_len=6, vocab=VOCAB)
    arrivals = workload.generate(spec)
    oracle = make_engine(lm, lm_params)
    want = {a.index: oracle.generate(list(a.prompt), a.max_new_tokens)
            for a in arrivals}
    reps, router = mk_fleet(lm, lm_params, n=2, max_queue=16)

    report = workload.replay(
        arrivals,
        lambda a: router.submit(list(a.prompt), a.max_new_tokens,
                                priority=a.priority),
        pump=lambda: router.step(),
        drain_timeout_s=120.0,
    )
    summary = workload.summarize(report)
    assert summary["finished"] == len(arrivals)
    for o in report.outcomes:
        assert o.finished
        assert list(o.handle.tokens) == want[o.arrival.index]
    assert summary["latency_p99_s"] >= summary["latency_p50_s"]


# ---------------------------------------------------------------------------
# CLI smoke (subprocess — slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_cli_traffic_autoscale_chaos_smoke():
    from conftest import subprocess_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.serve",
         "--replicas", "2", "--verify", "--autoscale",
         "--traffic", ("rate=200,requests=10,abusive_frac=0.2,"
                       "prompt_buckets=4-8:0.6|10-20:0.4,"
                       "output_buckets=4-8:0.7|10-16:0.3"),
         "--chaos", "kill:replica=1:at=0.5",
         "--slo", "queue=30,decode=30",
         "--vocab", "64", "--d-model", "16", "--d-ff", "32",
         "--max-len", "64", "--block-size", "4", "--n-blocks", "64"],
        capture_output=True, text=True, timeout=420,
        env=subprocess_env(n_devices=1), cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["parity"] == "ok"
    traffic = out["traffic"]
    assert traffic["finished"] == traffic["offered"]
    assert any(ev["action"] == "spawn" and ev["reason"] == "backfill"
               for ev in traffic["autoscaler_events"])
    assert set(traffic["burn_rates"]) == {"queue", "decode"}
    assert all(v < 1.0 for v in traffic["burn_rates"].values())


def test_autoscaler_anomaly_forces_scale_up(lm, lm_params):
    """A fleet-view anomaly (goodput collapse) votes scale-up exactly
    like the burn-rate override — healthy watermarks, no burned SLO,
    yet the fleet grows with reason='anomaly'."""
    from chainermn_tpu.observability import AnomalyDetector

    reporter = Reporter()
    reps, router = mk_fleet(lm, lm_params, n=1, reporter=reporter)

    def factory(rid):
        return Replica(rid, make_engine(lm, lm_params), role="both",
                       reporter=reporter)

    det = AnomalyDetector(reporter=reporter, window=2, baseline=8,
                          min_samples=2, drop_factor=0.5)
    tokens = 0.0
    for i in range(6):  # healthy baseline: 100 tokens/s
        tokens += 100.0
        det.update({"counters": {"serving/tokens": tokens}}, now=float(i))
    assert not det.alarming()
    for i in range(6, 8):  # goodput collapses to 5 tokens/s
        tokens += 5.0
        det.update({"counters": {"serving/tokens": tokens}}, now=float(i))
    assert det.alarming()

    scaler = Autoscaler(
        router, factory,
        AutoscalerConfig(min_replicas=1, max_replicas=2, k_up=2,
                         cooldown_s=0.0),
        reporter=reporter, anomaly=det,
    )
    assert scaler.step(now=0.0) is None  # hysteresis: first vote
    ev = scaler.step(now=0.1)
    assert ev is not None and ev["action"] == "spawn"
    assert ev["reason"] == "anomaly"
    assert "as0" in router.replicas
    # the anomaly/* series reached the shared registry for dashboards
    s = reporter.summary()
    assert s["gauges"]["anomaly/goodput_drop"]["value"] == 1.0
    assert s["counters"]["anomaly/goodput_drop"] == 1


def test_traffic_tenant_dimension_deterministic_and_zipf():
    """Toggling the tenant dimension never perturbs the base arrival
    stream (child RNG), ids replay bit-for-bit, and popularity is
    Zipf-skewed toward t0."""
    base = workload.generate(TrafficSpec(seed=3, requests=60))
    spec = TrafficSpec(seed=3, requests=60, tenants=4)
    arr = workload.generate(spec)
    key = lambda a: (a.t, a.prompt, a.max_new_tokens, a.priority,
                     a.template, a.abusive)
    assert [key(a) for a in base] == [key(a) for a in arr]
    assert all(a.tenant is None for a in base)
    ids = [a.tenant for a in arr]
    assert set(ids) <= {f"t{k}" for k in range(4)}
    counts = {t: ids.count(t) for t in set(ids)}
    assert counts["t0"] == max(counts.values())  # Zipf head
    assert workload.generate(spec) == arr  # replay determinism
    # spec string round-trip carries the dimension
    s2 = TrafficSpec.parse(spec.format())
    assert s2.tenants == 4 and s2.tenant_zipf == spec.tenant_zipf


def test_traffic_summarize_per_tenant_curves(lm, lm_params):
    """bench-style replay against a real fleet reports per-tenant
    curves; untenanted replays report none."""
    reps, router = mk_fleet(lm, lm_params, n=2, max_queue=16)
    spec = TrafficSpec(
        seed=11, requests=8, rate=200.0, tenants=3,
        prompt_buckets=((3, 8, 1.0),), output_buckets=((3, 5, 1.0),),
        vocab=VOCAB,
    )
    arrivals = workload.generate(spec)

    def submit(a):
        return router.submit(list(a.prompt), a.max_new_tokens,
                             priority=a.priority, tenant=a.tenant)

    report = workload.replay(arrivals, submit, pump=router.step,
                             speedup=50.0)
    router.run_until_idle()
    summary = workload.summarize(report)
    per_tenant = summary["per_tenant"]
    assert set(per_tenant) <= {f"t{k}" for k in range(3)}
    assert sum(d["offered"] for d in per_tenant.values()) == 8
    assert sum(d["finished"] for d in per_tenant.values()) \
        == summary["finished"]
    fin_tokens = sum(d["tokens"] for d in per_tenant.values())
    assert fin_tokens == summary["goodput_tokens"]
    # the off-switch: no per_tenant block at all
    plain = workload.summarize(workload.ReplayReport(
        outcomes=report.outcomes[:0], wall_s=1.0))
    assert "per_tenant" not in plain


# ---------------------------------------------------------------------------
# Diurnal traffic dimension + deficit-weighted fair admission under load
# ---------------------------------------------------------------------------


def test_traffic_diurnal_envelope_deterministic_and_off_switch():
    """diurnal=0 is byte-identical to a pre-diurnal spec; a positive
    depth modulates the MMPP intensity through a seeded day-curve that
    replays bit-for-bit and round-trips through the spec string."""
    base = workload.generate(TrafficSpec(seed=5, requests=40))
    flat = workload.generate(TrafficSpec(seed=5, requests=40,
                                         diurnal=0.0))
    assert flat == base                       # the off-switch
    spec = TrafficSpec(seed=5, requests=40, diurnal=0.8,
                       diurnal_period_s=10.0)
    arr = workload.generate(spec)
    assert workload.generate(spec) == arr     # replay determinism
    key = lambda a: (a.prompt, a.max_new_tokens, a.priority,
                     a.template, a.abusive)
    # the envelope stretches/compresses arrival TIMES only — the
    # request contents come from untouched child generators
    assert [key(a) for a in arr] == [key(a) for a in base]
    assert [a.t for a in arr] != [a.t for a in base]
    s2 = TrafficSpec.parse(spec.format())
    assert s2.diurnal == 0.8 and s2.diurnal_period_s == 10.0
    assert TrafficSpec.parse(spec.format()) == spec


def test_traffic_diurnal_envelope_shape():
    """The day-curve crosses both sides of 1.0 over one period and is
    clamped strictly positive even at depth > 1."""
    spec = TrafficSpec(seed=5, diurnal=0.8, diurnal_period_s=10.0)
    env = [spec.diurnal_envelope(t) for t in
           [10.0 * k / 16 for k in range(16)]]
    assert max(env) > 1.0 > min(env)
    assert spec.diurnal_envelope(3.0) == pytest.approx(
        spec.diurnal_envelope(13.0))      # one-period translation
    deep = TrafficSpec(seed=5, diurnal=5.0, diurnal_period_s=10.0)
    assert all(
        deep.diurnal_envelope(10.0 * k / 64) >= 0.05
        for k in range(64)
    )
    # depth 0: identically 1 (no envelope at all)
    assert TrafficSpec(seed=5).diurnal_envelope(3.0) == 1.0


def test_tenant_fair_admission_under_doubled_load(lm, lm_params):
    """2x-load replay with DRR weights on every scheduler: the Zipf
    head tenant cannot starve the tail — every tenant finishes its
    offered work, and the deficit gauges ride the Reporter."""
    from chainermn_tpu.observability.reporter import Reporter

    reporter = Reporter()
    reps, router = mk_fleet(lm, lm_params, n=2, max_queue=32,
                            reporter=reporter)
    spec = TrafficSpec(
        seed=11, requests=16, rate=120.0, tenants=3,
        prompt_buckets=((3, 8, 1.0),), output_buckets=((3, 5, 1.0),),
        vocab=VOCAB,
    ).scaled(2.0)
    weights = spec.tenant_weights()
    assert weights["t0"] > weights["t2"]      # Zipf head weighs more
    for r in reps:
        r.scheduler.set_tenant_weights(weights)
    arrivals = workload.generate(spec)

    def submit(a):
        return router.submit(list(a.prompt), a.max_new_tokens,
                             priority=a.priority, tenant=a.tenant)

    report = workload.replay(arrivals, submit, pump=router.step,
                             speedup=50.0)
    router.run_until_idle()
    summary = workload.summarize(report)
    per_tenant = summary["per_tenant"]
    assert summary["finished"] == 16          # nothing starved out
    for t, d in per_tenant.items():
        assert d["finished"] == d["offered"], (t, d)
    gauges = reporter.summary()["gauges"]
    assert any(k.startswith("serve/tenant_deficit/") for k in gauges)
