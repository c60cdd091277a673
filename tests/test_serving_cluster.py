"""Multi-replica serving tier: router, migration, disaggregation,
failover health.

The cluster-level contract extends the single-engine one from
tests/test_serving.py:

1. **Bit-exact routing** — a token stream is identical whether a
   request runs alone through ``engine.generate``, shares one
   replica's continuous batch, or crosses replicas (failover re-prefill
   from the committed prefix, prefill→decode KV-page migration).
   Counter-based sampling makes the stream a pure function of
   ``(prompt, committed prefix, position)``.
2. **KV conservation across migration** — extract + restore moves a
   live sequence between pools with ``assert_consistent`` holding on
   both sides and the pages bit-equal over the wire.
3. **Load-aware placement** — the router spreads decode work, honors
   roles/draining/watermark admissibility, and propagates the
   frontend's throughput-derived retry-after hint when every queue is
   full.
4. **Liveness** — heartbeat death detection re-queues exactly the dead
   replica's in-flight requests; survivors never see corrupted state.

All CPU, in-process (threads at most).  The cross-process service loop
soaks in tests/test_multiprocess.py.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    OutOfBlocks,
    QueueFull,
    Request,
    SamplingParams,
    prompt_digests,
)
from chainermn_tpu.serving.cluster import (
    HeartbeatMonitor,
    Replica,
    ReplicaRouter,
    ThreadedClusterDriver,
    extract_sequence,
    recv_snapshot,
    restore_sequence,
    scale_signals,
    send_snapshot,
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


def prompts_for(n, rng_seed=7, lo=3, hi=13):
    rng = np.random.default_rng(rng_seed)
    return [
        [int(t) for t in rng.integers(0, VOCAB, size=int(l))]
        for l in rng.integers(lo, hi, size=n)
    ]


def oracle_streams(lm, lm_params, prompts, n):
    """Sequential single-engine reference — a FRESH engine per call so
    no cluster state can leak into the baseline."""
    eng = make_engine(lm, lm_params)
    return [eng.generate(p, n) for p in prompts]


# ---------------------------------------------------------------------------
# Unit seams: seq_len, adopt_request, retry-after hint
# ---------------------------------------------------------------------------


def test_kv_seq_len_tracks_allocation(lm, lm_params):
    eng = make_engine(lm, lm_params)
    eng.kv.allocate("s", 6)
    assert eng.kv.seq_len("s") == 6
    eng.kv.extend("s", 9)
    assert eng.kv.seq_len("s") == 9
    eng.kv.free("s")
    with pytest.raises(KeyError):
        eng.kv.seq_len("s")


def test_adopt_request_validates_cache_state(lm, lm_params):
    from chainermn_tpu.serving import ContinuousBatchingScheduler

    eng = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(eng)
    req = Request(request_id="r", prompt=[1, 2, 3], max_new_tokens=4)
    req.generated = [5]
    # no pages for "r" at all
    with pytest.raises(ValueError):
        sched.adopt_request(req)
    # pages covering the wrong number of positions
    eng.kv.allocate("r", 2)
    with pytest.raises(ValueError):
        sched.adopt_request(req)
    eng.kv.extend("r", len(req.context) - 1)
    sched.adopt_request(req)
    assert req in sched.running
    # adoption is batch-capacity bounded (retryable, not terminal)
    for i in range(eng.max_batch - 1):
        sched.running.append(
            Request(request_id=i, prompt=[1], max_new_tokens=1)
        )
    r2 = Request(request_id="r2", prompt=[1, 2], max_new_tokens=4)
    eng.kv.allocate("r2", 1)
    with pytest.raises(OutOfBlocks):
        sched.adopt_request(r2)


def test_adopted_request_stream_is_bit_exact(lm, lm_params):
    """Adoption = exactly the state a locally-running request has
    between iterations: prefill by hand, adopt, finish — stream matches
    the sequential engine."""
    from chainermn_tpu.serving import ContinuousBatchingScheduler

    prompt = prompts_for(1)[0]
    [want] = oracle_streams(lm, lm_params, [prompt], 6)

    eng = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(eng)
    req = Request(request_id="a", prompt=prompt, max_new_tokens=6)
    eng.kv.allocate("a", len(prompt))
    logits = eng.prefill(prompt, "a")
    req.generated = [eng.sample(logits, req.sampling, len(prompt))]
    sched.adopt_request(req)
    sched.run_to_completion()
    assert req.generated == want


def test_frontend_retry_after_hint_from_throughput(lm, lm_params):
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler,
        ServeFrontend,
    )

    fe = ServeFrontend(
        ContinuousBatchingScheduler(make_engine(lm, lm_params)),
        max_queue=2,
    )
    p = prompts_for(1)[0]
    # cold: no throughput estimate yet, hint is None
    fe.submit(p, 8)
    fe.submit(p, 8)
    with pytest.raises(QueueFull) as e1:
        fe.submit(p, 8)
    assert e1.value.retry_after_s is None
    assert fe.decode_tokens_per_sec() is None
    for _ in range(4):
        fe.step()
    assert fe.decode_tokens_per_sec() > 0
    fe.submit(p, 8)   # the first two are running now; queue refills
    fe.submit(p, 8)
    with pytest.raises(QueueFull) as e2:
        fe.submit(p, 8)
    assert e2.value.retry_after_s > 0
    assert "retry after" in str(e2.value)
    fe.run_until_idle()


# ---------------------------------------------------------------------------
# Router: load-aware placement, parity, backpressure
# ---------------------------------------------------------------------------


def _mk_cluster(lm, lm_params, n=2, roles=None, **router_kw):
    reps = [
        Replica(i, make_engine(lm, lm_params),
                role=(roles[i] if roles else "both"),
                max_queue=router_kw.pop(f"_q{i}", 8))
        for i in range(n)
    ]
    return reps, ReplicaRouter(reps, **router_kw)


def test_router_parity_and_load_spread(lm, lm_params):
    prompts = prompts_for(6, rng_seed=3)
    want = oracle_streams(lm, lm_params, prompts, 8)
    reps, router = _mk_cluster(lm, lm_params, n=2)
    handles = [router.submit(p, 8) for p in prompts]
    router.run_until_idle()
    for h, w in zip(handles, want):
        assert h.status == "finished"
        assert router.result(h) == w
    # load-aware scoring spreads concurrent work over both replicas
    assert {h.replica_id for h in handles} == {0, 1}
    for r in reps:
        r.engine.kv.assert_consistent()


def test_router_respects_draining_and_roles(lm, lm_params):
    reps, router = _mk_cluster(lm, lm_params, n=2)
    router.drain(0)
    h = router.submit(prompts_for(1)[0], 4)
    router.run_until_idle()
    assert h.replica_id == 1
    # prefill-only replicas never take decode placements
    reps2, router2 = _mk_cluster(lm, lm_params, n=2,
                                 roles=["prefill", "both"])
    h2 = router2.submit(prompts_for(1)[0], 4)
    router2.run_until_idle()
    assert h2.replica_id == 1


def test_router_queue_full_propagates_min_hint(lm, lm_params):
    reps = [Replica(0, make_engine(lm, lm_params, max_batch=1),
                    max_queue=1)]
    router = ReplicaRouter(reps)
    p = prompts_for(1)[0]
    router.submit(p, 8)
    with pytest.raises(QueueFull):
        router.submit(p, 8)
    router.run_until_idle()


def test_router_failover_is_bit_exact(lm, lm_params):
    """Kill a replica mid-stream: its requests re-place on the
    survivor with the committed prefix replayed — streams stay
    bit-identical to the sequential oracle and the survivor's cache
    invariants hold."""
    prompts = prompts_for(6, rng_seed=11, lo=4, hi=10)
    want = oracle_streams(lm, lm_params, prompts, 8)
    reps, router = _mk_cluster(
        lm, lm_params, n=2,
        health=HeartbeatMonitor([0, 1], miss_after_s=1e9),
    )
    handles = [router.submit(p, 8) for p in prompts]
    for _ in range(3):  # some tokens committed on both replicas
        router.step()
    victim = next(h.replica_id for h in handles if not h.done)
    survivor = 1 - victim
    requeued = router.fail_replica(victim, "test kill")
    assert requeued > 0
    router.run_until_idle()
    for h, w in zip(handles, want):
        assert h.status == "finished"
        assert h.tokens == w
    assert any(h.failovers == 1 for h in handles)
    assert all(
        h.replica_id == survivor for h in handles if h.failovers
    )
    reps[survivor].engine.kv.assert_consistent()


def test_cluster_handle_timeout_and_result(lm, lm_params):
    clock = [0.0]
    reps = [Replica(0, make_engine(lm, lm_params),
                    clock=lambda: clock[0])]
    router = ReplicaRouter(reps, clock=lambda: clock[0])
    h = router.submit(prompts_for(1)[0], 8, timeout_s=5.0)
    router.step()
    clock[0] = 10.0
    router.step()
    assert h.status == "timeout"
    with pytest.raises(TimeoutError):
        router.result(h)


# ---------------------------------------------------------------------------
# Migration: extract/restore, wire roundtrip
# ---------------------------------------------------------------------------


def test_migration_mid_stream_is_bit_exact(lm, lm_params):
    """Move a live sequence to a DIFFERENTLY-SIZED pool mid-decode and
    finish there — the stream equals the sequential oracle's."""
    prompt = prompts_for(1, rng_seed=5)[0]
    [want] = oracle_streams(lm, lm_params, [prompt], 8)

    src = make_engine(lm, lm_params)
    dst = make_engine(lm, lm_params, n_blocks=32)
    sp = SamplingParams()
    src.kv.allocate("s", len(prompt))
    logits = src.prefill(prompt, "s")
    toks = [src.sample(logits, sp, len(prompt))]
    cur = len(prompt)
    for _ in range(3):
        src.kv.extend("s", cur + 1)
        logits = src.decode([toks[-1]], ["s"], [cur])[0]
        cur += 1
        toks.append(src.sample(logits, sp, cur))

    snap = extract_sequence(src, "s", context=prompt + toks[:-1])
    assert snap.seq_len == cur and snap.n_pages > 0
    src.kv.free("s")
    src.kv.assert_consistent()

    restore_sequence(dst, snap, "t")
    dst.kv.assert_consistent()
    while len(toks) < 8:
        dst.kv.extend("t", cur + 1)
        logits = dst.decode([toks[-1]], ["t"], [cur])[0]
        cur += 1
        toks.append(dst.sample(logits, sp, cur))
    assert toks == want


def test_restore_rejects_mismatched_geometry(lm, lm_params):
    src = make_engine(lm, lm_params)
    src.kv.allocate("s", 5)
    src.prefill([1, 2, 3, 4, 5], "s")
    snap = extract_sequence(src, "s")
    bad = make_engine(lm, lm_params, block_size=8, n_blocks=32)
    with pytest.raises(ValueError):
        restore_sequence(bad, snap, "t")
    bad.kv.assert_consistent()  # failed restore leaks nothing


def test_snapshot_socket_roundtrip(monkeypatch):
    """KV snapshot over a REAL loopback SocketPlane pair: typed frames,
    dtype/shape/bit-equal pages, context intact."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_kvtransport import FakeKvClient

    from chainermn_tpu.communicators import kvtransport as kvt
    from chainermn_tpu.serving.cluster.migration import KVSnapshot

    fake = FakeKvClient()
    monkeypatch.setattr(kvt, "client", lambda: fake)
    p0, p1 = kvt.SocketPlane(0), kvt.SocketPlane(1)

    class MiniPlane:
        """ObjectPlane-shaped shim over a raw SocketPlane."""

        def __init__(self, sp, rank):
            self.sp, self.rank, self.members = sp, rank, [0, 1]
            self._seq = {}

        def send(self, obj, dest, tag=0):
            k = ("s", dest, tag)
            self.sp.send("mig", dest, tag, self._seq.get(k, 0), obj)
            self._seq[k] = self._seq.get(k, 0) + 1

        def recv(self, src, tag=0, timeout_ms=None):
            k = ("r", src, tag)
            out = self.sp.recv("mig", src, tag, self._seq.get(k, 0),
                               timeout_ms=timeout_ms)
            self._seq[k] = self._seq.get(k, 0) + 1
            return out

    rng = np.random.default_rng(0)
    snap = KVSnapshot(
        seq_len=7, block_size=4,
        pages=[
            rng.standard_normal((2, 4, 2, 8)).astype(np.float32),
            rng.standard_normal((2, 4, 2, 8)).astype(np.float32),
        ],
        context=[1, 2, 3, 4, 5, 6, 7],
    )
    got = []
    t = threading.Thread(
        target=lambda: got.append(
            recv_snapshot(MiniPlane(p1, 1), 0, timeout_ms=10_000)
        )
    )
    t.start()
    send_snapshot(MiniPlane(p0, 0), 1, snap)
    t.join(10)
    assert got and got[0].seq_len == 7
    assert got[0].context == snap.context
    for a, b in zip(got[0].pages, snap.pages):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Disaggregation: prefill role never decodes, decoders never prefill long
# ---------------------------------------------------------------------------


def test_disagg_prefill_decode_split(lm, lm_params):
    long_prompt = prompts_for(1, rng_seed=9, lo=24, hi=25)[0]
    short = prompts_for(3, rng_seed=10, lo=3, hi=6)
    want = oracle_streams(
        lm, lm_params, [long_prompt] + short, 8
    )
    reps, router = _mk_cluster(
        lm, lm_params, n=2, roles=["prefill", "decode"],
        prefill_threshold=10,
    )
    handles = [router.submit(long_prompt, 8)]
    handles += [router.submit(p, 8) for p in short]
    router.run_until_idle()
    for h, w in zip(handles, want):
        assert h.status == "finished"
        assert h.tokens == w
    # the long prompt decoded on the decode replica, and the prefill
    # replica never ran a decode step
    assert handles[0].replica_id == 1
    assert reps[0].engine._tokens_decoded == 0
    # short prompts bypassed the prefill tier entirely
    assert all(h.replica_id == 1 for h in handles[1:])
    for r in reps:
        r.engine.kv.assert_consistent()


def test_disagg_requeues_when_prompt_cannot_fit(lm, lm_params):
    """A prompt larger than the prefill pool is a terminal error, not a
    hang; one that merely doesn't fit RIGHT NOW re-queues behind the
    pool."""
    from chainermn_tpu.serving.cluster.disagg import (
        PrefillJob,
        run_prefill_job,
    )

    eng = make_engine(lm, lm_params, n_blocks=4)  # 16 token positions
    res = run_prefill_job(eng, PrefillJob(
        handle=0, prompt=list(range(1, 30)), sampling=SamplingParams(),
    ))
    assert res is not None and res.error is not None
    # transiently full: pages held by another sequence
    eng2 = make_engine(lm, lm_params, n_blocks=4)
    eng2.kv.allocate("hog", 12)
    out = run_prefill_job(eng2, PrefillJob(
        handle=1, prompt=list(range(1, 9)), sampling=SamplingParams(),
    ))
    assert out is None  # requeue signal
    eng2.kv.free("hog")
    out = run_prefill_job(eng2, PrefillJob(
        handle=1, prompt=list(range(1, 9)), sampling=SamplingParams(),
    ))
    assert out is not None and out.error is None
    assert out.snapshot.n_pages == 2
    eng2.kv.assert_consistent()  # scratch freed either way


# ---------------------------------------------------------------------------
# Cluster-global prefix index (gossip)
# ---------------------------------------------------------------------------


def test_prefix_digest_content_addressed_and_defrag_stable():
    """Digests are a pure function of the token run — platform-width
    independent, and untouched by defragmentation (defrag rewrites
    page VALUES; the index keys are token runs)."""
    from chainermn_tpu.serving import PagedKVCache, prefix_digest, \
        prompt_digests

    toks = list(range(12))
    d1 = prefix_digest(toks)
    assert d1 == prefix_digest(tuple(toks))
    assert d1 == prefix_digest(np.asarray(toks, np.int32))
    assert d1 != prefix_digest(toks[:-1])
    assert prompt_digests(toks, 4) == [
        prefix_digest(toks[:4]), prefix_digest(toks[:8]),
        prefix_digest(toks),
    ]
    assert prompt_digests(toks[:3], 4) == []     # no full page
    kv = PagedKVCache(16, 4)
    kv.allocate("a", 12)
    kv.register_prefix("a", toks)
    before = kv.prefix_digests()
    kv.free("a")
    kv.defragment()
    assert kv.prefix_digests() == before
    assert kv.match_prefix(toks)                 # index still serves


def test_prefix_digest_tenant_salt_isolates_namespaces():
    """The tenant namespace salts the digest AND the index key: the
    same token run digests differently per namespace, None reproduces
    the historical unsalted digest, and a registration in one namespace
    never matches from another — per-tenant prefix isolation is
    content-addressing, not an ACL bolted on top."""
    from chainermn_tpu.serving import PagedKVCache, prefix_digest, \
        prompt_digests

    toks = list(range(12))
    assert prefix_digest(toks) == prefix_digest(toks, namespace=None)
    da, db = prefix_digest(toks, "ta"), prefix_digest(toks, "tb")
    assert len({prefix_digest(toks), da, db}) == 3
    assert prompt_digests(toks, 4, namespace="ta") == [
        prefix_digest(toks[:4], "ta"), prefix_digest(toks[:8], "ta"),
        da,
    ]
    kv = PagedKVCache(16, 4)
    kv.allocate("a", 12)
    kv.register_prefix("a", toks, namespace="ta")
    assert kv.match_prefix(toks, namespace="ta")
    assert kv.match_prefix(toks, namespace="tb") == []
    assert kv.match_prefix(toks) == []           # default namespace too
    assert da in kv.prefix_digests()


def test_request_prefix_namespace_follows_tenant_unless_shared():
    """A request's prefix pages index under its tenant by default;
    ``shared_prefix`` opts into the unsalted shared namespace (the
    common-system-prompt case), and untenanted requests land there
    already."""
    r = Request(request_id="r", prompt=[1], max_new_tokens=1,
                tenant="ta")
    assert r.prefix_namespace == "ta"
    s = Request(request_id="s", prompt=[1], max_new_tokens=1,
                tenant="ta", shared_prefix=True)
    assert s.prefix_namespace is None
    t = Request(request_id="t", prompt=[1], max_new_tokens=1)
    assert t.prefix_namespace is None


def test_scheduler_tenant_prefix_isolation_and_shared_optin(lm,
                                                            lm_params):
    """Two tenants submitting the SAME prompt must not share prefix
    pages (zero cross-tenant prefix hits); with ``shared_prefix`` both
    land in the shared namespace and the second reuses the first's
    pages.  Streams are bit-identical throughout — isolation changes
    page accounting, never tokens."""
    from chainermn_tpu.serving import ContinuousBatchingScheduler

    prompt = [int(t) for t in
              np.random.default_rng(3).integers(0, VOCAB, size=9)]
    want = oracle_streams(lm, lm_params, [prompt], 5)[0]

    def run(shared):
        eng = make_engine(lm, lm_params)
        sched = ContinuousBatchingScheduler(eng)
        # sequential, so the second tenant's prompt arrives AFTER the
        # first's prefix pages are registered — a hit iff shareable
        for i, ten in enumerate(("ta", "tb")):
            sched.add_request(Request(
                request_id=f"r{i}", prompt=list(prompt),
                max_new_tokens=5, tenant=ten, shared_prefix=shared))
            while sched.has_work:
                sched.step()
        assert [r.generated for r in sched.results().values()] \
            == [want, want]
        return eng._tokens_prefix_cached

    assert run(shared=False) == 0          # isolated: no reuse
    assert run(shared=True) > 0            # opted in: pages shared


# ---------------------------------------------------------------------------
# Shard groups: plan_groups, lockstep mirroring, pipelined decode
# ---------------------------------------------------------------------------


def test_plan_groups_partitions_ranks_into_leader_led_runs():
    from chainermn_tpu.serving.cluster import plan_groups

    groups = plan_groups(5, group_size=2)
    assert [g.leader for g in groups] == [1, 3]
    assert [g.followers for g in groups] == [(2,), (4,)]
    assert all(g.group_size == 2 and g.pp_stages == 1 for g in groups)
    assert groups[0].ranks == (1, 2) and groups[0].n_shards == 2

    # tp x pp: shard count is the product
    tp_pp = plan_groups(5, group_size=2, pp_stages=2)
    assert len(tp_pp) == 1 and tp_pp[0].ranks == (1, 2, 3, 4)
    assert tp_pp[0].n_shards == 4 and tp_pp[0].pp_stages == 2

    # K=1 degenerates to today's one-process replicas
    solo = plan_groups(4)
    assert [g.leader for g in solo] == [1, 2, 3]
    assert all(g.followers == () for g in solo)

    with pytest.raises(ValueError):
        plan_groups(4, group_size=2)     # 3 ranks don't split into 2s
    with pytest.raises(ValueError):
        plan_groups(2, group_size=2)     # not even one full group


def test_engine_mirror_replay_lockstep_parity(lm, lm_params):
    """The shard-group invariant, single-process: a follower that only
    replays the leader's mirrored device steps (prefill / decode /
    chunk / cow / defrag) over its own identically-seeded params ends
    the workload with a BIT-IDENTICAL KV cache — no scheduler, no
    sampler, no block tables of its own.  Mixed greedy + sampled
    traffic with a shared prefix, so the replay covers the chunk
    (suffix prefill) and CoW (rewind) ops, not just the easy two."""
    from chainermn_tpu.serving import ContinuousBatchingScheduler

    leader = make_engine(lm, lm_params)
    follower = make_engine(lm, lm_params)
    ops = []
    leader.mirror_sink = lambda op, payload: ops.append((op, payload))

    rng = np.random.default_rng(11)
    shared = [int(t) for t in rng.integers(0, VOCAB, size=8)]
    sched = ContinuousBatchingScheduler(leader)
    for i in range(3):
        # r2's prompt IS the shared prefix: fully cached, so the
        # scheduler takes the CoW-rewind path ("cow" coverage).
        tail = ([int(t) for t in rng.integers(0, VOCAB, size=3 + i)]
                if i < 2 else [])
        sched.add_request(Request(
            request_id=f"r{i}", prompt=shared + tail, max_new_tokens=6,
            sampling=(SamplingParams() if i % 2 == 0 else
                      SamplingParams(temperature=0.9, top_k=8,
                                     seed=100 + i)),
        ))
        while sched.has_work:
            sched.step()
    # Deterministic fragmentation: compact first, then leave a hole
    # below a live allocation so this defragment MUST move pages.
    leader.defragment()
    leader.kv.allocate("x", 8)
    leader.kv.allocate("y", 8)
    leader.kv.free("x")
    assert leader.defragment() > 0

    assert {op for op, _ in ops} >= {"prefill", "decode", "chunk",
                                     "cow", "defrag"}
    for op, payload in ops:
        follower.apply_step(op, payload)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        leader._cache, follower._cache,
    )
    with pytest.raises(ValueError):
        follower.apply_step("nonsense", ())


def test_pp_microbatched_decode_streams_bit_exact(lm, lm_params):
    """Splitting the decode batch into pipeline microbatches must not
    change a single token: per-sequence attention + counter-based
    sampling make each row's result independent of batch composition,
    so the contiguous-span split is bit-exact by construction.  This is
    the invariant that lets pp_stages be a pure throughput knob."""
    from chainermn_tpu.serving import ContinuousBatchingScheduler

    prompts = prompts_for(4, rng_seed=23)
    want = oracle_streams(lm, lm_params, prompts, 6)

    def run(pp):
        eng = make_engine(lm, lm_params)
        eng.pp_stages = pp
        sched = ContinuousBatchingScheduler(eng)
        for i, p in enumerate(prompts):
            sched.add_request(Request(
                request_id=i, prompt=list(p), max_new_tokens=6))
        while sched.has_work:
            sched.step()
        res = sched.results()
        return [res[i].generated for i in range(len(prompts))]

    assert run(1) == want
    assert run(2) == want
    assert run(3) == want


def test_prefix_gossip_versioned_anti_entropy():
    """Snapshots apply strictly-newer only: duplicates and reordered
    deliveries are no-ops, so load-beat gossip is idempotent."""
    from chainermn_tpu.serving.cluster import PrefixGossip

    g = PrefixGossip()
    assert g.observe("B", 2, (10, 20, 30))
    assert not g.observe("B", 2, (10, 20, 30))       # dup
    assert not g.observe("B", 1, (99,))              # stale reorder
    assert g.hit_pages([10, 20, 30], "B") == 3
    assert g.hit_pages([10, 99, 30], "B") == 1       # leading run only
    assert g.hit_pages([99, 20], "B") == 0
    assert g.observe("B", 5, (10,))                  # newer wins
    assert g.hit_pages([10, 20], "B") == 1
    assert g.best([10]) == ("B", 1)
    g.forget("B")
    assert g.hit_pages([10], "B") == 0 and g.replicas() == []


def test_kv_index_version_bumps_on_mutation(lm, lm_params):
    """Every prefix-index mutation bumps the anti-entropy stamp, so a
    receiver can order snapshots without clocks."""
    engine = make_engine(lm, lm_params)
    v0 = engine.kv.index_version
    engine.generate(prompts_for(1, rng_seed=2, lo=8, hi=9)[0], 2)
    kv = engine.kv
    kv.allocate("w", 8)
    kv.register_prefix("w", list(range(8)))
    assert kv.index_version > v0
    v1 = kv.index_version
    kv.free("w")
    kv.drop_prefix_cache()
    assert kv.index_version > v1


def test_router_gossip_routes_to_warm_replica(lm, lm_params):
    """Same-template traffic converges on the replica already holding
    the template's pages — scored from the gossiped digest view, not
    just the in-process index probe."""
    template = prompts_for(1, rng_seed=41, lo=12, hi=13)[0]  # 3 pages
    reps, router = _mk_cluster(lm, lm_params, n=3)
    h0 = router.submit(list(template), 4)
    router.run_until_idle()
    warm = h0.replica_id
    router.step()                        # anti-entropy load beat
    dig = prompt_digests(template, 4)
    assert router.gossip.hit_pages(dig, warm) >= 3
    tails = prompts_for(3, rng_seed=43, lo=4, hi=8)
    handles = [router.submit(template + t, 4) for t in tails]
    router.run_until_idle()
    want = oracle_streams(lm, lm_params,
                          [template + t for t in tails], 4)
    for h, w in zip(handles, want):
        assert h.status == "finished" and h.tokens == w
        assert h.replica_id == warm      # prefix affinity held
    for r in reps:
        r.engine.kv.assert_consistent()


def test_stale_gossip_falls_back_to_local_prefill(lm, lm_params):
    """A phantom remote hit (gossip lags the holder dropping its
    cache) may still steer routing — but the chosen replica's
    admission re-probes its OWN index, so the request degrades to a
    full local prefill with the stream bit-exact, never corrupt."""
    template = prompts_for(1, rng_seed=41, lo=12, hi=13)[0]
    reps, router = _mk_cluster(lm, lm_params, n=2)
    h0 = router.submit(list(template), 4)
    router.run_until_idle()
    warm = h0.replica_id
    router.step()                        # gossip now advertises warm
    # the holder loses its cache; the router's view goes stale
    reps[warm].engine.kv.drop_prefix_cache()
    prompt = template + prompts_for(1, rng_seed=47, lo=4, hi=5)[0]
    h = router.submit(list(prompt), 4)
    router.run_until_idle()
    want = oracle_streams(lm, lm_params, [prompt], 4)[0]
    assert h.status == "finished" and h.tokens == want
    assert h.replica_id == warm          # routed by the stale view
    sched = reps[warm].scheduler
    assert sched._prefix_hit_tokens == 0  # phantom: local re-probe missed
    reps[warm].engine.kv.assert_consistent()
    # the next beat re-syncs the view to the replica's CURRENT index
    # (which now holds the just-served prompt — template included —
    # re-registered by its full local prefill)
    router.step()
    kv = reps[warm].engine.kv
    assert router.gossip.version(warm) == kv.index_version
    assert router.gossip.hit_pages(prompt_digests(template, 4), warm) \
        == len(kv.match_prefix(template)) == 3


def test_replica_load_gossip_fields_roundtrip(lm, lm_params):
    """ReplicaLoad carries the digest snapshot over the wire dict
    format unchanged, and peers predating the fields still parse."""
    from chainermn_tpu.serving.cluster import ReplicaLoad

    rep = Replica(0, make_engine(lm, lm_params))
    rep.frontend.submit(prompts_for(1, rng_seed=41, lo=12, hi=13)[0], 2)
    while rep.scheduler.has_work:
        rep.step()
    ld = rep.load()
    assert ld.block_size == 4 and ld.prefix_version > 0
    assert len(ld.prefix_digests) > 0
    assert ReplicaLoad.from_dict(ld.as_dict()) == ld
    # wire compat: an old peer's dict without the gossip fields
    old = {k: v for k, v in ld.as_dict().items()
           if k not in ("block_size", "prefix_version",
                        "prefix_digests")}
    ld_old = ReplicaLoad.from_dict(old)
    assert ld_old.block_size == 0 and ld_old.prefix_digests == ()


def test_replica_load_max_bucket_roundtrip(lm, lm_params):
    """The warm-ladder watermark rides the load beat: after a replica
    serves a prompt past its seed ladder, its gossiped ``max_bucket``
    covers the full context, survives the wire dict roundtrip, and an
    old peer's dict without the field still parses (cold: 0)."""
    from chainermn_tpu.serving.cluster import ReplicaLoad

    rep = Replica(0, make_engine(lm, lm_params, prefill_buckets=(8,)))
    prompt = prompts_for(1, rng_seed=71, lo=20, hi=21)[0]
    rep.frontend.submit(list(prompt), 2)
    while rep.scheduler.has_work:
        rep.step()
    ld = rep.load()
    assert ld.max_bucket >= len(prompt)
    assert ReplicaLoad.from_dict(ld.as_dict()) == ld
    old = {k: v for k, v in ld.as_dict().items() if k != "max_bucket"}
    assert ReplicaLoad.from_dict(old).max_bucket == 0


def test_router_warm_ladder_routes_long_prompts(lm, lm_params):
    """A prompt past the seed bucket ladder prefers the replica whose
    ladder already grew to cover it — even with ZERO shared pages: the
    warm replica serves it without a growth recompile.  The prefix
    cache is wiped first so only the ladder watermark can steer."""
    reps = [Replica(i, make_engine(lm, lm_params, prefill_buckets=(8,)))
            for i in range(2)]
    router = ReplicaRouter(reps)
    long0 = prompts_for(1, rng_seed=73, lo=20, hi=21)[0]
    reps[0].frontend.submit(list(long0), 2)  # grow replica 0's ladder
    while reps[0].scheduler.has_work:
        reps[0].step()
    assert reps[0].engine.max_bucket >= len(long0)
    # no shared pages can help the score: wipe the cache, keep the
    # ladder warm (compiled buckets are engine state, not kv state)
    reps[0].engine.kv.drop_prefix_cache()
    router.step()                        # load beat re-syncs the view
    prompt = prompts_for(1, rng_seed=79, lo=12, hi=13)[0]
    assert len(prompt) > 8               # past replica 1's cold ladder
    h = router.submit(list(prompt), 4)
    router.run_until_idle()
    want = oracle_streams(lm, lm_params, [prompt], 4)[0]
    assert h.status == "finished" and h.tokens == want
    # otherwise-identical scores tie-break to replica 1; only the
    # warm-ladder bonus can have pulled the placement to replica 0
    assert h.replica_id == 0
    for r in reps:
        r.engine.kv.assert_consistent()


# ---------------------------------------------------------------------------
# Health: heartbeats, scale signals, gauges
# ---------------------------------------------------------------------------


def test_heartbeat_monitor_detects_and_revives():
    clock = [0.0]
    mon = HeartbeatMonitor([0, 1], miss_after_s=2.0,
                           clock=lambda: clock[0])
    mon.beat(0)
    mon.beat(1)
    clock[0] = 1.0
    assert mon.check() == []
    clock[0] = 2.5
    mon.beat(1)
    assert mon.check() == [0]       # newly dead, exactly once
    assert mon.check() == []
    assert not mon.alive(0) and mon.alive(1)
    mon.beat(0)                     # replacement process beats again
    assert mon.alive(0)
    clock[0] = 3.0
    assert mon.check() == []


def test_scale_signals_pressure_and_drain(lm, lm_params):
    reps, router = _mk_cluster(lm, lm_params, n=2)
    sig = scale_signals(router.loads())
    assert sig["replicas_alive"] == 2
    assert sig["scale_up"] is False
    # idle twin fleet: one replica is a drain candidate
    assert sig["drain_candidate"] is not None
    # saturate the queues → scale-up signal, no drain candidate
    for h in range(20):
        try:
            router.submit(prompts_for(1)[0], 4)
        except QueueFull:
            break
    sig = scale_signals(router.loads(), queue_pressure_frac=0.1)
    assert sig["queued"] > 0
    assert sig["drain_candidate"] is None
    router.run_until_idle()


def test_replica_gauges_and_prometheus_labels(lm, lm_params):
    from chainermn_tpu.observability import Reporter
    from chainermn_tpu.tools.obs import to_prometheus

    rep = Reporter()
    replica = Replica("r0", make_engine(lm, lm_params), reporter=rep)
    h = replica.frontend.submit(prompts_for(1)[0], 4)
    while not h.done:
        replica.step()
    g = rep.summary()["gauges"]
    assert g["serving/running/replica/r0"]["value"] == 0
    assert g["serving/free_blocks/replica/r0"]["value"] == 64
    # bare names (single-engine serving) stay unsuffixed
    assert "serving/running" not in g

    summary = {"gauges": {
        "serving/running/replica/r0": {"sum": 2.0, "max": 2.0},
        "serving/running": {"sum": 1.0, "max": 1.0},
    }}
    prom = to_prometheus(summary)
    assert ('chainermn_tpu_gauge{name="serving/running",'
            'replica="r0"} 2' in prom)
    assert 'chainermn_tpu_gauge{name="serving/running"} 1' in prom


# ---------------------------------------------------------------------------
# CLI + threaded soak
# ---------------------------------------------------------------------------


def test_serve_cli_local_verify_smoke():
    from conftest import subprocess_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.serve",
         "--replicas", "2", "--verify", "--requests", "4",
         "--new-tokens", "6", "--prompt-len", "8",
         "--vocab", "32", "--d-model", "16", "--d-ff", "32",
         "--max-len", "64", "--block-size", "4", "--n-blocks", "32"],
        capture_output=True, text=True, timeout=420,
        env=subprocess_env(n_devices=1), cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["parity"] == "ok"
    assert out["statuses"] == {"finished": 4}
    assert out["tokens"] == 24


def test_serving_cluster_soak_threaded_failover(lm, lm_params):
    """Soak (auto-marked slow): threaded replicas, concurrent
    submission, one replica killed mid-stream — every stream bit-exact
    vs the sequential oracle, survivor invariants intact."""
    prompts = prompts_for(10, rng_seed=21, lo=4, hi=12)
    # half the traffic shares a 2-page prefix so the kill lands with
    # refcounted/registered pages live in every pool
    rng = np.random.default_rng(37)
    shared = [int(t) for t in rng.integers(0, VOCAB, size=8)]
    prompts = [shared + p if i % 2 == 0 else p
               for i, p in enumerate(prompts)]
    want = oracle_streams(lm, lm_params, prompts, 8)
    reps = [Replica(i, make_engine(lm, lm_params), max_queue=16,
                    spec_tokens=2)
            for i in range(3)]
    router = ReplicaRouter(
        reps, health=HeartbeatMonitor([0, 1, 2], miss_after_s=1e9),
    )
    with ThreadedClusterDriver(router) as drv:
        handles = [router.submit(p, 8, timeout_s=120.0)
                   for p in prompts]
        # let some tokens commit, then kill whichever replica owns
        # the first unfinished handle
        while sum(len(h.tokens) for h in handles) < 5:
            router.step(drive_replicas=False)
        victim = next(
            (h.replica_id for h in handles
             if not h.done and h.replica_id is not None), 0,
        )
        router.fail_replica(victim, "soak kill")
        drv.run_until_idle(timeout_s=240.0)
    for h, w in zip(handles, want):
        assert h.status == "finished", (h.request_id, h.status, h.error)
        assert h.tokens == w
    for r in reps:
        if r.replica_id != victim:
            r.engine.kv.assert_consistent()


# ---------------------------------------------------------------------------
# Fleet metrics plane: beat-carried snapshots, idempotent merge,
# dead-replica series hygiene, per-tenant accounting through the view
# ---------------------------------------------------------------------------


def test_metrics_gossip_idempotent_under_dup_and_reorder():
    """Replaying the beat stream in any order, with duplicates, folds to
    the same fleet view — the strictly-newer version check makes the
    merge idempotent exactly like the prefix index."""
    import random

    from chainermn_tpu.observability.reporter import Reporter
    from chainermn_tpu.serving.cluster import MetricsGossip

    def snap(steps, tokens):
        r = Reporter()
        r.count("serving/steps", steps)
        r.count("serving/tokens", tokens)
        r.gauge(f"serving/running/replica/{steps}", steps)
        return r.summary()

    beats = [(1, 1, snap(1, 10)), (1, 2, snap(2, 25)),
             (2, 1, snap(3, 7)), (2, 2, snap(5, 9))]
    g = MetricsGossip()
    for rid, v, s in beats:
        assert g.observe(rid, v, s)
    want = g.fleet_view()
    assert want["counters"]["serving/steps"] == 2 + 5
    assert want["counters"]["serving/tokens"] == 25 + 9

    rng = random.Random(7)
    for _ in range(5):
        replay = beats * 3
        rng.shuffle(replay)
        g2 = MetricsGossip()
        for rid, v, s in replay:
            g2.observe(rid, v, s)
        assert g2.fleet_view() == want
        assert g2.version(1) == 2 and g2.version(2) == 2

    # wire compat: None summaries and stale versions are no-ops
    assert not g.observe(1, 5, None)
    assert not g.observe(1, 1, snap(99, 99))
    assert g.fleet_view() == want
    # forget drops the replica's whole contribution from the next view
    g.forget(2)
    assert g.replicas() == [1]
    assert g.fleet_view()["counters"]["serving/steps"] == 2
    assert g.latest(2) is None and g.version(2) is None


def test_fleet_view_tenants_and_dead_replica_series_drop(lm, lm_params):
    """End-to-end fleet plane, in process: each replica owns a registry
    gossiped on its load beats, the router's fleet_view merges them with
    its own reporter (per-tenant counters included), and failing a
    replica drops its per-replica series from the very next view."""
    from chainermn_tpu.observability.reporter import Reporter

    router_rep = Reporter()
    mreps = {i: Reporter() for i in range(2)}
    reps = [
        Replica(i, make_engine(lm, lm_params), role="both",
                reporter=mreps[i], metrics_reporter=mreps[i],
                max_queue=8)
        for i in range(2)
    ]
    router = ReplicaRouter(
        reps, reporter=router_rep,
        health=HeartbeatMonitor([0, 1], miss_after_s=1e9),
    )
    prompts = prompts_for(4, rng_seed=19)
    handles = [router.submit(p, 6, tenant=f"t{i % 2}")
               for i, p in enumerate(prompts)]
    router.run_until_idle()
    assert all(h.status == "finished" for h in handles)

    view = router.fleet_view()
    # one scrape covers the fleet: per-tenant token accounting is exact
    produced = sum(len(h.tokens) for h in handles)
    assert (view["counters"]["tenant/t0/tokens_out"]
            + view["counters"]["tenant/t1/tokens_out"]) == produced
    assert (view["counters"]["tenant/t0/tokens_in"]
            + view["counters"]["tenant/t1/tokens_in"]
            ) == sum(len(p) for p in prompts)
    assert view["counters"]["tenant/t0/admit"] == 2
    # per-tenant KV residency gauges rode the beats in
    assert view["gauges"]["tenant/t0/kv_page_seconds"]["value"] > 0
    # per-replica series from BOTH replicas are visible in the one view
    for rid in (0, 1):
        assert any(k.endswith(f"/replica/{rid}") for k in view["gauges"])

    # kill replica 0: snapshot AND router-side per-replica series drop
    # from the very next fleet_view — no beat needed, no stale series
    router.fail_replica(0, "test kill")
    view2 = router.fleet_view()
    for table in ("gauges", "counters", "histograms"):
        stale = [k for k in view2.get(table, {})
                 if k.endswith("/replica/0") or "/replica/0/" in k]
        assert not stale, (table, stale)
    assert 0 not in router.metrics.replicas()
    # the survivor's series are untouched
    assert any(k.endswith("/replica/1") for k in view2["gauges"])
    reps[1].engine.kv.assert_consistent()


def test_retire_replica_forgets_metrics_snapshot(lm, lm_params):
    """Planned scale-down hygiene matches the failure path: retiring a
    drained replica removes its gossiped snapshot and per-replica
    series from the fleet view."""
    from chainermn_tpu.observability.reporter import Reporter

    router_rep = Reporter()
    mreps = {i: Reporter() for i in range(2)}
    reps = [
        Replica(i, make_engine(lm, lm_params), role="both",
                reporter=mreps[i], metrics_reporter=mreps[i],
                max_queue=8)
        for i in range(2)
    ]
    router = ReplicaRouter(reps, reporter=router_rep)
    # enough concurrent work that BOTH replicas serve some of it, so
    # the survivor's snapshot carries tenant counters after the retire
    handles = [router.submit(p, 4, tenant="acme")
               for p in prompts_for(6, rng_seed=23)]
    router.run_until_idle()
    assert all(h.status == "finished" for h in handles)
    assert {h.replica_id for h in handles} == {0, 1}
    assert 1 in router.metrics.replicas()
    router.drain(1)
    router.migrate_out(1)
    router.run_until_idle()
    assert router.retire_replica(1)
    assert 1 not in router.metrics.replicas()
    view = router.fleet_view()
    assert not any(
        k.endswith("/replica/1") or "/replica/1/" in k
        for table in ("gauges", "counters", "histograms")
        for k in view.get(table, {})
    )
    # tenant counters from the SURVIVOR keep accumulating in the view
    assert view["counters"]["tenant/acme/tokens_out"] > 0
