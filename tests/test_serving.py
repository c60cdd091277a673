"""Serving subsystem: paged KV cache, engine, scheduler, frontend.

The contract under test, in rough order of importance:

1. **Bit-exact batching** — a request's token stream is identical
   whether it runs alone (``engine.generate``), shares continuous-
   batched iterations, or is preempted and recomputed mid-flight.
   Token-id comparisons: greedy argmax over fp32 logits makes them an
   exact-equality surface.
2. **Page conservation** — no allocation pattern (including eviction
   churn and defragmentation) leaks or aliases a page.
3. **Bounded recompiles** — compiled step count tracks the bucket
   ladder, not the request count.
4. **Policy behavior** — FCFS admission, latest-first preemption,
   queue backpressure, deadline expiry (fake clock: no sleeps).
5. **Collective-free decode** — the jitted decode step's HLO census is
   pinned empty in ``tests/golden/serving_decode_census.json``
   (regen: ``python tests/test_serving.py --regen``).

All CPU; the module-scope LM keeps the suite's jit count low.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.serving import (
    ContinuousBatchingScheduler,
    EngineConfig,
    InferenceEngine,
    OutOfBlocks,
    PagedKVCache,
    QueueFull,
    Request,
    SamplingParams,
    ServeFrontend,
)

CENSUS_GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "golden", "serving_decode_census.json",
)
SP_CENSUS_GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "golden", "serving_sp_prefill_census.json",
)
TP_CENSUS_GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "golden", "serving_tp_decode_census.json",
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


@pytest.fixture(scope="module")
def oracle(lm, lm_params):
    """Naive full-recompute greedy decode on the plain dense model — the
    reference every cached-KV path must match bit-exactly."""

    def run(prompt, n):
        toks = list(map(int, prompt))
        out = []
        for _ in range(n):
            logits = lm.apply(lm_params, jnp.asarray([toks], jnp.int32))
            out.append(int(np.argmax(
                np.asarray(logits[0, -1], np.float32)
            )))
            toks.append(out[-1])
        return out

    return run


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


# ---------------------------------------------------------------------------
# PagedKVCache: accounting invariants
# ---------------------------------------------------------------------------
def test_kv_cache_alloc_free_conservation():
    kv = PagedKVCache(n_blocks=8, block_size=4)
    t = kv.allocate("a", 9)          # 3 pages
    assert t == [0, 1, 2] and kv.used_blocks == 3
    kv.assert_consistent()
    kv.allocate("b", 4)              # 1 page
    kv.assert_consistent()
    assert kv.free("a") == 3
    kv.assert_consistent()
    assert kv.used_blocks == 1 and "a" not in kv and "b" in kv
    with pytest.raises(KeyError):
        kv.free("a")
    with pytest.raises(ValueError):
        kv.allocate("b", 1)          # double-allocate
    kv.free("b")
    assert kv.used_blocks == 0 and kv.stats().utilization == 0.0


def test_kv_cache_extend_and_out_of_blocks():
    kv = PagedKVCache(n_blocks=4, block_size=4)
    kv.allocate("a", 4)
    assert kv.extend("a", 5) == [1]      # crosses a page boundary
    assert kv.extend("a", 8) == []       # within the second page
    kv.assert_consistent()
    kv.allocate("b", 8)
    with pytest.raises(OutOfBlocks):
        kv.extend("a", 9)
    with pytest.raises(OutOfBlocks):
        kv.allocate("c", 1)
    kv.assert_consistent()               # failed ops must not leak
    assert not kv.can_allocate(1)
    kv.free("b")
    assert kv.can_allocate(8) and not kv.can_allocate(8, reserve=1)


def test_kv_cache_padded_table_uses_oob_sentinel():
    kv = PagedKVCache(n_blocks=8, block_size=4)
    kv.allocate("a", 5)
    t = kv.padded_table("a", 4)
    assert t.dtype == np.int32
    assert list(t) == [0, 1, kv.invalid, kv.invalid]
    assert kv.invalid == 8               # OOB-high, never negative
    with pytest.raises(ValueError):
        kv.padded_table("a", 1)


def test_kv_prefix_share_refcounts_and_cow_split():
    kv = PagedKVCache(n_blocks=8, block_size=4)
    toks = list(range(10))                   # 2 full pages + 2 tokens
    kv.allocate("a", 10)
    assert kv.match_prefix(toks) == []       # nothing registered yet
    assert kv.register_prefix("a", toks) == 2
    hit = kv.match_prefix(toks)
    assert hit == kv.block_table("a")[:2]
    # second sequence shares the head; only the suffix draws pages
    before = kv.free_blocks
    kv.allocate("b", 10, prefix_pages=hit)
    assert kv.block_table("b")[:2] == hit
    assert before - kv.free_blocks == 1      # 1 fresh page, not 3
    assert kv.refcount(hit[0]) == 2
    kv.assert_consistent()
    # first partial-page write into a shared page → CoW split
    split = kv.make_writable("b", 4)         # position in shared page 2
    assert split is not None
    old, new = split
    assert old == hit[1] and kv.block_table("b")[1] == new
    assert kv.refcount(old) == 1 and kv.refcount(new) == 1
    # a's table still points at the original; the index is untouched
    assert kv.block_table("a")[1] == old
    assert kv.match_prefix(toks) == hit
    # private unregistered pages never split
    assert kv.make_writable("b", 9) is None
    kv.assert_consistent()


def test_kv_evict_one_of_two_sharers():
    kv = PagedKVCache(n_blocks=8, block_size=4)
    toks = list(range(8))
    kv.allocate("a", 8)
    kv.register_prefix("a", toks)
    shared = kv.match_prefix(toks)
    kv.allocate("b", 8, prefix_pages=shared)
    # evict (preempt/free) one sharer: pages survive with refcount 1
    kv.free("a")
    kv.assert_consistent()
    assert [kv.refcount(p) for p in shared] == [1, 1]
    assert kv.match_prefix(toks) == shared   # still shareable
    # evict the second: refcount-0 registered pages PARK, not free
    kv.free("b")
    kv.assert_consistent()
    assert kv.cached_blocks == 2
    assert kv.match_prefix(toks) == shared
    # resurrection from the cached pool costs nothing
    kv.allocate("c", 8, prefix_pages=kv.match_prefix(toks))
    assert kv.cached_blocks == 0 and kv.block_table("c") == shared
    kv.assert_consistent()


def test_kv_cached_pool_lru_eviction_under_pressure():
    kv = PagedKVCache(n_blocks=4, block_size=4)
    kv.allocate("a", 8)
    kv.register_prefix("a", list(range(8)))
    kv.free("a")                             # both pages parked
    assert kv.cached_blocks == 2
    assert kv.free_blocks == 4               # reclaimable counts cached
    # pool pressure evicts the OLDEST cached page and unregisters it
    kv.allocate("b", 12)                     # needs 3: 2 free + 1 cached
    kv.assert_consistent()
    assert kv.cached_blocks == 1
    assert len(kv.match_prefix(list(range(8)))) <= 1
    with pytest.raises(OutOfBlocks):
        kv.allocate("c", 8)                  # 1 cached + 0 free < 2
    kv.assert_consistent()


def test_kv_defragment_while_shared():
    kv = PagedKVCache(n_blocks=8, block_size=4)
    toks = list(range(8))
    kv.allocate("a", 8)
    kv.register_prefix("a", toks)
    kv.allocate("hole", 8)
    kv.allocate("b", 10, prefix_pages=kv.match_prefix(toks))
    kv.free("hole")                          # holes mid-pool
    shared_before = kv.match_prefix(toks)
    perm = kv.defragment()
    kv.assert_consistent()                   # conservation incl. refcounts
    assert perm is not None
    # both sharers' tables moved TOGETHER and the index followed
    shared_after = kv.match_prefix(toks)
    assert kv.block_table("a")[:2] == shared_after
    assert kv.block_table("b")[:2] == shared_after
    assert [kv.refcount(p) for p in shared_after] == [2, 2]
    # permutation semantics: new slot i holds old page perm[i]
    assert [perm[p] for p in shared_after] == shared_before
    # cached (refcount-0) pages survive defrag too
    kv.free("a")
    kv.free("b")
    assert kv.cached_blocks == 2
    assert kv.defragment() is None or kv.match_prefix(toks)
    kv.assert_consistent()


def test_kv_cache_defragment_permutation_semantics():
    kv = PagedKVCache(n_blocks=8, block_size=4)
    kv.allocate("a", 8)
    kv.allocate("b", 8)
    kv.free("a")                          # holes at pages 0,1
    pages = np.arange(8)                  # fake device pages: id content
    old_table = kv.block_table("b")
    perm = kv.defragment()
    kv.assert_consistent()
    new_pages = pages[perm]               # engine: take(pages, perm, 0)
    # b's data moved with its table: content at the new slots is the old
    # page ids it occupied before.
    assert [new_pages[i] for i in kv.block_table("b")] == old_table
    assert kv.block_table("b") == [0, 1]  # dense prefix
    # already compact: no device copy, free list reseeded dense
    assert kv.defragment() is None
    assert kv.allocate("c", 4) == [2]


# ---------------------------------------------------------------------------
# Engine: cached-KV decode parity, buckets, defrag
# ---------------------------------------------------------------------------
def test_engine_greedy_matches_full_recompute_oracle(lm, lm_params,
                                                     oracle):
    engine = make_engine(lm, lm_params)
    for prompt in prompts_for(4):
        assert engine.generate(prompt, 6) == oracle(prompt, 6)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0    # generate() frees its sequence


def test_engine_recompile_count_tracks_buckets(lm, lm_params):
    engine = make_engine(lm, lm_params)
    lengths = [3, 5, 9, 12]              # table-width buckets 1, 2, 4, 4
    rng = np.random.default_rng(0)
    for L in lengths:
        engine.generate([int(t) for t in rng.integers(0, VOCAB, L)], 3)
    st1 = engine.stats()
    # compiles track buckets touched, never the request count
    assert 0 < st1["prefill_compiles"] <= 3, st1
    # the same length profile again (fresh tokens): ZERO new compiles
    for L in lengths * 2:
        engine.generate([int(t) for t in rng.integers(0, VOCAB, L)], 3)
    st2 = engine.stats()
    assert st2["prefill_compiles"] == st1["prefill_compiles"], (st1, st2)
    assert st2["decode_compiles"] == st1["decode_compiles"], (st1, st2)
    # a much longer prompt lands in untouched buckets: compiles grow
    engine.generate(list(range(30)), 3)
    assert engine.stats()["prefill_compiles"] > st2["prefill_compiles"]
    st3 = engine.stats()
    if "decode_jit_cache_size" in st3:   # cross-check jit's own view
        assert st3["decode_jit_cache_size"] == st3["decode_compiles"]


def test_engine_defragment_mid_stream_keeps_numerics(lm, lm_params,
                                                     oracle):
    engine = make_engine(lm, lm_params)
    prompt = prompts_for(1)[0]
    want = oracle(prompt, 5)
    sid = "s"
    engine.kv.allocate(sid, len(prompt))
    logits = engine.prefill(prompt, sid)
    got, cur = [], len(prompt)
    for step in range(5):
        nxt = int(np.argmax(logits))
        got.append(nxt)
        if step == 4:
            break
        engine.kv.extend(sid, cur + 1)
        if step == 1:
            # Punch a hole below a live page so compaction has to MOVE
            # pages — including this sequence's — then decode again:
            # the stream must not notice.  ("lo"/"hi" take the next two
            # pages off the LIFO free list; freeing "lo" leaves "hi"
            # stranded above a hole.)
            engine.kv.allocate("lo", engine.kv.block_size)
            engine.kv.allocate("hi", engine.kv.block_size)
            engine.kv.free("lo")
            assert engine.defragment() > 0
            engine.kv.free("hi")
        logits = engine.decode([nxt], [sid], [cur])[0]
        cur += 1
    engine.kv.free(sid)
    assert got == want


def test_sampling_params_validation_and_determinism():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    logits = np.random.default_rng(0).normal(size=VOCAB).astype(
        np.float32
    )
    sp = SamplingParams(temperature=0.8, top_k=5, seed=3)
    draws = {InferenceEngine.sample(logits, sp, position=7)
             for _ in range(4)}
    assert len(draws) == 1               # counter-based: reproducible
    # top-k truncation: every draw over many positions is a top-k token
    topk = set(np.argsort(logits)[-5:])
    for pos in range(50):
        assert InferenceEngine.sample(logits, sp, pos) in topk
    # greedy ignores the RNG entirely
    g = SamplingParams()
    assert InferenceEngine.sample(logits, g, 0) == int(np.argmax(logits))


# ---------------------------------------------------------------------------
# Scheduler: continuous batching == sequential; preemption; fairness
# ---------------------------------------------------------------------------
def test_scheduler_batched_equals_sequential(lm, lm_params, oracle):
    engine = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(engine)
    prompts = prompts_for(6)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=p,
                                  max_new_tokens=6))
    res = sched.run_to_completion()
    for i, p in enumerate(prompts):
        assert res[i].state.value == "finished"
        assert res[i].generated == oracle(p, 6), f"request {i} diverged"
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_scheduler_preemption_recompute_is_bit_exact(lm, lm_params,
                                                     oracle):
    # Pool sized to force eviction: 4 requests want ~4 pages each but
    # only 10 exist.  Everyone must still finish with the exact
    # unpreempted stream.
    engine = make_engine(lm, lm_params, n_blocks=10)
    sched = ContinuousBatchingScheduler(engine, watermark_blocks=0)
    prompts = prompts_for(4, rng_seed=11)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=p,
                                  max_new_tokens=6))
    res = sched.run_to_completion()
    assert sum(r.preemptions for r in res.values()) > 0, (
        "scenario no longer triggers preemption; shrink the pool"
    )
    for i, p in enumerate(prompts):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == oracle(p, 6)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_scheduler_admission_is_fcfs(lm, lm_params):
    # max_batch 2: with 4 waiting requests, the first two admitted must
    # be the first two submitted, and a request is only admitted after
    # an earlier one retires.
    engine = make_engine(lm, lm_params, max_batch=2)
    sched = ContinuousBatchingScheduler(engine)
    order = []
    for i, p in enumerate(prompts_for(4, rng_seed=3)):
        req = Request(request_id=i, prompt=p, max_new_tokens=4)
        req.on_token = (
            lambda rid, tok: order.append(rid) if rid not in order
            else None
        )
        sched.add_request(req)
    sched.step()
    assert sorted(r.request_id for r in sched.running) == [0, 1]
    sched.run_to_completion()
    assert order == [0, 1, 2, 3]         # first token order = FCFS


def test_scheduler_rejects_impossible_requests(lm, lm_params):
    engine = make_engine(lm, lm_params, n_blocks=2)  # 8-token pool
    sched = ContinuousBatchingScheduler(engine)
    sched.add_request(Request(request_id=0, prompt=list(range(30)),
                              max_new_tokens=50))    # > max_len
    sched.add_request(Request(request_id=1, prompt=list(range(20)),
                              max_new_tokens=4))     # > pool
    sched.add_request(Request(request_id=2, prompt=[], max_new_tokens=4))
    res = sched.run_to_completion()
    assert res[0].state.value == "failed" and "max_len" in res[0].error
    assert res[1].state.value == "failed"
    assert res[2].state.value == "failed"
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_scheduler_tenant_drr_interleaves_backlogged_tenants(
        lm, lm_params):
    """A tenant that floods the queue first no longer monopolizes
    admission: with equal weights, two backlogged tenants alternate
    (FIFO preserved *within* each tenant), and clearing the weights
    reverts to the historical global FCFS exactly."""
    def run(weights):
        engine = make_engine(lm, lm_params, max_batch=1)
        sched = ContinuousBatchingScheduler(engine)
        sched.set_tenant_weights(weights)
        order = []
        for i in range(8):
            req = Request(request_id=i, prompt=[1 + i % 8, 2, 3],
                          max_new_tokens=4,
                          tenant="a" if i < 4 else "b")
            req.on_token = (
                lambda rid, tok: order.append(rid) if rid not in order
                else None
            )
            sched.add_request(req)
        sched.run_to_completion()
        return order

    # all of tenant a submitted before any of tenant b, equal costs
    assert run({"a": 1.0, "b": 1.0}) == [0, 4, 1, 5, 2, 6, 3, 7]
    assert run(None) == list(range(8))        # off-switch: strict FCFS


def test_scheduler_tenant_drr_weighted_shares_and_gauges(
        lm, lm_params):
    """Weights divide admission service: at 2:1 and equal costs, the
    first 9 serialized admissions split exactly 6/3, and the deficit
    counters ride the Reporter as serve/tenant_deficit/<id> gauges."""
    from chainermn_tpu.observability import Reporter

    rep = Reporter()
    engine = make_engine(lm, lm_params, max_batch=1)
    sched = ContinuousBatchingScheduler(engine, reporter=rep)
    sched.set_tenant_weights({"a": 2.0, "b": 1.0})
    order = []
    for i in range(24):
        req = Request(request_id=i, prompt=[1 + i % 8, 2, 3],
                      max_new_tokens=4,
                      tenant="a" if i % 2 == 0 else "b")
        req.on_token = (
            lambda rid, tok: order.append(rid) if rid not in order
            else None
        )
        sched.add_request(req)
    sched.run_to_completion()
    first9 = order[:9]
    by_tenant = {"a": 0, "b": 0}
    for rid in first9:
        by_tenant["a" if rid % 2 == 0 else "b"] += 1
    assert by_tenant == {"a": 6, "b": 3}
    # FIFO within each tenant throughout
    for parity in (0, 1):
        got = [rid for rid in order if rid % 2 == parity]
        assert got == sorted(got)
    gauges = rep.summary()["gauges"]
    assert any(k.startswith("serve/tenant_deficit/") for k in gauges)


def test_scheduler_publishes_gauges_and_counters(lm, lm_params):
    from chainermn_tpu.observability import Reporter

    rep = Reporter()
    engine = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(engine, reporter=rep)
    for i, p in enumerate(prompts_for(3)):
        sched.add_request(Request(request_id=i, prompt=p,
                                  max_new_tokens=4))
    sched.step()
    mid = rep.summary()["gauges"]
    assert mid["serving/running"]["value"] > 0
    assert mid["serving/cache_utilization"]["value"] > 0
    sched.run_to_completion()
    s = rep.summary()
    assert s["gauges"]["serving/running"]["value"] == 0   # last wins
    assert s["counters"]["serving/tokens"] == 12


def test_prefix_and_spec_gauges_flow_to_prometheus(lm, lm_params):
    """serve/prefix_hit_rate and serve/spec_accept_len reach the
    Reporter once their mechanisms fire, and render through the
    Prometheus exporter."""
    from chainermn_tpu.observability import Reporter
    from chainermn_tpu.tools.obs import to_prometheus

    rep = Reporter()
    engine = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(engine, reporter=rep,
                                        spec_tokens=3)
    # repetitive prompt → the n-gram speculator proposes drafts
    shared = [1, 2, 3, 4, 1, 2, 3, 4]        # two full pages
    sched.add_request(Request(request_id=0, prompt=list(shared),
                              max_new_tokens=4))
    sched.run_to_completion()
    # same prompt again AFTER its pages were registered → prefix hit
    sched.add_request(Request(request_id=1,
                              prompt=list(shared) + [5, 6],
                              max_new_tokens=4))
    sched.run_to_completion()
    g = rep.summary()["gauges"]
    assert g["serve/prefix_hit_rate"]["value"] > 0
    assert g["serve/spec_accept_len"]["value"] >= 1.0
    prom = to_prometheus(rep.summary())
    assert 'name="serve/prefix_hit_rate"' in prom
    assert 'name="serve/spec_accept_len"' in prom
    engine.kv.assert_consistent()


# ---------------------------------------------------------------------------
# Prefix cache + speculative decoding: the bit-exactness contract
# ---------------------------------------------------------------------------
def _shared_prefix_prompts():
    """Duplicate-prefix traffic: alternating prompts share an 8-token
    (2 full pages) head, one prompt IS exactly the shared head (the
    full-hit CoW-rewind path), the rest are fully random."""
    rng = np.random.default_rng(11)
    shared = [int(t) for t in rng.integers(0, VOCAB, size=8)]
    out = []
    for i in range(6):
        tail = [int(t) for t in rng.integers(0, VOCAB, size=3 + i % 3)]
        out.append(shared + tail if i % 2 == 0 else tail)
    out.append(list(shared))
    return out


@pytest.mark.parametrize("spec", [0, 3])
def test_prefix_cached_and_speculative_streams_bit_exact(
        lm, lm_params, oracle, spec):
    prompts = _shared_prefix_prompts()
    engine = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(engine, spec_tokens=spec)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=list(p),
                                  max_new_tokens=10))
    res = sched.run_to_completion()
    for i, p in enumerate(prompts):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == oracle(p, 10), f"request {i} diverged"
    # the mechanisms actually fired: shared pages were claimed, the
    # full-hit prompt took the CoW rewind, speculation emitted >1/step
    assert sched._prefix_hit_tokens > 0
    st = engine.stats()
    assert st["cow_splits"] >= 1
    assert st["tokens_prefix_cached"] > 0
    if spec:
        assert sched._spec_rows > 0
        assert sched._spec_emitted > sched._spec_rows  # accept_len > 1
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0                  # cached pages only


def test_speculative_sampled_streams_bit_exact(lm, lm_params):
    """Under temperature sampling the acceptance rate drops but the
    streams stay byte-identical: exact-match acceptance replays the
    counter-based RNG at the same positions sequential decode would."""
    prompts = _shared_prefix_prompts()
    sp = SamplingParams(temperature=0.8, top_k=8, seed=5)
    seq = make_engine(lm, lm_params)
    want = [seq.generate(p, 10, sampling=sp) for p in prompts]
    engine = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(engine, spec_tokens=3)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=list(p),
                                  max_new_tokens=10, sampling=sp))
    res = sched.run_to_completion()
    for i in range(len(prompts)):
        assert res[i].generated == want[i], f"request {i} diverged"
    assert sched._spec_rows > 0
    engine.kv.assert_consistent()


def test_speculative_survives_pool_pressure_bit_exact(lm, lm_params,
                                                      oracle):
    """Draft page growth is best-effort: when the pool can't hold the
    speculative over-extension the row decodes plainly that step, and
    preemption/recompute still replays the exact stream."""
    engine = make_engine(lm, lm_params, n_blocks=10)
    sched = ContinuousBatchingScheduler(engine, watermark_blocks=0,
                                        spec_tokens=3)
    prompts = prompts_for(4, rng_seed=11)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=p,
                                  max_new_tokens=6))
    res = sched.run_to_completion()
    for i, p in enumerate(prompts):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == oracle(p, 6)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_chunk_recompile_counts_pinned(lm, lm_params):
    """The speculative verify / suffix-prefill chunk program compiles
    once per (batch, chunk, width) bucket: a second identical workload
    on the same engine adds ZERO compiles of any kind."""
    prompts = _shared_prefix_prompts()

    def run(engine):
        sched = ContinuousBatchingScheduler(engine, spec_tokens=3)
        for i, p in enumerate(prompts):
            sched.add_request(Request(request_id=i, prompt=list(p),
                                      max_new_tokens=8))
        sched.run_to_completion()

    engine = make_engine(lm, lm_params)
    run(engine)
    st1 = engine.stats()
    assert st1["chunk_compiles"] == len(st1["chunk_shapes"])
    engine.reset()
    run(engine)
    st2 = engine.stats()
    assert (st2["prefill_compiles"], st2["decode_compiles"],
            st2["chunk_compiles"]) == \
        (st1["prefill_compiles"], st1["decode_compiles"],
         st1["chunk_compiles"])


# ---------------------------------------------------------------------------
# Model-based drafts (layer-truncated self-draft)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampling", [
    SamplingParams(),
    SamplingParams(temperature=0.8, top_k=8, seed=5),
], ids=["greedy", "sampled"])
def test_model_draft_streams_bit_exact(lm, lm_params, sampling):
    """The layer-truncated self-draft proposes instead of the n-gram
    lookup; exact-match acceptance keeps every stream byte-identical to
    the sequential engine under greedy AND temperature/top-k sampling —
    the draft source is a pure throughput decision."""
    prompts = _shared_prefix_prompts()
    seq = make_engine(lm, lm_params)
    want = [seq.generate(p, 8, sampling=sampling) for p in prompts]
    engine = make_engine(lm, lm_params, draft="model")
    sched = ContinuousBatchingScheduler(engine, spec_tokens=3)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=list(p),
                                  max_new_tokens=8, sampling=sampling))
    res = sched.run_to_completion()
    for i, w in enumerate(want):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == w, f"request {i} diverged"
    st = engine.stats()
    assert st["draft_source"] == "model"
    assert st["draft_layers"] == 1          # n_layers // 2 of the 2-layer lm
    assert sched._spec_rows_by.get("model", 0) > 0
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_model_draft_under_pool_pressure_and_defrag(lm, lm_params,
                                                    oracle):
    """Acceptance churn: model drafts through a pool small enough to
    force preemption, with defrag while prefix pages are shared —
    every stream still bit-exact, nothing leaked."""
    prompts = _shared_prefix_prompts()
    engine = make_engine(lm, lm_params, n_blocks=14, max_batch=3,
                         draft="model")
    sched = ContinuousBatchingScheduler(engine, watermark_blocks=0,
                                        spec_tokens=3)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=list(p),
                                  max_new_tokens=6))
    steps = 0
    while sched.has_work:
        sched.step()
        steps += 1
        if steps % 5 == 0:
            engine.defragment()
            engine.kv.assert_consistent()
        assert steps < 10_000
    res = sched.results()
    for i, p in enumerate(prompts):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == oracle(p, 6)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_model_draft_exact_when_full_depth(lm, lm_params):
    """draft_layers == the target's depth makes the draft the target:
    under greedy every proposal is accepted, so each verify row banks
    spec_tokens + 1 tokens — the upper bound the accept-length gauge
    should sit at."""
    engine = make_engine(lm, lm_params, draft="model", draft_layers=2)
    sched = ContinuousBatchingScheduler(engine, spec_tokens=3)
    p = prompts_for(1, rng_seed=2, lo=8, hi=9)[0]
    sched.add_request(Request(request_id=0, prompt=p,
                              max_new_tokens=9))
    res = sched.run_to_completion()
    assert res[0].state.value == "finished"
    assert sched._spec_emitted == 4 * sched._spec_rows
    assert engine.stats()["draft_layers"] == 2


def test_draft_model_param_subset_and_validation(lm, lm_params):
    """The draft params are references into the target tree — a strict
    subset, never copies — and bad depths are loud."""
    from chainermn_tpu.serving.spec import DraftModel, draft_param_names

    engine = make_engine(lm, lm_params, draft="model")
    dm = engine.draft_model
    assert set(dm.params) == set(draft_param_names(1))
    for name, sub in dm.params.items():
        assert sub is engine.params[name]   # reference, not a copy
    with pytest.raises(ValueError):
        DraftModel(lm, engine.params, 3, ())   # deeper than the target
    with pytest.raises(ValueError):
        DraftModel(lm, engine.params, 0, ())


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampling", [
    SamplingParams(),
    SamplingParams(temperature=0.7, top_k=6, seed=9),
], ids=["greedy", "sampled"])
def test_chunked_prefill_streams_bit_exact(lm, lm_params, sampling):
    """Prompts longer than the chunk threshold prefill in scheduler-
    interleaved slices; the first sampled token and every token after
    are byte-identical to monolithic prefill."""
    prompts = prompts_for(4, rng_seed=17, lo=14, hi=30)
    seq = make_engine(lm, lm_params)
    want = [seq.generate(p, 6, sampling=sampling) for p in prompts]
    engine = make_engine(lm, lm_params, prefill_chunk=4)
    sched = ContinuousBatchingScheduler(engine)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=list(p),
                                  max_new_tokens=6, sampling=sampling))
    res = sched.run_to_completion()
    for i, w in enumerate(want):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == w, f"request {i} diverged"
    assert engine.stats()["prefill_chunk"] == 4
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_chunked_prefill_interleaves_with_decode(lm, lm_params,
                                                 oracle):
    """While a long prompt slices through its prefill, already-running
    requests keep decoding — the whole point of chunking: tokens are
    emitted for the short request during the long one's prefill
    window."""
    engine = make_engine(lm, lm_params, prefill_chunk=4)
    sched = ContinuousBatchingScheduler(engine)
    short = prompts_for(1, rng_seed=4, lo=4, hi=5)[0]
    long_p = prompts_for(1, rng_seed=8, lo=28, hi=29)[0]
    shortreq = Request(request_id=0, prompt=short, max_new_tokens=10)
    sched.add_request(shortreq)
    sched.step()                         # short admitted + first token
    sched.add_request(Request(request_id=1, prompt=long_p,
                              max_new_tokens=4))
    sched.step()                         # long admitted -> mid-prefill
    longreq = next(r for r in sched.running if r.request_id == 1)
    assert longreq.prefill_pos is not None
    emitted_during = 0
    while longreq.prefill_pos is not None:
        before = len(shortreq.generated)
        sched.step()
        emitted_during += len(shortreq.generated) - before
    assert emitted_during > 0, "decode starved during chunked prefill"
    res = sched.run_to_completion()
    assert res[0].generated == oracle(short, 10)
    assert res[1].generated == oracle(long_p, 4)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_chunked_prefill_preempted_mid_prefill_recomputes(lm, lm_params,
                                                          oracle):
    """Preempting a mid-prefill victim frees its partially-written
    pages and recomputes the whole prompt on re-admission — the stream
    is still exact."""
    engine = make_engine(lm, lm_params, prefill_chunk=4)
    sched = ContinuousBatchingScheduler(engine)
    long_p = prompts_for(1, rng_seed=23, lo=20, hi=21)[0]
    sched.add_request(Request(request_id=0, prompt=long_p,
                              max_new_tokens=5))
    sched.step()
    req = sched.running[0]
    assert req.prefill_pos is not None and req.prefill_pos < len(long_p)
    assert sched._preempt_one()
    assert req.prefill_pos is None and req.preemptions == 1
    res = sched.run_to_completion()
    assert res[0].state.value == "finished", res[0].error
    assert res[0].generated == oracle(long_p, 5)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_chunked_prefill_over_prefix_hit_covers_suffix_only(
        lm, lm_params, oracle):
    """A prefix-cache hit composes with chunking: the slices cover only
    the un-shared suffix, starting exactly at the hit boundary."""
    engine = make_engine(lm, lm_params, prefill_chunk=4)
    shared = prompts_for(1, rng_seed=5, lo=12, hi=13)[0]   # 3 full pages
    sched = ContinuousBatchingScheduler(engine)
    sched.add_request(Request(request_id=0, prompt=list(shared),
                              max_new_tokens=4))
    sched.run_to_completion()            # warm the prefix index
    tail = prompts_for(1, rng_seed=6, lo=10, hi=11)[0]
    p2 = shared + tail
    starts = []
    real_chunk = engine.chunk

    def spy(rows, ids, st):
        starts.append(int(st[0]))
        return real_chunk(rows, ids, st)

    engine.chunk = spy
    try:
        sched2 = ContinuousBatchingScheduler(engine)
        sched2.add_request(Request(request_id=1, prompt=p2,
                                   max_new_tokens=5))
        res = sched2.run_to_completion()
    finally:
        engine.chunk = real_chunk
    assert res[1].state.value == "finished", res[1].error
    assert res[1].generated == oracle(p2, 5)
    assert starts and min(starts) == len(shared), (
        "slices must start at the hit boundary, not re-prefill the "
        f"shared pages (starts={starts})"
    )
    assert sched2._prefix_hit_tokens >= len(shared)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_chunked_prefill_with_model_draft_and_sampling(lm, lm_params):
    """The whole v2 stack at once — chunked prefill + self-draft
    speculation + temperature sampling — still bit-exact."""
    sp = SamplingParams(temperature=0.8, top_k=8, seed=3)
    prompts = prompts_for(3, rng_seed=19, lo=14, hi=26)
    seq = make_engine(lm, lm_params)
    want = [seq.generate(p, 7, sampling=sp) for p in prompts]
    engine = make_engine(lm, lm_params, prefill_chunk=4, draft="model")
    sched = ContinuousBatchingScheduler(engine, spec_tokens=3)
    for i, p in enumerate(prompts):
        sched.add_request(Request(request_id=i, prompt=list(p),
                                  max_new_tokens=7, sampling=sp))
    res = sched.run_to_completion()
    for i, w in enumerate(want):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == w, f"request {i} diverged"
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


# ---------------------------------------------------------------------------
# Long context: streaming prefix registration, bucket growth, sp prefill
# ---------------------------------------------------------------------------
def _slice_spy(engine):
    """Wrap ``engine.chunk`` recording ``(seq_id, start, end)`` per
    non-padding row; returns (calls, original) — restore in finally."""
    calls = []
    real = engine.chunk

    def spy(rows, ids, starts):
        for row, sid, st in zip(rows, ids, starts):
            if int(st) >= 0:
                calls.append((sid, int(st), int(st) + len(row)))
        return real(rows, ids, starts)

    engine.chunk = spy
    return calls, real


def test_streaming_registration_interleaved_doc_prefills_once(
        lm, lm_params, oracle):
    """Two interleaved requests over ONE shared document: each
    completed slice is registered immediately, the trailing request
    adopts it and computes the NEXT slice, so the document's body pages
    are computed exactly once ACROSS the pair (the leapfrog).  Only the
    sub-page tail — where both must sample their own first token — is
    computed twice.  ``stream_prefix=False`` reverts to register-at-
    completion: the document is prefilled twice."""
    doc = prompts_for(1, rng_seed=41, lo=40, hi=41)[0]
    want = oracle(doc, 5)
    page = 4
    body = (len(doc) - 1) // page * page   # the adoptable full pages

    def interleaved(stream):
        engine = make_engine(lm, lm_params, prefill_chunk=4)
        sched = ContinuousBatchingScheduler(engine,
                                            stream_prefix=stream)
        calls, real = _slice_spy(engine)
        try:
            sched.add_request(Request(request_id=0, prompt=list(doc),
                                      max_new_tokens=5))
            sched.step()
            sched.step()        # A mid-prefill, slices registered
            sched.add_request(Request(request_id=1, prompt=list(doc),
                                      max_new_tokens=5))
            res = sched.run_to_completion()
        finally:
            engine.chunk = real
        for i in (0, 1):
            assert res[i].state.value == "finished", res[i].error
            assert res[i].generated == want, f"request {i} diverged"
        engine.kv.assert_consistent()
        assert engine.kv.used_blocks == 0
        return calls, sched

    on_calls, on_sched = interleaved(True)
    cov = [0] * len(doc)
    for _, s, e in on_calls:
        for i in range(s, min(e, len(doc))):
            cov[i] += 1
    assert all(c == 1 for c in cov[:body]), (
        f"document body prefilled more than once: {cov}"
    )
    assert on_sched._stream_hit_tokens > 0

    off_calls, off_sched = interleaved(False)
    assert len(off_calls) > len(on_calls)
    assert on_sched._dup_prefill_slices < off_sched._dup_prefill_slices
    b_on = sum(1 for c in on_calls if c[0] == 1)
    b_off = sum(1 for c in off_calls if c[0] == 1)
    assert b_on < b_off


def test_streaming_registration_survives_preemption(lm, lm_params,
                                                    oracle):
    """A mid-prefill victim's streamed slices stay registered (its
    pages park at refcount 0 in the reusable pool); both its own replay
    and a later request over the same document claim them at admission
    instead of recomputing — and the streams stay exact."""
    doc = prompts_for(1, rng_seed=43, lo=36, hi=37)[0]
    engine = make_engine(lm, lm_params, prefill_chunk=4)
    sched = ContinuousBatchingScheduler(engine)
    sched.add_request(Request(request_id=0, prompt=list(doc),
                              max_new_tokens=4))
    for _ in range(3):
        sched.step()
    req = sched.running[0]
    assert req.prefill_pos is not None and req.prefill_pos < len(doc)
    assert sched._preempt_one()
    sched.add_request(Request(request_id=1, prompt=list(doc),
                              max_new_tokens=4))
    res = sched.run_to_completion()
    want = oracle(doc, 4)
    for i in (0, 1):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == want, f"request {i} diverged"
    # admission claimed the preempted request's streamed pages
    assert sched._prefix_hit_tokens > 0
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_streaming_registration_defrag_while_shared(lm, lm_params,
                                                    oracle):
    """Compaction moves pages while two mid-prefill requests share the
    streamed document run — block tables and the prefix index follow
    the permutation, streams stay exact."""
    doc = prompts_for(1, rng_seed=45, lo=40, hi=41)[0]
    engine = make_engine(lm, lm_params, prefill_chunk=4)
    sched = ContinuousBatchingScheduler(engine)
    sched.add_request(Request(request_id=0, prompt=list(doc),
                              max_new_tokens=4))
    sched.step()
    sched.step()
    sched.add_request(Request(request_id=1, prompt=list(doc),
                              max_new_tokens=4))
    steps = 0
    while sched.has_work:
        sched.step()
        steps += 1
        if steps % 3 == 0:
            # punch a hole so compaction really moves live pages
            engine.kv.allocate("lo", engine.kv.block_size)
            engine.kv.allocate("hi", engine.kv.block_size)
            engine.kv.free("lo")
            engine.defragment()
            engine.kv.free("hi")
            engine.kv.assert_consistent()
        assert steps < 10_000
    res = sched.results()
    want = oracle(doc, 4)
    for i in (0, 1):
        assert res[i].state.value == "finished", res[i].error
        assert res[i].generated == want, f"request {i} diverged"
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_bucket_ladder_grows_lazily(lm, lm_params, oracle):
    """A prompt past the largest configured prefill bucket no longer
    raises: the ladder grows pow2 rungs (capped at max_len) on first
    use, one compile per new rung, and ``max_bucket`` tracks the
    longest context actually run.  ``max_len_growth=False`` restores
    the hard error."""
    engine = make_engine(lm, lm_params, prefill_buckets=(8,))
    prompt = prompts_for(1, rng_seed=47, lo=20, hi=21)[0]
    assert engine.generate(prompt, 4) == oracle(prompt, 4)
    st = engine.stats()
    assert st["bucket_growths"] >= 2       # 8 -> 16 -> 32
    assert st["max_bucket"] >= len(prompt)
    # grown rungs are cached like configured ones: the same length
    # profile again compiles nothing new
    assert engine.generate(prompt, 4) == oracle(prompt, 4)
    st2 = engine.stats()
    assert st2["prefill_compiles"] == st["prefill_compiles"]
    assert st2["bucket_growths"] == st["bucket_growths"]
    frozen = make_engine(lm, lm_params, prefill_buckets=(8,),
                         max_len_growth=False)
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        frozen.generate(prompt, 4)


def test_scheduler_admits_prompts_past_bucket_ladder(lm, lm_params,
                                                     oracle):
    """Satellite of the ladder growth: admission is bounded by max_len
    alone — a prompt longer than every configured bucket flows through
    chunked prefill (its chunk ladder growing as needed) instead of
    failing the request."""
    engine = make_engine(lm, lm_params, prefill_buckets=(8,),
                         chunk_buckets=(2,), prefill_chunk=4)
    sched = ContinuousBatchingScheduler(engine)
    prompt = prompts_for(1, rng_seed=53, lo=40, hi=41)[0]
    sched.add_request(Request(request_id=0, prompt=list(prompt),
                              max_new_tokens=4))
    res = sched.run_to_completion()
    assert res[0].state.value == "finished", res[0].error
    assert res[0].generated == oracle(prompt, 4)
    st = engine.stats()
    assert st["bucket_growths"] >= 1       # chunk ladder 2 -> 4
    assert engine.max_bucket >= len(prompt)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_sp_sharded_prefill_streams_bit_exact(lm, lm_params):
    """sp>1 runs each prefill slice over a sequence-sharded mesh axis;
    the K/V reassembly is a pure concatenation (all_gather, no
    reduction), so streams are byte-identical to the unsharded engine
    under greedy AND sampled decoding — and decode still runs the
    plain collective-free program."""
    prompts = prompts_for(3, rng_seed=59, lo=17, hi=33)
    sampled = SamplingParams(temperature=0.7, top_k=6, seed=5)

    def run(engine):
        sched = ContinuousBatchingScheduler(engine)
        for i, p in enumerate(prompts):
            sched.add_request(Request(
                request_id=i, prompt=list(p), max_new_tokens=5,
                sampling=SamplingParams() if i % 2 else sampled,
            ))
        res = sched.run_to_completion()
        for i in range(len(prompts)):
            assert res[i].state.value == "finished", res[i].error
        return [res[i].generated for i in range(len(prompts))]

    want = run(make_engine(lm, lm_params, prefill_chunk=8))
    for sp in (2, 4):
        engine = make_engine(lm, lm_params, prefill_chunk=8, sp=sp)
        assert run(engine) == want, f"sp={sp} diverged"
        st = engine.stats()
        assert st["sp"] == sp and st["sp_chunk_compiles"] >= 1
        assert st["decode_compiles"] >= 1
        engine.kv.assert_consistent()
        assert engine.kv.used_blocks == 0
    with pytest.raises(ValueError, match="power of two"):
        make_engine(lm, lm_params, sp=3)
    with pytest.raises(ValueError, match="devices"):
        make_engine(lm, lm_params, sp=16)


def test_stream_counters_flow_to_prometheus(lm, lm_params):
    """serve/prefill_stream_hits and serve/dup_prefill_slices reach the
    Reporter as counters and render through the Prometheus exporter."""
    from chainermn_tpu.observability import Reporter
    from chainermn_tpu.tools.obs import to_prometheus

    doc = prompts_for(1, rng_seed=61, lo=40, hi=41)[0]

    def run(stream):
        rep = Reporter()
        engine = make_engine(lm, lm_params, prefill_chunk=4)
        sched = ContinuousBatchingScheduler(engine, reporter=rep,
                                            stream_prefix=stream)
        sched.add_request(Request(request_id=0, prompt=list(doc),
                                  max_new_tokens=4))
        sched.step()
        sched.step()
        sched.add_request(Request(request_id=1, prompt=list(doc),
                                  max_new_tokens=4))
        sched.run_to_completion()
        return rep.summary()

    s_on = run(True)
    assert s_on["counters"]["serve/prefill_stream_hits"] > 0
    prom = to_prometheus(s_on)
    assert 'serve/prefill_stream_hits' in prom
    # with streaming off the duplicate work the counter exists to
    # expose actually happens — and is counted
    s_off = run(False)
    assert s_off["counters"]["serve/dup_prefill_slices"] > 0
    assert 'serve/dup_prefill_slices' in to_prometheus(s_off)


# ---------------------------------------------------------------------------
# Frontend: backpressure, deadlines, streaming
# ---------------------------------------------------------------------------
def test_frontend_backpressure_queue_full(lm, lm_params):
    fe = ServeFrontend(
        ContinuousBatchingScheduler(make_engine(lm, lm_params)),
        max_queue=2,
    )
    p = prompts_for(1)[0]
    fe.submit(p, 4)
    fe.submit(p, 4)
    with pytest.raises(QueueFull):
        fe.submit(p, 4)
    fe.step()                            # admission drains the queue
    fe.submit(p, 4)                      # now accepted
    fe.run_until_idle()


def test_frontend_timeout_fake_clock(lm, lm_params, oracle):
    now = [0.0]
    fe = ServeFrontend(
        ContinuousBatchingScheduler(make_engine(lm, lm_params)),
        clock=lambda: now[0],
    )
    prompts = prompts_for(2, rng_seed=5)
    h_ok = fe.submit(prompts[0], 4)
    h_to = fe.submit(prompts[1], 40, timeout_s=0.5)
    fe.step()
    now[0] = 1.0                         # h_to's deadline passes
    fe.run_until_idle()
    assert h_ok.status == "finished"
    assert h_ok.tokens == oracle(prompts[0], 4)
    assert h_to.status == "timeout" and h_to.done
    assert h_to.error == "deadline exceeded"
    with pytest.raises(TimeoutError):
        fe.result(h_to)
    # the evicted sequence's pages were reclaimed
    fe.scheduler.engine.kv.assert_consistent()
    assert fe.scheduler.engine.kv.used_blocks == 0
    assert h_ok.latency_s is not None and h_ok.latency_s >= 0


def test_frontend_streaming_matches_final_tokens(lm, lm_params):
    fe = ServeFrontend(
        ContinuousBatchingScheduler(make_engine(lm, lm_params)),
    )
    streamed = {}
    handles = [
        fe.submit(p, 5, on_token=lambda rid, tok:
                  streamed.setdefault(rid, []).append(tok))
        for p in prompts_for(3, rng_seed=9)
    ]
    fe.run_until_idle()
    for h in handles:
        assert h.status == "finished"
        assert streamed[h.request_id] == h.tokens
        assert len(h.tokens) == 5


def test_frontend_temperature_stream_independent_of_batching(lm,
                                                             lm_params):
    """Seeded temperature sampling: the stream must not depend on what
    else shares the batch — run the same request alone and among
    neighbors."""
    sp = SamplingParams(temperature=0.7, top_k=8, seed=42)
    prompt = prompts_for(1, rng_seed=13)[0]

    def run(extra):
        fe = ServeFrontend(
            ContinuousBatchingScheduler(make_engine(lm, lm_params)),
        )
        h = fe.submit(prompt, 6, sampling=sp)
        for q in extra:
            fe.submit(q, 6, sampling=SamplingParams(temperature=1.3,
                                                    seed=1))
        fe.run_until_idle()
        return h.tokens

    alone = run([])
    crowded = run(prompts_for(3, rng_seed=17))
    assert alone == crowded


# ---------------------------------------------------------------------------
# Collective-free decode: pinned HLO census
# ---------------------------------------------------------------------------
def _decode_census() -> dict:
    from chainermn_tpu.analysis.fixtures import fixture_serving_decode
    from chainermn_tpu.observability import audit_fn

    t = fixture_serving_decode()
    audit = audit_fn(t["fn"], *t["args"])
    return {
        "target": t["target"],
        "hlo_collectives": audit.census(),
        "reduction_collectives": audit.reduction_collectives(),
        "per_axis_operand_bytes": dict(
            sorted(audit.bytes_per_axis.items())
        ),
    }


def test_decode_step_collective_census_matches_golden():
    with open(CENSUS_GOLDEN_PATH) as f:
        golden = json.load(f)
    current = _decode_census()
    assert current == golden, (
        "decode-step collective census drifted — a psum crept into the "
        "per-sequence data plane?  If intended (it should not be), "
        f"regenerate with: python {__file__} --regen"
    )
    # the golden itself must pin ZERO collectives (guards a bad regen)
    assert golden["reduction_collectives"] == 0
    assert all(v == 0 for v in golden["hlo_collectives"].values())
    assert golden["per_axis_operand_bytes"] == {}


def _sp_prefill_census() -> dict:
    from chainermn_tpu.analysis.fixtures import fixture_sharded_prefill
    from chainermn_tpu.observability import audit_fn

    t = fixture_sharded_prefill()
    audit = audit_fn(t["fn"], *t["args"])
    return {
        "target": t["target"],
        "hlo_collectives": audit.census(),
        "reduction_collectives": audit.reduction_collectives(),
    }


def test_sp_prefill_collective_census_matches_golden():
    """The sequence-sharded prefill program's collective budget is
    pinned: exactly the per-layer K/V all-gathers (pure concatenation),
    ZERO reduction collectives — the shape of the bit-exactness
    argument, enforced on the compiled HLO."""
    with open(SP_CENSUS_GOLDEN_PATH) as f:
        golden = json.load(f)
    current = _sp_prefill_census()
    assert current == golden, (
        "sp-prefill collective census drifted — if a reduction crept "
        "in, the serving plane's bit-exactness contract is broken; if "
        "the change is an intended gather restructure, regenerate "
        f"with: python {__file__} --regen"
    )
    # the golden itself must pin gathers-only (guards a bad regen)
    assert golden["reduction_collectives"] == 0
    assert golden["hlo_collectives"]["all_gather"] > 0
    assert all(v == 0 for k, v in golden["hlo_collectives"].items()
               if k != "all_gather")


def _tp_decode_census() -> dict:
    """Census of the tensor-parallel decode step's COMPILED HLO.

    The ``tp`` plan shards by NamedSharding annotation, so its
    collectives exist only after GSPMD partitioning — ``audit_fn``'s
    jaxpr view sees zero.  The per-layer count is pinned by differencing
    a 2-layer program against the 1-layer one, and the sampling tail
    (argmax over the replicated fp32 logits) is audited separately: the
    leader samples locally, so the tail must stay collective-free."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from chainermn_tpu.analysis.fixtures import fixture_tp_decode
    from chainermn_tpu.observability import audit_compiled

    assert len(jax.devices()) >= 2, "TP census needs >= 2 devices"
    audits = {}
    for n_layers in (1, 2):
        t = fixture_tp_decode(n_layers=n_layers)
        audits[n_layers] = audit_compiled(t["fn"], *t["args"])
    c1, c2 = audits[1].census(), audits[2].census()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    logits = jax.ShapeDtypeStruct(
        (2, VOCAB), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec()),
    )
    tail = audit_compiled(
        jax.jit(lambda x: jnp.argmax(x, axis=-1).astype(jnp.int32)),
        logits,
    )
    return {
        "target": "tp_decode",
        "hlo_collectives": c1,
        "per_layer_collectives": {k: c2[k] - c1[k] for k in sorted(c1)},
        "reduction_collectives": audits[1].reduction_collectives(),
        "sampling_tail_collectives": tail.census(),
        "sampling_tail_reduction_collectives": tail.reduction_collectives(),
    }


def test_tp_decode_collective_census_matches_golden():
    """The TP decode step's wire cost is pinned at the compiled-HLO
    level: exactly two all-reduces per layer (attention out-projection
    and FFN down-projection — the canonical Megatron-style partition),
    no gathers or permutes, and a collective-free sampling tail.  Any
    drift means GSPMD stopped partitioning the decode step the way the
    shard-group design assumes."""
    with open(TP_CENSUS_GOLDEN_PATH) as f:
        golden = json.load(f)
    current = _tp_decode_census()
    assert current == golden, (
        "tp-decode collective census drifted — the GSPMD partition of "
        "the shard-group decode step changed.  If the new lowering is "
        "intended (check the per-layer count stayed O(1)), regenerate "
        f"with: python {__file__} --regen"
    )
    # the golden itself must pin the Megatron shape (guards a bad regen)
    per_layer = golden["per_layer_collectives"]
    assert per_layer["psum"] == 2
    assert all(v == 0 for k, v in per_layer.items() if k != "psum")
    assert golden["reduction_collectives"] > 0
    # sampling must never pay for the tensor parallelism
    assert golden["sampling_tail_reduction_collectives"] == 0
    assert all(
        v == 0 for v in golden["sampling_tail_collectives"].values()
    )


# ---------------------------------------------------------------------------
# Subprocess smoke: the example
# ---------------------------------------------------------------------------
def test_serve_lm_example_smoke():
    from conftest import subprocess_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "serve_lm", "serve_lm.py"),
         "--train-steps", "2", "--requests", "3", "--new-tokens", "4",
         "--n-blocks", "32", "--d-model", "16", "--d-ff", "32"],
        capture_output=True, text=True, timeout=420,
        env=subprocess_env(n_devices=1), cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "req 0:" in proc.stdout and "gauges:" in proc.stdout


# ---------------------------------------------------------------------------
# Soak (auto-marked slow by conftest): eviction + defrag churn
# ---------------------------------------------------------------------------
def test_serving_soak_eviction_defrag_churn(lm, lm_params, oracle):
    engine = make_engine(lm, lm_params, n_blocks=12, max_batch=3)
    sched = ContinuousBatchingScheduler(engine, watermark_blocks=0)
    fe = ServeFrontend(sched, max_queue=64)
    prompts = prompts_for(24, rng_seed=23, lo=3, hi=15)
    handles = [fe.submit(p, 5) for p in prompts]
    steps = 0
    while sched.has_work:
        fe.step()
        steps += 1
        if steps % 7 == 0:
            engine.defragment()          # churn the page layout
            engine.kv.assert_consistent()
        assert steps < 10_000
    for h, p in zip(handles, prompts):
        assert h.status == "finished", h.error
        assert h.tokens == oracle(p, 5)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0


def test_serving_soak_shared_prefix_spec_churn(lm, lm_params, oracle):
    """Soak (auto-marked slow): duplicate-prefix traffic + speculative
    decoding through a pool small enough to force cached-page eviction,
    CoW splits, preemption and defrag churn at once — every stream
    still bit-exact, no page leaked or double-freed."""
    engine = make_engine(lm, lm_params, n_blocks=14, max_batch=3)
    sched = ContinuousBatchingScheduler(engine, watermark_blocks=0,
                                        spec_tokens=3)
    fe = ServeFrontend(sched, max_queue=64)
    rng = np.random.default_rng(29)
    shared = [int(t) for t in rng.integers(0, VOCAB, size=8)]
    prompts = []
    for i, p in enumerate(prompts_for(18, rng_seed=31, lo=3, hi=9)):
        prompts.append(shared + p if i % 2 == 0 else p)
    handles = [fe.submit(p, 5) for p in prompts]
    steps = 0
    while sched.has_work:
        fe.step()
        steps += 1
        if steps % 7 == 0:
            engine.defragment()
            engine.kv.assert_consistent()
        assert steps < 10_000
    for h, p in zip(handles, prompts):
        assert h.status == "finished", h.error
        assert h.tokens == oracle(p, 5)
    engine.kv.assert_consistent()
    assert engine.kv.used_blocks == 0
    assert sched._prefix_hit_tokens > 0  # sharing really was in play


# ---------------------------------------------------------------------------
# --regen
# ---------------------------------------------------------------------------
def _regen():
    # Outside pytest, conftest's device-count flag hasn't run; set it
    # before the first backend touch or the tp mesh degenerates to one
    # device and the TP census regenerates as all-zero.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        )
    jax.config.update("jax_platforms", "cpu")
    os.makedirs(os.path.dirname(CENSUS_GOLDEN_PATH), exist_ok=True)
    for path, census in ((CENSUS_GOLDEN_PATH, _decode_census()),
                         (SP_CENSUS_GOLDEN_PATH, _sp_prefill_census()),
                         (TP_CENSUS_GOLDEN_PATH, _tp_decode_census())):
        with open(path, "w") as f:
            json.dump(census, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="regenerate the decode-census golden")
    if not ap.parse_args().regen:
        ap.error("run under pytest, or pass --regen to regenerate")
    _regen()


# ---------------------------------------------------------------------------
# Per-tenant KV page-seconds: exact residency integrals
# ---------------------------------------------------------------------------


def test_kv_page_seconds_conservation():
    """With an injectable clock, per-tenant residency integrals are
    exact, shared prefix pages bill their FIRST owner only, untenanted
    holdings stay in the pool integral, and the sum of all owner
    buckets equals the pool integral through alloc / extend / share /
    truncate / free / defragment."""
    t = [0.0]
    kv = PagedKVCache(n_blocks=16, block_size=4, clock=lambda: t[0])

    kv.allocate("a", 8, tenant="ta")        # 2 pages, ta
    t[0] = 5.0                              # ta: 2pg x 5s = 10
    kv.allocate("b", 4, tenant="tb")        # 1 page, tb
    t[0] = 7.0                              # ta +4, tb +2
    kv.extend("a", 12)                      # ta now holds 3 pages
    t[0] = 10.0                             # ta +9, tb +3
    ps = kv.page_seconds()
    assert ps == {"ta": pytest.approx(23.0), "tb": pytest.approx(5.0)}
    assert kv.pool_page_seconds() == pytest.approx(28.0)

    # tb shares ta's registered prefix: the 3 shared pages keep
    # accruing to ta (first owner), only tb's fresh page bills tb
    toks = list(range(12))
    kv.register_prefix("a", toks)
    shared = kv.match_prefix(toks)
    assert len(shared) == 3
    kv.allocate("c", 14, prefix_pages=shared, tenant="tb")
    t[0] = 12.0                             # ta +6, tb +2+2
    ps = kv.page_seconds()
    assert ps == {"ta": pytest.approx(29.0), "tb": pytest.approx(9.0)}
    assert kv.pool_page_seconds() == pytest.approx(sum(ps.values()))
    kv.assert_consistent()

    # untenanted holdings: excluded from the tenant map, in the pool
    kv.allocate("d", 4)                     # 1 page, owner None
    t[0] = 14.0                             # ta +6, tb +4, None +2
    ps = kv.page_seconds()
    assert set(ps) == {"ta", "tb"}
    assert kv.pool_page_seconds() == pytest.approx(sum(ps.values()) + 2.0)

    # freeing the first owner does NOT re-bill still-shared pages: a's
    # pages stay held under ta while c references them
    kv.free("a")
    kv.free("d")
    t[0] = 16.0                             # ta +6, tb +4
    ps = kv.page_seconds()
    assert ps == {"ta": pytest.approx(41.0), "tb": pytest.approx(17.0)}
    assert kv.pool_page_seconds() == pytest.approx(sum(ps.values()) + 2.0)

    # owners survive page renumbering
    kv.defragment()
    kv.assert_consistent()
    t[0] = 18.0                             # ta +6, tb +4
    kv.truncate("c", 12)                    # releases tb's fresh page
    t[0] = 20.0                             # ta +6, tb +2 (b only)
    ps = kv.page_seconds()
    assert ps == {"ta": pytest.approx(53.0), "tb": pytest.approx(23.0)}

    # all sequences gone: the meter stops (cached refcount-0 prefix
    # pages are reclaimable capacity, not tenant residency)
    kv.free("b")
    kv.free("c")
    t[0] = 100.0
    assert kv.page_seconds() == {"ta": pytest.approx(53.0),
                                 "tb": pytest.approx(23.0)}
    assert kv.pool_page_seconds() == pytest.approx(53.0 + 23.0 + 2.0)
    kv.assert_consistent()


def test_kv_page_seconds_scheduler_attribution(lm, lm_params):
    """Request.tenant flows scheduler -> kv.allocate: the scheduler's
    end-of-step gauges publish per-tenant page-seconds that sum to the
    pool integral when every request is tenanted."""
    from chainermn_tpu.observability.reporter import Reporter

    reporter = Reporter()
    engine = make_engine(lm, lm_params)
    sched = ContinuousBatchingScheduler(engine, reporter=reporter)
    sched.add_request(Request(request_id=0, prompt=[1, 2, 3, 4, 5],
                              max_new_tokens=4, tenant="ta"))
    sched.add_request(Request(request_id=1, prompt=[6, 7, 8],
                              max_new_tokens=4, tenant="tb"))
    sched.run_to_completion()
    ps = engine.kv.page_seconds()
    assert set(ps) == {"ta", "tb"}
    assert sum(ps.values()) == pytest.approx(
        engine.kv.pool_page_seconds())
    g = reporter.summary()["gauges"]
    assert g["tenant/ta/kv_page_seconds"]["value"] == pytest.approx(
        ps["ta"])
    # tokens emitted under each tenant were counted as they streamed
    c = reporter.summary()["counters"]
    assert c["tenant/ta/tokens_out"] == 4
    assert c["tenant/tb/tokens_out"] == 4
    engine.kv.assert_consistent()
