"""Telemetry subsystem: Reporter semantics and aggregation, StepRecorder
file contract (atomic append / rotation / crash recovery), hlo_audit
census parity with the communicator bandwidth claims, span fan-out, and
the ``tools.obs`` CLI (JSON summary + Prometheus textfile).

Cross-PROCESS Reporter aggregation runs in tests/_mp_worker.py (the real
multi-process harness); here the communicators are single-process, where
``aggregate`` takes the trivial object-plane path.
"""

import json
import math
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.observability import (
    AnomalyDetector,
    MetricsExporter,
    Reporter,
    StepRecorder,
    audit_allreduce,
    audit_fn,
    get_reporter,
    merge_summaries,
    read_records,
    recover,
    report,
    scope,
    span,
    telemetry_active,
)
from chainermn_tpu.observability.reporter import _bucket
from chainermn_tpu.tools.obs import metric_diff, summarize, to_prometheus


# ---------------------------------------------------------------------------
# Reporter
# ---------------------------------------------------------------------------

def test_reporter_scalar_semantics():
    r = Reporter()
    for v in (3.0, 1.0, 2.0):
        r.observe("loss", v)
    s = r.summary()["scalars"]["loss"]
    assert s["count"] == 3
    assert s["sum"] == 6.0
    assert s["min"] == 1.0 and s["max"] == 3.0
    assert s["last"] == 2.0
    assert s["mean"] == 2.0


def test_reporter_counters_and_histograms():
    r = Reporter()
    r.count("steps")
    r.count("steps", 4)
    r.histogram_observe("lat", 0.75)   # ceil(log2(0.75)) = 0
    r.histogram_observe("lat", 3.0)    # ceil(log2(3)) = 2
    r.histogram_observe("lat", 0.0)    # non-positive -> lowest bucket
    s = r.summary()
    assert s["counters"]["steps"] == 5
    assert s["histograms"]["lat"] == {"0": 1, "2": 1, "-30": 1}


def test_bucket_clamps():
    assert _bucket(-1.0) == -30
    assert _bucket(2.0**100) == 63
    assert _bucket(1.0) == 0
    assert _bucket(2.0) == 1


def test_merge_summaries_weighted_mean():
    a, b = Reporter(), Reporter()
    a.observe("loss", 1.0)
    a.observe("loss", 3.0)
    b.observe("loss", 5.0)
    b.count("steps", 2)
    a.count("steps", 1)
    m = merge_summaries([a.summary(), b.summary()])
    assert m["scalars"]["loss"]["count"] == 3
    assert m["scalars"]["loss"]["mean"] == pytest.approx(3.0)
    assert m["scalars"]["loss"]["min"] == 1.0
    assert m["scalars"]["loss"]["max"] == 5.0
    assert m["counters"]["steps"] == 3


def test_aggregate_single_process_trivial_path():
    import chainermn_tpu

    comm = chainermn_tpu.create_communicator("naive")
    r = Reporter()
    r.observe("x", 2.0)
    agg = r.aggregate(comm)
    assert agg["scalars"]["x"]["mean"] == 2.0
    # reset=True clears after the merge
    r.aggregate(comm, reset=True)
    assert r.summary()["scalars"] == {}


def test_reporter_scope_stack():
    assert get_reporter() is None
    assert not telemetry_active()
    r = Reporter()
    with scope(r):
        assert get_reporter() is r
        assert telemetry_active()
        report({"a": 1.0})
    assert get_reporter() is None
    report({"a": 1.0})  # no-op, must not raise
    assert r.summary()["scalars"]["a"]["count"] == 1


# ---------------------------------------------------------------------------
# StepRecorder / JSONL file contract
# ---------------------------------------------------------------------------

def _mk_recorder(tmp_path, **kw):
    kw.setdefault("capture_compile_events", False)
    return StepRecorder(str(tmp_path / "steps.jsonl"), **kw)


def test_recorder_rows_and_step_derivations(tmp_path):
    clock = iter([10.0, 10.5, 11.5])
    rec = _mk_recorder(tmp_path, mem_every=0, clock=lambda: next(clock))
    with rec:
        rec.step(step=0, items=64, loss=np.float32(1.5))
        r1 = rec.step(step=1, items=64, loss=jnp.float32(0.5))
        r2 = rec.step(step=2, items=128)
    rows = read_records(rec.path)
    assert [r["step"] for r in rows] == [0, 1, 2]
    assert "dt" not in rows[0]  # no previous step to diff against
    assert r1["dt"] == pytest.approx(0.5)
    assert r1["per_sec"] == pytest.approx(128.0)
    assert r2["dt"] == pytest.approx(1.0)
    # numpy/jax scalars serialized as plain floats
    assert isinstance(rows[0]["loss"], float) and rows[0]["loss"] == 1.5
    assert rows[1]["loss"] == 0.5


def test_recorder_rotation_bounds_files(tmp_path):
    rec = _mk_recorder(tmp_path, rotate_bytes=400, max_files=3)
    with rec:
        for i in range(60):
            rec.record("e", i=i, pad="x" * 40)
    segs = sorted(
        p for p in os.listdir(tmp_path) if p.startswith("steps.jsonl")
    )
    assert "steps.jsonl" in segs
    assert f"steps.jsonl.{rec.max_files - 1}" in segs
    assert len(segs) <= rec.max_files
    rows = read_records(rec.path)
    # Retained rows are the TAIL of the stream, in order.
    idx = [r["i"] for r in rows]
    assert idx == sorted(idx)
    assert idx[-1] == 59
    # Oldest→newest ordering across segments: the rotated segment's rows
    # precede the live file's.
    live = read_records(rec.path, include_rotated=False)
    assert live[-1]["i"] == 59
    assert len(live) < len(rows)


def test_recorder_crash_recovery(tmp_path):
    rec = _mk_recorder(tmp_path)
    with rec:
        rec.record("a", i=0)
        rec.record("b", i=1)
    # Simulate a SIGKILL mid-write: a trailing unterminated partial line.
    with open(rec.path, "a") as f:
        f.write('{"event": "c", "i": 2')
    rows = read_records(rec.path)  # reader skips the torn tail
    assert [r["event"] for r in rows] == ["a", "b"]
    with pytest.raises(ValueError):
        read_records(rec.path, strict=True)
    assert recover(rec.path) == 2  # truncates in place, counts valid rows
    assert read_records(rec.path, strict=True) == rows
    # A resumed recorder appends to the recovered file cleanly.
    rec2 = _mk_recorder(tmp_path)
    with rec2:
        rec2.record("d", i=3)
    assert [r["event"] for r in read_records(rec.path)] == ["a", "b", "d"]


def test_recorder_missing_file_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        read_records(str(tmp_path / "nope.jsonl"))


def test_span_feeds_reporter_and_recorder(tmp_path):
    r = Reporter()
    rec = _mk_recorder(tmp_path)
    with scope(r), rec:
        with span("work"):
            pass
        row = rec.step(step=0)
    assert r.summary()["scalars"]["span/work"]["count"] == 1
    assert "work" in row["spans"]
    assert row["spans"]["work"] >= 0.0


@pytest.mark.slow
def test_recorder_rotation_soak(tmp_path):
    """Soak: tens of thousands of rows through a small rotation window —
    segment count stays bounded and the retained tail stays parseable."""
    rec = _mk_recorder(tmp_path, rotate_bytes=4096, max_files=4)
    with rec:
        for i in range(30_000):
            rec.record("e", i=i)
    segs = [p for p in os.listdir(tmp_path) if p.startswith("steps.jsonl")]
    assert len(segs) <= 4
    rows = read_records(rec.path)
    assert rows[-1]["i"] == 29_999
    idx = [r["i"] for r in rows]
    assert idx == sorted(idx)


# ---------------------------------------------------------------------------
# hlo_audit
# ---------------------------------------------------------------------------

def _comm(name):
    import chainermn_tpu

    return chainermn_tpu.create_communicator(name)


def test_audit_allreduce_flat_census(devices8):
    audit = audit_allreduce(_comm("flat"), 1 << 20)
    c = audit.census()
    assert set(c) == {"psum", "reduce_scatter", "all_gather", "ppermute"}
    assert c["psum"] == 1 and c["reduce_scatter"] == 0


def test_audit_two_dimensional_inter_savings(devices8):
    """``allreduce_bench.py``'s headline static claim, via the library: the 2D
    backend's inter-axis operand bytes are flat's divided by intra."""
    nbytes = 1 << 20
    flat = audit_allreduce(_comm("flat"), nbytes)
    td = audit_allreduce(_comm("two_dimensional"), nbytes)
    intra = _comm("flat").intra_size
    assert flat.bytes_per_axis["inter"] == nbytes
    assert td.bytes_per_axis["inter"] * intra == nbytes
    assert td.counts.get("reduce_scatter", 0) >= 1
    assert td.counts.get("all_gather", 0) >= 1


def test_audit_fn_on_jitted_step(devices8):
    """audit_fn traces through jit and charges bytes to mesh axes."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    comm = _comm("flat")

    def body(x):
        return lax.psum(x, comm.axes)

    fn = jax.jit(comm.shard_map(
        body, in_specs=(comm._world_spec,), out_specs=comm._world_spec
    ))
    x = jnp.ones((8, 256), jnp.float32)
    audit = audit_fn(fn, x)
    assert audit.counts.get("psum") == 1
    # per-device operand: (1, 256) float32 = 1 KiB charged to both axes
    assert audit.bytes_per_axis["inter"] == 1024
    assert audit.bytes_per_axis["intra"] == 1024
    summ = audit.summary()
    assert summ["counts"]["psum"] == 1


def test_audit_fn_no_collectives():
    import jax

    audit = audit_fn(jax.jit(lambda x: x * 2), jnp.ones((4,)))
    assert audit.counts == {}
    assert audit.census()["psum"] == 0


def test_bench_bytes_per_leg_parity(devices8):
    """The allreduce_bench wrappers and the library agree exactly — one
    source of truth for ``allreduce_static_bytes_per_leg``."""
    bench_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks",
    )
    sys.path.insert(0, bench_dir)
    try:
        from allreduce_bench import bytes_per_leg, collective_profile
    finally:
        sys.path.remove(bench_dir)
    comm = _comm("two_dimensional")
    nbytes = 1 << 20
    audit = audit_allreduce(comm, nbytes, np.float32)
    assert bytes_per_leg(comm, nbytes, np.float32) == audit.bytes_per_axis
    assert collective_profile(comm, nbytes, np.float32) == audit.census()


# ---------------------------------------------------------------------------
# tools.obs CLI
# ---------------------------------------------------------------------------

def _write_rows(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


_CLI_ROWS = [
    {"event": "start", "rank": 0, "t": 0.0},
    {"event": "hlo_audit", "rank": 0, "t": 0.0,
     "counts": {"psum": 2}, "bytes_per_axis": {"inter": 1024, "intra": 2048}},
    {"event": "step", "rank": 0, "t": 1.0, "step": 0, "items": 32,
     "loss": 4.0, "spans": {"fwd": 0.25}},
    {"event": "step", "rank": 0, "t": 2.0, "step": 1, "items": 32,
     "loss": 2.0, "dt": 0.5, "per_sec": 64.0, "spans": {"fwd": 0.25}},
    {"event": "step", "rank": 0, "t": 3.0, "step": 2, "items": 32,
     "loss": 1.0, "dt": 0.5, "per_sec": 64.0},
    {"event": "compile", "rank": 0, "t": 0.5, "name": "x", "secs": 2.0},
]


def test_summarize_core_numbers(tmp_path):
    p = tmp_path / "log.jsonl"
    _write_rows(p, _CLI_ROWS)
    s = summarize(read_records(str(p)))
    assert s["steps"]["count"] == 3
    assert s["steps"]["wall_s"] == pytest.approx(1.0)
    assert s["steps"]["per_sec"] == pytest.approx(2.0)
    assert s["loss"] == {
        "first": 4.0, "last": 1.0, "min": 1.0,
        "curve": [[0, 4.0], [1, 2.0], [2, 1.0]],
    }
    assert s["spans"]["fwd"] == {"total_s": 0.5, "count": 2}
    assert s["compile"] == {"count": 1, "total_s": 2.0}
    assert s["collectives"]["counts"] == {"psum": 2}


def test_summarize_rank_aggregation_matches_single_process(tmp_path):
    """Two rank logs carrying the same per-step global loss summarize to
    the same loss values as one single-process log — the acceptance
    contract for multi-host step logs."""
    single = [
        {"event": "step", "rank": 0, "step": i, "loss": float(10 - i),
         "dt": 0.5, "items": 8}
        for i in range(4)
    ]
    r0 = tmp_path / "r0.jsonl"
    r1 = tmp_path / "r1.jsonl"
    mono = tmp_path / "mono.jsonl"
    _write_rows(mono, single)
    _write_rows(r0, single)
    _write_rows(r1, [dict(r, rank=1) for r in single])
    s_mono = summarize(read_records(str(mono)))
    s_multi = summarize(
        read_records(str(r0)) + read_records(str(r1))
    )
    assert s_multi["loss"] == s_mono["loss"]
    assert s_multi["steps"]["count"] == s_mono["steps"]["count"]
    assert s_multi["steps"]["wall_s"] == pytest.approx(
        s_mono["steps"]["wall_s"]
    )
    assert s_multi["steps"]["items_per_sec"] == pytest.approx(
        s_mono["steps"]["items_per_sec"]
    )
    assert s_multi["ranks"] == [0, 1]


def test_loss_curve_downsampling(tmp_path):
    rows = [
        {"event": "step", "rank": 0, "step": i, "loss": float(i), "dt": 1.0}
        for i in range(100)
    ]
    p = tmp_path / "log.jsonl"
    _write_rows(p, rows)
    s = summarize(read_records(str(p)), curve_points=16)
    curve = s["loss"]["curve"]
    assert len(curve) <= 17  # 16 strided points + appended last
    assert curve[0] == [0, 0.0]
    assert curve[-1] == [99, 99.0]


PROM_GOLDEN = """\
# HELP t_steps_total Training steps recorded
# TYPE t_steps_total counter
t_steps_total 3
# HELP t_step_seconds_sum Sum of host-side step durations
# TYPE t_step_seconds_sum counter
t_step_seconds_sum 1
# HELP t_step_seconds_mean Mean step duration
# TYPE t_step_seconds_mean gauge
t_step_seconds_mean 0.5
# HELP t_steps_per_second Steps per second
# TYPE t_steps_per_second gauge
t_steps_per_second 2
# HELP t_items_per_second Items (tokens or images) per second
# TYPE t_items_per_second gauge
t_items_per_second 96
# HELP t_loss_last Last recorded loss
# TYPE t_loss_last gauge
t_loss_last 1
# HELP t_loss_min Minimum recorded loss
# TYPE t_loss_min gauge
t_loss_min 1
# HELP t_compile_events_total jax.monitoring compile events
# TYPE t_compile_events_total counter
t_compile_events_total 1
# HELP t_compile_seconds_total Total compile seconds
# TYPE t_compile_seconds_total counter
t_compile_seconds_total 2
# HELP t_span_seconds_total Host-side span durations
# TYPE t_span_seconds_total counter
t_span_seconds_total{span="fwd"} 0.5
# HELP t_collective_ops_total Collective primitives in the audited step program
# TYPE t_collective_ops_total counter
t_collective_ops_total{primitive="psum"} 2
# HELP t_collective_operand_bytes Per-device collective operand bytes per mesh axis
# TYPE t_collective_operand_bytes gauge
t_collective_operand_bytes{axis="inter"} 1024
t_collective_operand_bytes{axis="intra"} 2048
"""


def test_prometheus_golden(tmp_path):
    p = tmp_path / "log.jsonl"
    _write_rows(p, _CLI_ROWS)
    text = to_prometheus(summarize(read_records(str(p))), prefix="t")
    assert text == PROM_GOLDEN


def test_obs_cli_subprocess(tmp_path):
    """The installed entry point end-to-end: summarize prints one JSON
    object; prom writes the textfile."""
    p = tmp_path / "log.jsonl"
    _write_rows(p, _CLI_ROWS)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.obs", "summarize",
         str(p)],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr
    s = json.loads(out.stdout)
    assert s["steps"]["count"] == 3
    prom = tmp_path / "log.prom"
    out = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.obs", "prom", str(p),
         "-o", str(prom), "--prefix", "t"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert prom.read_text() == PROM_GOLDEN


def test_compilation_cache_dir(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set no directory is set in code;
    without it the cache is the checkout's fixed .jax_cache."""
    import jax

    from chainermn_tpu.utils.profiling import setup_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        target = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        assert setup_compilation_cache() == target
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert setup_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", min_secs)


def test_instrumented_step_counts_calls(devices8):
    import chainermn_tpu
    import optax

    comm = _comm("flat")
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    params = {"w": jnp.ones((8, 2))}
    state = opt.init(params)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    step = opt.make_train_step(loss_fn)
    batch = (jnp.ones((16, 8)), jnp.zeros((16, 2)))
    # telemetry off: plain call, no reporter interaction
    params, state, _ = step(params, state, batch)
    r = Reporter()
    with scope(r):
        params, state, _ = step(params, state, batch)
        params, state, _ = step(params, state, batch)
    s = r.summary()
    assert s["counters"]["train_step_calls"] == 2
    assert s["scalars"]["span/train_step"]["count"] == 2


def test_evaluator_reports_through_reporter(devices8, tmp_path):
    import chainermn_tpu
    from chainermn_tpu.extensions import Evaluator

    comm = _comm("flat")

    def metric_fn(params, batch):
        (x,) = batch
        return {"val/m": jnp.mean(x * params)}

    ev = Evaluator(metric_fn, comm)
    r = Reporter()
    rec = _mk_recorder(tmp_path)
    with scope(r), rec:
        out = ev.evaluate(jnp.float32(2.0), [(jnp.ones((8, 4)),)])
    assert out["val/m"] == pytest.approx(2.0)
    s = r.summary()
    assert s["scalars"]["eval/val/m"]["last"] == pytest.approx(2.0)
    assert s["scalars"]["span/evaluate"]["count"] == 1
    rows = [x for x in read_records(rec.path) if x["event"] == "eval"]
    assert rows and rows[0]["metrics"]["val/m"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# Fleet-live plane: scrape endpoint, native histograms, stale-series
# hygiene, anomaly detection, and the ``obs diff`` regression gate
# ---------------------------------------------------------------------------


def _scrape(url):
    import urllib.request

    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        return resp.read().decode()


def test_native_histogram_exposition_cumulative():
    """Pow2 histograms render as a real Prometheus histogram: cumulative
    ``le`` buckets at exact 2^b upper bounds, +Inf, _sum, _count."""
    r = Reporter()
    for v in (0.75, 3.0, 3.5, 0.0):  # buckets 0, 2, 2, -30
        r.histogram_observe("trace/decode", v)
    text = to_prometheus(r.summary())
    assert "# TYPE chainermn_tpu_histogram histogram" in text
    rows = [ln for ln in text.splitlines()
            if ln.startswith("chainermn_tpu_histogram")]
    import re

    cums = [int(m.group(1)) for m in (
        re.search(r"} (\d+)$", ln) for ln in rows if "_bucket" in ln
    )]
    assert cums == [1, 2, 4, 4]  # le=2^-30, le=1, le=4, le=+Inf
    bounds = re.findall(r'le="([^"]+)"', "\n".join(rows))
    assert bounds[1:] == ["1", "4", "+Inf"]
    assert float(bounds[0]) == pytest.approx(2.0 ** -30)
    (sum_row,) = [ln for ln in rows if "_sum" in ln]
    assert float(sum_row.rsplit(" ", 1)[1]) == pytest.approx(9.0, rel=1e-6)
    (count_row,) = [ln for ln in rows if "_count" in ln]
    assert count_row.endswith(" 4")


def test_native_histogram_replica_label_split():
    r = Reporter()
    r.histogram_observe("trace/decode/replica/3", 2.0)
    text = to_prometheus(r.summary())
    assert 'name="trace/decode",replica="3"' in text
    assert "trace/decode/replica/3" not in text


def test_metrics_exporter_scrape_counters_move():
    """Two scrapes of a live endpoint observe the counter move — the
    pull-model smoke test."""
    r = Reporter()
    r.count("serving/steps", 3)
    exp = MetricsExporter(r, port=0)
    port = exp.start()
    try:
        assert exp.url == f"http://127.0.0.1:{port}/metrics"
        assert exp.start() == port  # idempotent
        t1 = _scrape(exp.url)
        assert 'chainermn_tpu_counter_total{name="serving/steps"} 3' in t1
        r.count("serving/steps", 2)
        r.gauge("serving/queue_depth", 4)
        t2 = _scrape(exp.url)
        assert 'chainermn_tpu_counter_total{name="serving/steps"} 5' in t2
        assert 'chainermn_tpu_gauge{name="serving/queue_depth"} 4' in t2
    finally:
        exp.stop()
    exp.stop()  # idempotent after shutdown


def test_metrics_exporter_callable_source_and_404():
    """A zero-arg callable works as the source (the router's fleet-view
    hook); non-metrics paths 404; bad sources are rejected."""
    import urllib.error
    import urllib.request

    calls = []

    def source():
        calls.append(1)
        return {"counters": {"fleet/scrapes": len(calls)}}

    with MetricsExporter(source, port=0) as exp:
        assert 'name="fleet/scrapes"} 1' in _scrape(exp.url)
        assert 'name="fleet/scrapes"} 2' in _scrape(exp.url)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                exp.url.replace("/metrics", "/nope"), timeout=10
            )
        assert ei.value.code == 404
    with pytest.raises(TypeError):
        MetricsExporter(42)


def test_forget_replica_drops_only_that_replicas_series():
    """The stale-series fix: a dead replica's series leave every table,
    without touching a replica whose id merely shares a prefix."""
    r = Reporter()
    r.gauge("serving/running/replica/2", 3)
    r.gauge("serving/running/replica/12", 1)
    r.count("serving/steps", 7)
    r.count("trace/stage/replica/2/decode", 1)  # id as a path segment
    r.histogram_observe("trace/decode/replica/2", 1.0)
    r.observe("lat/replica/2", 0.5)
    assert r.forget_replica(2) == 4
    s = r.summary()
    assert "serving/running/replica/2" not in s["gauges"]
    assert s["gauges"]["serving/running/replica/12"]["value"] == 1
    assert s["counters"] == {"serving/steps": 7}
    assert s["histograms"] == {}
    assert "lat/replica/2" not in s["scalars"]
    assert r.forget_replica(2) == 0


def _fleet_summary(tokens, hist=None):
    return {
        "counters": {"serving/tokens": tokens},
        "histograms": {
            "trace/decode": {str(b): c for b, c in (hist or {}).items()}
        },
    }


def test_anomaly_latency_regression_edge_counted_once():
    """Median of NEW observations rising past regression_factor x the
    baseline median alarms; the counter records the onset once while
    the gauge tracks the level."""
    rep = Reporter()
    det = AnomalyDetector(reporter=rep, window=2, baseline=8,
                          min_samples=2, regression_factor=2.0)
    hist = {0: 0}
    for i in range(6):  # healthy: one new bucket-0 obs (median 1.0)
        hist[0] += 1
        st = det.update(_fleet_summary(0, hist), now=float(i))
        assert not st["latency_regression"]
    assert not det.alarming()
    hist[3] = 0
    for i in range(6, 8):  # regression: new obs in bucket 3 (8x)
        hist[3] += 1
        st = det.update(_fleet_summary(0, hist), now=float(i))
    assert st["latency_regression"] and det.alarming()
    assert st["latency_ratio"] == pytest.approx(8.0)
    s = rep.summary()
    assert s["counters"]["anomaly/latency_regression"] == 1
    assert s["gauges"]["anomaly/latency_regression"]["value"] == 1.0
    # still alarming next tick: level stays, onset is not re-counted
    hist[3] += 1
    det.update(_fleet_summary(0, hist), now=8.0)
    assert rep.summary()["counters"]["anomaly/latency_regression"] == 1
    # recovery clears the gauge
    for i in range(9, 15):
        hist[0] += 1
        det.update(_fleet_summary(0, hist), now=float(i))
    assert not det.alarming()
    assert rep.summary()["gauges"][
        "anomaly/latency_regression"]["value"] == 0.0


def test_anomaly_goodput_drop_and_membership_step_down():
    """Token rate falling below drop_factor x baseline alarms; a merged
    counter stepping DOWN (a replica leaving the fleet view) reads as
    zero rate, never negative."""
    det = AnomalyDetector(window=2, baseline=8, min_samples=2,
                          drop_factor=0.5)
    tokens = 0.0
    st = None
    for i in range(6):  # 100 tokens/s baseline
        tokens += 100.0
        st = det.update(_fleet_summary(tokens), now=float(i))
        assert not st["goodput_drop"]
    for i in range(6, 8):  # collapse to 10 tokens/s
        tokens += 10.0
        st = det.update(_fleet_summary(tokens), now=float(i))
    assert st["goodput_drop"] and det.alarming()
    assert st["goodput_ratio"] == pytest.approx(0.1)
    # fleet-membership step-down: no crash, clamped to zero rate
    st = det.update(_fleet_summary(tokens - 500.0), now=9.0)
    assert st["goodput_ratio"] is not None and st["goodput_ratio"] >= 0.0


def test_anomaly_source_callable_and_no_source_error():
    det = AnomalyDetector()
    with pytest.raises(ValueError):
        det.update()
    fleet = {"n": 0.0}

    def source():
        fleet["n"] += 50.0
        return _fleet_summary(fleet["n"])

    det2 = AnomalyDetector(source=source, window=2, baseline=8,
                           min_samples=2)
    for i in range(4):
        det2.update(now=float(i))
    assert not det2.alarming()


def test_metric_diff_directional_gate():
    a = {"latency_p99_s": 1.0, "tokens_per_sec": 100.0, "widgets": 3.0}
    b = {"latency_p99_s": 1.5, "tokens_per_sec": 100.0, "widgets": 4.0}
    d = metric_diff(a, b, threshold=0.05)
    assert not d["ok"]
    assert [r["key"] for r in d["regressions"]] == ["latency_p99_s"]
    # directionless leaves report as changed but never gate
    assert [r["key"] for r in d["changed"]] == ["widgets"]
    # the same movement in reverse is an improvement, not a regression
    d2 = metric_diff(b, a, threshold=0.05)
    assert d2["ok"]
    assert [r["key"] for r in d2["improvements"]] == ["latency_p99_s"]
    # throughput drops gate too (higher-is-better heuristic)
    d3 = metric_diff({"goodput_tps": 100.0}, {"goodput_tps": 80.0})
    assert not d3["ok"]


def test_obs_diff_cli_exit_codes(tmp_path):
    """The regression gate: nonzero exit + JSON report on a seeded
    regression, zero on self-compare."""
    from chainermn_tpu.tools import obs

    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(
        {"serving": {"latency_p99_s": 1.0, "goodput_tps": 50.0}}
    ))
    b.write_text(json.dumps(
        {"serving": {"latency_p99_s": 2.0, "goodput_tps": 50.0}}
    ))
    out = tmp_path / "diff.json"
    rc = obs.main(["diff", str(a), str(b), "--threshold", "0.1",
                   "-o", str(out)])
    assert rc == 1
    rep = json.loads(out.read_text())
    assert not rep["ok"]
    assert rep["regressions"][0]["key"] == "serving.latency_p99_s"
    assert obs.main(["diff", str(a), str(a), "-o", str(out)]) == 0
    assert json.loads(out.read_text())["ok"]
