"""Elastic supervisor + chaos harness unit tests (fast tier).

The supervisor is pure process plumbing, so everything here runs with
stdlib dummy ranks (``_elastic_dummy_worker.py``) — no jax, no
communicator stack.  The jax.distributed soaks (real training, real
kills, digest parity) live in ``test_multiprocess.py``.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from conftest import subprocess_env

from chainermn_tpu.elastic import (
    EXIT_PREEMPTED,
    ChaosEngine,
    ChaosSchedule,
    ElasticSupervisor,
    Fault,
    FileBeat,
    HeartbeatMonitor,
    SupervisorConfig,
    read_beat,
)

_DUMMY = os.path.join(os.path.dirname(__file__), "_elastic_dummy_worker.py")


# ---------------------------------------------------------------------------
# chaos schedules
# ---------------------------------------------------------------------------

def test_chaos_schedule_roundtrip():
    text = ("kill:rank=1:step=5;term:rank=0:step=8;"
            "hb_stall:rank=1:step=3:secs=30;ckpt_corrupt:rank=0:gen=4;"
            "ckpt_torn:rank=1:gen=6;ckpt_slow:secs=0.05;"
            "kill:rank=0:step=2:inc=1")
    s = ChaosSchedule.parse(text)
    assert len(s.faults) == 7
    assert ChaosSchedule.parse(s.format()).format() == s.format()
    assert s.faults[0] == Fault(kind="kill", rank=1, step=5)
    assert s.faults[-1].inc == 1


@pytest.mark.parametrize("bad", [
    "explode:rank=1:step=5",        # unknown kind
    "kill:rank=1:step=5:when=now",  # unknown key
    "kill:rank=1",                  # missing required step
    "hb_stall:step=3",              # missing required secs
    "kill:rank=1:step5",            # not key=value
])
def test_chaos_schedule_rejects(bad):
    with pytest.raises(ValueError):
        ChaosSchedule.parse(bad)


def test_chaos_fault_targeting():
    f = Fault(kind="kill", rank=1, step=5)
    assert f.targets(rank=1, incarnation=0)
    assert not f.targets(rank=0, incarnation=0)
    assert not f.targets(rank=1, incarnation=2)  # inc defaults to 0
    every_inc = Fault(kind="kill", rank=1, step=5, inc=-1)
    assert every_inc.targets(rank=1, incarnation=7)
    any_rank = Fault(kind="term", step=2)
    assert any_rank.targets(rank=0, incarnation=0)
    assert any_rank.targets(rank=3, incarnation=0)

    s = ChaosSchedule.parse("kill:rank=1:step=5;term:rank=0:step=8:inc=2")
    assert [f.kind for f in s.for_rank(1, 0)] == ["kill"]
    assert s.for_rank(0, 0) == ()
    assert [f.kind for f in s.for_rank(0, 2)] == ["term"]


class _FakeBeat:
    def __init__(self):
        self.suppressed = []

    def suppress(self, secs):
        self.suppressed.append(secs)


def test_chaos_engine_hb_stall_fires_once():
    hb = _FakeBeat()
    eng = ChaosEngine(
        ChaosSchedule.parse("hb_stall:rank=0:step=3:secs=9"),
        rank=0, incarnation=0, heartbeat=hb,
    )
    eng.on_step(2)
    assert hb.suppressed == []
    eng.on_step(3)
    assert hb.suppressed == [9.0]
    eng.on_step(4)  # fired-once: a step fault never re-fires
    assert hb.suppressed == [9.0]


def test_chaos_engine_term_sends_sigterm():
    got = []
    prev = signal.signal(signal.SIGTERM, lambda *a: got.append(a[0]))
    try:
        eng = ChaosEngine(
            ChaosSchedule.parse("term:rank=0:step=1"),
            rank=0, incarnation=0,
        )
        eng.on_step(0)
        assert got == []
        eng.on_step(1)
        # delivery is on the next bytecode boundary; give it one
        time.sleep(0.01)
        assert got == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)


class _FakeCkpt:
    """Just enough checkpointer surface for wrap_checkpointer."""

    def __init__(self, path):
        self._path = str(path)
        self.saves = []

        class _C:
            rank = 0
        self.comm = _C()

    def save(self, state, iteration, block=True):
        self.saves.append((iteration, block))
        with open(self._path, "wb") as f:
            f.write(b"HDRxxxxpayloadCRC4")

    def wait(self):
        pass

    def _snap(self, iteration, rank):
        return self._path


def test_chaos_engine_corrupts_committed_snapshot(tmp_path):
    snap = tmp_path / "snap"
    ck = _FakeCkpt(snap)
    eng = ChaosEngine(
        ChaosSchedule.parse("ckpt_corrupt:rank=0:gen=2"),
        rank=0, incarnation=0,
    )
    eng.wrap_checkpointer(ck)
    ck.save({}, 1, block=False)
    assert snap.read_bytes() == b"HDRxxxxpayloadCRC4"
    ck.save({}, 2, block=False)
    damaged = snap.read_bytes()
    assert len(damaged) == 18 and damaged != b"HDRxxxxpayloadCRC4"
    # the flipped byte sits just before the trailing u32 crc
    assert damaged[-5] == (b"HDRxxxxpayloadCRC4"[-5] ^ 0xFF)
    # the corrupting save was forced synchronous
    assert ck.saves == [(1, False), (2, True)]


def test_chaos_engine_torn_truncates(tmp_path):
    snap = tmp_path / "snap"
    ck = _FakeCkpt(snap)
    eng = ChaosEngine(
        ChaosSchedule.parse("ckpt_torn:rank=0:gen=1"),
        rank=0, incarnation=0,
    )
    eng.wrap_checkpointer(ck)
    ck.save({}, 1)
    assert snap.read_bytes() == b"HDRxxxxpayloadCRC4"[:-7]


def test_chaos_engine_incarnation_gating():
    eng = ChaosEngine(
        ChaosSchedule.parse("kill:rank=0:step=1"),
        rank=0, incarnation=1,  # fault belongs to incarnation 0
    )
    eng.on_step(1)  # must NOT SIGKILL us
    assert eng._armed == []


# ---------------------------------------------------------------------------
# heartbeat module (shared with serving)
# ---------------------------------------------------------------------------

def test_heartbeat_monitor_shared_with_serving():
    from chainermn_tpu.elastic.heartbeat import HeartbeatMonitor as a
    from chainermn_tpu.serving.cluster import HeartbeatMonitor as b
    from chainermn_tpu.serving.cluster.health import HeartbeatMonitor as c
    assert a is b is c


def test_heartbeat_monitor_deadline_and_revival():
    t = [0.0]
    m = HeartbeatMonitor([0, 1], miss_after_s=1.0, clock=lambda: t[0])
    assert m.check() == []
    t[0] = 0.9
    m.beat(1)
    t[0] = 1.5
    assert m.check() == [0]      # rank 0 missed its deadline
    assert m.check() == []       # newly-dead reported exactly once
    assert not m.alive(0) and m.alive(1)
    m.beat(0)                    # replacement incarnation revives
    assert m.alive(0)
    t[0] = 10.0
    assert sorted(m.check()) == [0, 1]


def test_filebeat_and_read_beat(tmp_path):
    path = tmp_path / "hb" / "rank0"
    assert read_beat(str(path)) is None
    fb = FileBeat(str(path))
    fb.beat(7)
    m1 = read_beat(str(path))
    assert m1 is not None
    assert path.read_text() == "7"
    fb.suppress(60)
    fb.beat(8)                   # suppressed: no write
    assert path.read_text() == "7"
    assert read_beat(str(path)) == m1


# ---------------------------------------------------------------------------
# supervisor (in-process, dummy ranks)
# ---------------------------------------------------------------------------

def _config(tmp_path, mode, nproc=1, **kw):
    cfg = dict(
        argv=[sys.executable, _DUMMY, mode],
        nproc=nproc,
        heartbeat_timeout_s=1.0,
        start_grace_s=10.0,
        poll_s=0.02,
        grace_s=2.0,
        backoff_s=0.05,
        workdir=str(tmp_path / "sup"),
        echo=False,
        barrier_timeout_s=30.0,
    )
    cfg.update(kw)
    return SupervisorConfig(**cfg)


def test_supervisor_clean_run(tmp_path):
    report = ElasticSupervisor(_config(tmp_path, "ok")).run()
    assert report["status"] == "ok"
    assert report["restarts"] == 0
    assert report["preemptions"] == 0
    assert report["incarnations"] == 1
    assert report["params_digest"] == "abad1dea"


def test_supervisor_restarts_after_crash(tmp_path):
    sup = ElasticSupervisor(_config(tmp_path, "crash_once"))
    report = sup.run()
    assert report["status"] == "ok"
    assert report["restarts"] == 1
    assert report["incarnations"] == 2
    # dummy's incarnation-1 output carries "resumed from iteration 10"
    assert report["resume_generation"] == 10
    kinds = [e["kind"] for e in sup.events]
    assert "failure" in kinds and "success" in kinds


def test_supervisor_exhausts_restart_budget(tmp_path):
    t0 = time.monotonic()
    sup = ElasticSupervisor(
        _config(tmp_path, "crash_always", max_restarts=1)
    )
    report = sup.run()
    assert report["status"] == "failed"
    assert report["restarts"] == 2  # budget 1 exceeded on the 2nd crash
    assert report["incarnations"] == 2
    assert any(
        e["kind"] == "give_up" and e["reason"] == "max_restarts"
        for e in sup.events
    )
    assert time.monotonic() - t0 < 30  # bounded: no deadline-less waits


def test_supervisor_teardown_is_bounded_and_sigkills(tmp_path):
    """Rank 1 crashes while rank 0 ignores SIGTERM and beats forever:
    the supervisor must SIGKILL rank 0 within its grace window, then
    respawn and finish.  Bounded by the supervisor's own events and its
    configured grace: the whole run's wall clock also holds four process
    start-ups, which a loaded machine stretches without limit."""
    cfg = _config(tmp_path, "teardown", nproc=2)
    sup = ElasticSupervisor(cfg)
    teardown, spans = sup._teardown, []

    def timed_teardown(ranks):
        t0 = time.monotonic()
        teardown(ranks)
        spans.append(time.monotonic() - t0)

    sup._teardown = timed_teardown
    report = sup.run()
    assert report["status"] == "ok"
    assert report["restarts"] == 1
    kinds = [e["kind"] for e in sup.events]
    td = [e for e in sup.events if e["kind"] == "teardown"]
    assert td[0]["sigkilled"] == [0] and td[0]["incarnation"] == 0, td
    assert "spawn" in kinds[kinds.index("teardown"):], kinds  # respawned
    # SIGTERM, the whole grace, SIGKILL; then a reap and a reader join
    # (2 s) a rank at most — _teardown's own deadlines.
    assert cfg.grace_s <= spans[0] <= (
        cfg.grace_s + cfg.nproc * (cfg.grace_s + 2.0)), spans


def test_supervisor_heartbeat_deadline_detects_stall(tmp_path):
    """Rank 1 stays alive but stops beating: only the heartbeat
    deadline can catch it (exit-code polling never fires)."""
    sup = ElasticSupervisor(
        _config(tmp_path, "stall", nproc=2, heartbeat_timeout_s=0.5,
                start_grace_s=5.0)
    )
    report = sup.run()
    assert report["status"] == "ok"
    assert report["restarts"] == 1
    fails = [e for e in sup.events if e["kind"] == "failure"]
    assert any(1 in e["heartbeat_dead"] for e in fails), fails


def test_supervisor_rescales_to_survivors(tmp_path):
    sup = ElasticSupervisor(
        _config(tmp_path, "crash_rank1_once", nproc=2,
                rescale_on_failure=True, min_nproc=1)
    )
    report = sup.run()
    assert report["status"] == "ok"
    assert report["nproc"] == 2
    assert report["world"] == 1  # shrank to the survivor count
    assert any(
        e["kind"] == "rescale" and e["to_world"] == 1 for e in sup.events
    )


def test_supervisor_counts_preemption_separately(tmp_path):
    report = ElasticSupervisor(_config(tmp_path, "preempt_once")).run()
    assert report["status"] == "ok"
    assert report["preemptions"] == 1
    assert report["restarts"] == 0  # preemption is not a crash
    assert report["incarnations"] == 2
    assert report["exit_codes"] == {"0": 0}


def test_supervisor_counters_through_obs(tmp_path):
    """The elastic/* counters ride the step log into tools.obs
    summarize and the Prometheus exporter."""
    log = tmp_path / "sup.jsonl"
    ElasticSupervisor(
        _config(tmp_path, "crash_once", step_log=str(log))
    ).run()
    from chainermn_tpu.observability.step_log import read_records
    from chainermn_tpu.tools.obs import summarize, to_prometheus

    rows = read_records(str(log))
    summary = summarize(rows)
    assert summary["counters"]["elastic/restarts"] == 1
    assert summary["counters"]["elastic/preemptions"] == 0
    assert summary["counters"]["elastic/resume_generation"] == 10
    prom = to_prometheus(summary)
    assert 'counter_total{name="elastic/restarts"} 1' in prom
    # supervisor lifecycle rows are regular events in the same log
    kinds = {r.get("kind") for r in rows if r.get("event") == "elastic"}
    assert {"spawn", "failure", "teardown", "success"} <= kinds


# ---------------------------------------------------------------------------
# crash postmortem (global_except_hook satellite)
# ---------------------------------------------------------------------------

def test_postmortem_row_written_on_crash(tmp_path):
    pm = tmp_path / "postmortem.jsonl"
    code = (
        "import chainermn_tpu.global_except_hook as geh\n"
        "geh.add_hook()\n"
        "geh.set_current_step(7)\n"
        "raise RuntimeError('chaos-postmortem-test')\n"
    )
    env = subprocess_env(n_devices=1)
    env["CHAINERMN_TPU_POSTMORTEM_FILE"] = str(pm)
    env["CHAINERMN_TPU_ELASTIC_RANK"] = "3"
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert res.returncode == 13, res.stderr  # the crash barrier's exit
    from chainermn_tpu.observability.step_log import read_records

    rows = [r for r in read_records(str(pm)) if r.get("event") == "crash"]
    assert len(rows) == 1
    row = rows[0]
    assert row["rank"] == 3
    assert row["step"] == 7
    assert "RuntimeError" in row["exc"]
    assert "chaos-postmortem-test" in row["traceback"]


def test_postmortem_file_tolerates_torn_tail(tmp_path):
    """O_APPEND rows survive a torn tail: read_records must still
    return the intact rows."""
    pm = tmp_path / "postmortem.jsonl"
    row = json.dumps({"event": "crash", "rank": 0, "step": 1,
                      "exc": "X", "traceback": "tb", "t": 0.0, "size": 1})
    pm.write_text(row + "\n" + row[: len(row) // 2])
    from chainermn_tpu.observability.step_log import read_records

    rows = read_records(str(pm))
    assert len(rows) == 1 and rows[0]["rank"] == 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_smoke(tmp_path):
    env = subprocess_env(n_devices=1)
    res = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.elastic",
         "--nproc", "1", "--max-restarts", "0", "--no-echo",
         "--workdir", str(tmp_path / "sup"),
         "--", sys.executable, "-c", "print('hello from the rank')"],
        capture_output=True, text=True, env=env, timeout=180,
        cwd=str(tmp_path),
    )
    assert res.returncode == 0, res.stdout + res.stderr
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("ELASTIC_REPORT ")]
    assert len(line) == 1
    report = json.loads(line[0].split(" ", 1)[1])
    assert report["status"] == "ok"
    assert report["nproc"] == 1


def test_cli_rejects_bad_chaos_schedule(tmp_path):
    env = subprocess_env(n_devices=1)
    res = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.elastic",
         "--nproc", "1", "--chaos", "explode:rank=0:step=1",
         "--workdir", str(tmp_path / "sup"),
         "--", sys.executable, "-c", "print('never runs')"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=str(tmp_path),
    )
    assert res.returncode != 0
    assert "never runs" not in res.stdout


def test_cli_requires_command(tmp_path):
    env = subprocess_env(n_devices=1)
    res = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.elastic",
         "--nproc", "1"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=str(tmp_path),
    )
    assert res.returncode == 2  # argparse usage error
