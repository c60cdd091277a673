"""The start-up ledger (``observability/startup.py``): what it records of
a tiny job on the CPU mesh, what it costs past its record, how the
recorder and the reporter read it, and that it is the program's one
``jax.monitoring`` bridge."""

import os
import re
import threading

import jax
import jax.numpy as jnp
import optax
import pytest
from jax import monitoring
from jax.sharding import NamedSharding, PartitionSpec

import chainermn_tpu
from chainermn_tpu import observability as obs
from chainermn_tpu.observability import startup
from chainermn_tpu.tools import obs as obs_cli

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def ledger():
    """A fresh ledger as the process's: whatever ran before in this
    worker, the test reads its own job."""
    with startup.use(startup.Ledger()) as fresh:
        yield fresh


@pytest.fixture
def cache_dir(tmp_path):
    """An empty persistent cache that keeps every compilation."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    try:
        yield str(tmp_path / "cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


class Job:
    """``create_communicator`` -> ``create_multi_node_optimizer`` ->
    ``make_train_step`` on the 8-device mesh, state committed where the
    step returns it (so a second call finds the first's program)."""

    def __init__(self, width=2):
        self.comm = chainermn_tpu.create_communicator("flat")
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), self.comm)
        replicated = NamedSharding(self.comm.mesh, PartitionSpec())
        self.params = jax.device_put(
            {"w": jnp.ones((8, width))}, replicated)
        self.state = jax.device_put(opt.init(self.params), replicated)
        self.width = width

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] - y) ** 2)

        self.step_fn = opt.make_train_step(loss_fn, donate=False)

    def step(self, rows=16):
        batch = self.comm.global_batch(
            (jnp.ones((rows, 8)), jnp.zeros((rows, self.width))))
        self.params, self.state, loss = self.step_fn(
            self.params, self.state, batch)
        return jax.block_until_ready(loss)


def stages(ledger, name, program="train_step"):
    return [s for s in ledger.spans() if s.kind == "stage"
            and s.name == name and s.program == program]


def test_the_import_span_and_the_boundary_marks_are_there(devices8, ledger):
    job = Job()
    job.step()
    report = ledger.summary()
    phases = {p["name"]: p for p in report["phases"]}
    assert phases["import"]["s"] > 0
    assert phases["import_jax"]["parent"] == "import"
    assert phases["import"]["start_s"] >= 0        # after the process began
    assert phases["import_jax"]["start_s"] >= phases["import"]["start_s"]
    marks = report["marks"]
    assert list(marks) == [
        "create_communicator", "build_mesh", "create_multi_node_optimizer",
        "make_train_step", "make_train_step.return"]
    assert list(marks.values()) == sorted(marks.values())
    first = report["calls"]["train_step"][0]
    assert first["index"] == 0
    assert first["start_s"] > marks["make_train_step.return"]
    assert report["calls"]["global_batch"][0]["start_s"] < first["start_s"]


def test_the_os_says_when_the_process_began(ledger):
    assert startup._os_process_start() is not None
    assert ledger.process_start <= ledger.imported["first"]
    # a process start that cannot be read is the package's first statement
    late = dict(ledger.imported, first=ledger.process_start - 5.0,
                jax=(ledger.process_start - 5.0,) * 2)
    assert startup.Ledger(late).process_start == late["first"]


def test_setup_compilation_cache_is_marked_once(ledger, monkeypatch,
                                                tmp_path):
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        setup_compilation_cache()
        at = ledger.marks["setup_compilation_cache"]
        setup_compilation_cache()
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", min_secs)
    assert ledger.marks["setup_compilation_cache"] == at


def test_the_first_calls_stages_name_the_program_and_parent_to_the_call(
        devices8, ledger):
    job = Job()
    jax.clear_caches()
    job.step()
    (call,) = [c for c in ledger.calls("train_step") if c.index == 0]
    for name in startup.STAGES:
        found = stages(ledger, name)
        assert found, name
        for s in found:
            assert s.parent == call.id
            assert call.start <= s.start <= s.end <= call.end
            assert s.thread == threading.get_ident()
    # functions traced inside the step's trace are folded, not kept
    assert [s.program for s in ledger.spans()
            if s.kind == "stage" and s.parent == call.id] == (
                ["train_step"] * 3)
    nested = {r["program"]: r for r in ledger.nested_traces()}
    assert nested["matmul"]["count"] >= 1 and nested["matmul"]["s"] > 0
    assert "train_step" not in nested
    row = {r["program"]: r for r in ledger.program_rows()}["train_step"]
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    assert row["compiles"] == 1
    report = ledger.summary()
    assert report["recompiles"] == []
    first = report["calls"]["train_step"][0]
    assert 0 <= first["self_s"] < first["s"]


def test_a_hit_a_miss_and_which_step_recompiled(devices8, cache_dir):
    with startup.use(startup.Ledger()) as cold:
        jax.clear_caches()
        job = Job()
        job.step()
    (compiled,) = stages(cold, "compile")
    assert compiled.cache_state == "miss"
    assert compiled.cache["requests"] == 1 and os.listdir(cache_dir)

    with startup.use(startup.Ledger()) as warm:
        jax.clear_caches()
        job = Job()
        job.step()
        job.step()
        (compiled,) = stages(warm, "compile")
        assert compiled.cache_state == "hit"
        assert compiled.cache.get("misses", 0) == 0
        assert compiled.cache["retrieval_s"] >= 0
        assert warm.recompiles() == []
        job.step(rows=32)               # the third call: another shape
    totals = warm.summary()["totals"]
    assert totals["hits"] >= 1
    step_rows = {r["program"]: r
                 for r in warm.program_rows()}["train_step"]
    assert (step_rows["compiles"], step_rows["hits"],
            step_rows["misses"]) == (2, 1, 1)
    (again,) = warm.recompiles()
    assert again["program"] == "train_step" and again["call"] == 2
    assert again["cache"] == "miss" and again["compile_s"] > 0
    assert any("RECOMPILED train_step in call 2" in line
               for line in startup.report_lines(warm.summary()))


def test_the_17th_call_appends_nothing(devices8, ledger):
    job = Job()
    for _ in range(startup.Ledger.CALLS):
        job.step()
    assert [c.index for c in ledger.calls("train_step")] == list(
        range(startup.Ledger.CALLS))
    assert len(ledger.calls("global_batch")) == startup.Ledger.CALLS
    before = ledger.cursor(), len(ledger.spans())
    assert ledger.open_call("train_step") is None
    job.step()
    assert (ledger.cursor(), len(ledger.spans())) == before
    startup.close(None)                 # what the wrapper does then


def test_what_is_kept_is_bounded_and_a_drain_loses_only_what_fell_out():
    class Small(startup.Ledger):
        HEAD, TAIL = 4, 3

    small = Small()
    start = small.cursor()
    for i in range(5):
        small.on_time_span(startup.EVENT_OF_STAGE["lower"], i, i + 0.5,
                           fun_name=f"f{i}")
    cursor, got = small.since(start)
    assert [s.program for s in got] == [f"f{i}" for i in range(5)]
    for i in range(5, 12):
        small.on_time_span(startup.EVENT_OF_STAGE["lower"], i, i + 0.5,
                           fun_name=f"f{i}")
    after, got = small.since(cursor)
    assert after == cursor + 7
    assert [s.program for s in got] == ["f9", "f10", "f11"]
    kept = [s.program for s in small.spans() if s.kind == "stage"]
    assert kept[:4 - start] == [f"f{i}" for i in range(4 - start)]
    assert kept[-3:] == ["f9", "f10", "f11"]
    assert small.summary()["totals"]["dropped"] == 12 + start - 7
    assert small.since(after) == (after, [])


def test_a_trace_inside_a_trace_is_folded_by_name():
    """JAX reports the inner function first (it ends first); a program
    costs three spans however many functions its tracing enters."""
    led = startup.Ledger()
    start = led.cursor()
    trace, lower = (startup.EVENT_OF_STAGE[k] for k in ("trace", "lower"))
    for i in range(1000):
        led.on_time_span(trace, 10.0 + i * 1e-3, 10.0 + i * 1e-3 + 5e-4,
                         fun_name="matmul")
        if i % 100 == 99:               # a layer's wrapper around them
            led.on_time_span(trace, 10.0 + (i - 99) * 1e-3 - 1e-4,
                             10.0 + i * 1e-3 + 6e-4, fun_name="layer")
    led.on_time_span(trace, 9.0, 12.0, fun_name="train_step")
    led.on_time_span(lower, 12.0, 13.0, fun_name="jit(train_step)")
    led.on_time_span(trace, 20.0, 20.5, fun_name="_norms")   # the next one
    _, got = led.since(start)
    assert [(s.name, s.program) for s in got] == [
        ("trace", "train_step"), ("lower", "train_step"),
        ("trace", "_norms")]
    assert got[0].end - got[0].start == pytest.approx(3.0)
    nested = {r["program"]: r for r in led.nested_traces()}
    assert nested["matmul"]["count"] == 1000
    assert nested["matmul"]["s"] == pytest.approx(0.5)
    assert nested["layer"]["count"] == 10
    totals = led.summary()["totals"]
    assert totals["nested_traces"] == 1010
    assert totals["trace_lower_s"] == pytest.approx(3.0 + 1.0 + 0.5)
    # another thread's traces do not nest in this one's
    import threading as th

    t = th.Thread(target=led.on_time_span, args=(trace, 9.5, 9.6),
                  kwargs={"fun_name": "prefetch"})
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert "prefetch" in [s.program for s in led.spans()]


def test_calls_and_phases_outlive_the_bound():
    class Small(startup.Ledger):
        HEAD, TAIL = 2, 2

    small = Small()
    with startup.use(small):
        with startup.phase("backend"):
            pass
        call = startup.open_call("train_step")
        startup.close(call)
    for i in range(20):
        small.on_time_span(startup.EVENT_OF_STAGE["lower"], i, i + 0.5,
                           fun_name=f"f{i}")
    assert [c.index for c in small.calls("train_step")] == [0]
    names = [s.name for s in small.spans()]
    assert "backend" in names and "train_step" in names
    assert "import" in [p["name"] for p in small.summary()["phases"]] or (
        not small.imported)


def test_union_and_self_times():
    assert startup.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.6)]) == 4
    assert startup.union_seconds([]) == 0
    led = startup.Ledger()
    outer = led.begin("phase", "build")
    inner = led.begin("phase", "weights")
    wall = outer.start - startup._WALL_TO_PERF
    led.on_time_span(startup.EVENT_OF_STAGE["lower"], wall, wall,
                     fun_name="jit(make)")
    led.close(inner)
    led.close(outer)
    phases = {p["name"]: p for p in led.summary()["phases"]}
    assert phases["weights"]["parent"] == "build"
    assert phases["build"]["self_s"] == pytest.approx(
        phases["build"]["s"] - phases["weights"]["s"])
    (lowered,) = [s for s in led.spans() if s.kind == "stage"]
    assert lowered.parent == inner.id and lowered.program == "make"
    assert lowered.start == pytest.approx(outer.start)


@pytest.mark.parametrize("fun_name,program", [
    ("train_step", "train_step"), ("jit(train_step)", "train_step"),
    ("jit_train_step", "train_step"), ("pmap(f)", "f"),
    ("jit(<lambda>)", "<lambda>"), (None, None)])
def test_one_name_a_program_whatever_the_stage(fun_name, program):
    assert startup.program_name(fun_name) == program


def test_phase_is_a_span_and_an_annotation(ledger, tmp_path):
    with startup.phase("backend") as span:
        with startup.phase("weights"):
            pass
    assert span.end is not None and span.kind == "phase"
    names = [p["name"] for p in ledger.summary()["phases"]]
    assert names[-2:] == ["backend", "weights"]
    with pytest.raises(RuntimeError, match="boom"):
        with startup.phase("broken"):
            raise RuntimeError("boom")
    assert [s.name for s in ledger.spans() if s.end is None] == []
    with startup.phase("after"):
        pass
    assert {p["name"]: p for p in ledger.summary()["phases"]}[
        "after"]["parent"] is None


def test_a_recorders_compile_rows_carry_program_and_cache(
        devices8, ledger, tmp_path):
    log = str(tmp_path / "steps.jsonl")
    reporter = obs.Reporter()
    with startup.phase("backend"):
        pass
    with obs.scope(reporter), obs.StepRecorder(log, mem_every=0) as rec:
        with startup.phase("weights"):
            jax.clear_caches()
            job = Job()
        job.step()
        rec.step(step=0)
        job.step()
        rec.step(step=1)
    rows = [r for r in obs.read_records(log) if r["event"] == "compile"]
    assert rows and all(
        {"name", "secs", "stage", "program", "cache"} <= set(r)
        for r in rows)
    by_stage = {r["stage"]: r for r in rows if r["program"] == "train_step"}
    assert set(by_stage) == set(startup.STAGES)
    assert by_stage["compile"]["cache"] in ("hit", "miss", "uncached")
    assert by_stage["trace"]["cache"] is None
    assert by_stage["lower"]["name"] == (
        "/jax/core/compile/jaxpr_to_mlir_module_duration")
    # every row before the second step row: the second call compiled nothing
    records = obs.read_records(log)
    second = max(i for i, r in enumerate(records) if r["event"] == "step")
    assert all(r["event"] != "compile" or r["program"] != "train_step"
               or i < second - 1 for i, r in enumerate(records))
    summary = obs_cli.summarize(records)
    assert summary["compile"]["count"] == len(rows)
    assert summary["compile"]["programs"]["train_step"]["compiles"] == 1
    got = reporter.summary()
    gauges = {k: v["value"] for k, v in got["gauges"].items()}
    assert gauges["startup/weights_s"] > 0
    assert gauges["startup/backend_s"] >= 0     # from before it opened
    assert gauges["startup/import_s"] > 0
    assert got["counters"]["compile/requests"] >= 1   # the suite's cache


def test_a_recorder_that_does_not_capture_writes_no_compile_row(
        devices8, ledger, tmp_path):
    log = str(tmp_path / "steps.jsonl")
    with obs.StepRecorder(log, mem_every=0,
                          capture_compile_events=False) as rec:
        jax.clear_caches()
        Job().step()
        rec.step(step=0)
    assert [r["event"] for r in obs.read_records(log)] == ["step"]


def _listeners():
    # the getters are not in the public module (the test's business only)
    from jax._src import monitoring as registry

    return (registry.get_event_listeners(),
            registry.get_event_duration_listeners(),
            registry.get_event_time_span_listeners())


def test_ten_recorders_leave_the_listeners_as_they_were(tmp_path):
    before = _listeners()
    assert not startup.register()       # the package's import already did
    for i in range(10):
        with obs.StepRecorder(str(tmp_path / f"steps{i}.jsonl")) as rec:
            rec.step(step=0)
    with startup.use(startup.Ledger()):
        pass
    assert _listeners() == before
    for registered in before:
        ours = [fn for fn in registered
                if getattr(fn, "__module__", "") == startup.__name__]
        assert len(ours) == 1


@pytest.mark.parametrize("record", [
    lambda: monitoring.record_event("/jax/some/new_event"),
    lambda: monitoring.record_event(
        "/jax/compilation_cache/cache_hits", unexpected="x"),
    lambda: monitoring.record_event_duration_secs("/jax/unknown", 1.0),
    lambda: monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", "not a number"),
    lambda: monitoring.record_event_time_span("/jax/unknown", 1.0, 2.0),
    lambda: monitoring.record_event_time_span(
        startup.EVENT_OF_STAGE["compile"], 1.0, 2.0),      # no fun_name
    lambda: monitoring.record_event_time_span(
        startup.EVENT_OF_STAGE["trace"], None, 2.0, fun_name="f"),
], ids=["event", "event-kwargs", "duration", "duration-value", "span",
        "span-no-fun-name", "span-no-start"])
def test_a_listener_never_raises_into_the_compile_path(ledger, record):
    errors = startup.listener_errors()
    record()
    spans = [s for s in ledger.spans() if s.kind == "stage"]
    assert all(s.program is None for s in spans)
    assert startup.listener_errors() - errors in (0, 1)
    assert ledger.summary()["totals"]["listener_calls"] == 1


def test_one_bridge_in_the_program():
    """``register_event`` is named in ``observability/startup.py`` alone,
    and nothing of the package reaches into ``jax._src.monitoring``."""
    naming, private = [], []
    for folder, _, files in os.walk(os.path.join(ROOT, "chainermn_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                text = f.read()
            if "register_event" in text:
                naming.append(os.path.relpath(path, ROOT))
            if re.search(r"jax\._src\.monitoring|jax\._src import "
                         r"monitoring|_unregister_event", text):
                private.append(os.path.relpath(path, ROOT))
    assert naming == ["chainermn_tpu/observability/startup.py"]
    assert private == []
