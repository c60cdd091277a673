"""What every cell's run shares: the manifest lookup, the device report,
the profiler slice, the per-layer readers and the result line.

A runner (one file under ``chipbench/runners/``, picked by the traffic
file's ``kind``) gets a :class:`Run` and returns a dict with ``correct``,
``attempted``, ``failed``, ``end_to_end`` (name -> value), ``setup_s`` and
``layer_ctx`` (what the per-layer readers read).  Everything that belongs
to one configuration, one traffic mix, one cell's limits or one per-layer
metric is a file found by its name in ``BENCHMARK.json``:

    chipbench/configs/<config>.json      chipbench/traffic/<traffic>.json
    chipbench/limits/<cell>.json         chipbench/layer_metrics/<metric>.json|.py
"""

import dataclasses
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(message):
    print(f"[chipbench] {message}", file=sys.stderr, flush=True)


def say(message):
    """An earlier stdout line (the result line is the last)."""
    print(f"[chipbench] {message}", flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(path=None):
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass
class Run:
    """One run of one cell."""

    manifest: dict
    cell: dict
    config: dict
    mix: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list = None
    checks: list = dataclasses.field(default_factory=list)

    def check(self, name, value, limit, ok=None):
        """Record one number compared beside its limit; print it."""
        if ok is None:
            ok = bool(value <= limit)
        self.checks.append((name, value, limit, bool(ok)))
        say(f"check {name}: value={value!r} limit={limit!r} "
            f"{'ok' if ok else 'FAILED'}")
        return bool(ok)

    def stage(self, what):
        """Log how long after the process started a stage was reached."""
        log(f"+{time.perf_counter() - self.t_start:7.2f} s  {what}")

    @property
    def correct(self):
        return bool(self.checks) and all(c[3] for c in self.checks)


def find_cell(manifest, name, base=None):
    """The cell, its configuration file, its traffic file, its limits."""
    base = base or ROOT
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in the manifest "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(base, configs[cell["config"]]["file"]))
    bench_dir = os.path.join(base, manifest["paths"][0])
    mix = load_json(os.path.join(
        bench_dir, "traffic", cell["traffic"] + ".json"))
    limits = load_json(os.path.join(bench_dir, "limits", name + ".json"))
    return cell, config, mix, limits


def device_report(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class ProfilerSlice:
    """A short profiler trace inside the window.  The trace is written
    under ``TMPDIR`` and removed once it has been read."""

    keep_dir = None    # chipbench/tools set this to keep traces to read

    def __init__(self):
        self.dir = None
        self.t0 = self.t1 = None

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def path(self):
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace to {self.dir}")
        return found[0]

    def remove(self):
        if self.dir and self.keep_dir:
            os.makedirs(self.keep_dir, exist_ok=True)
            shutil.copy(self.path(), self.keep_dir)
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def layer_reader(name, bench_dir=HERE):
    """The reader of one per-layer metric: ``<name>.py`` with
    ``read(ctx)``, or ``<name>.json`` naming a reducer of
    ``chipbench/reducers.py`` and its arguments."""
    base = os.path.join(bench_dir, "layer_metrics", name)
    if os.path.exists(base + ".py"):
        spec = importlib.util.spec_from_file_location(
            "chipbench_layer_" + name.replace(".", "_").replace("-", "_"),
            base + ".py")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
    entry = load_json(base + ".json")
    from chipbench import reducers

    fn = getattr(reducers, entry["reducer"])
    return lambda ctx: fn(ctx, **entry.get("args", {}))


def cell_metrics(manifest, cell_name, group):
    """The metrics of ``group`` this cell reports, by the manifest's rule:
    a metric without ``workloads`` belongs to every cell that reports the
    end-to-end metric it moves (for end-to-end ones: to every cell)."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def in_cell(metric):
        if "workloads" in metric:
            return cell_name in metric["workloads"]
        if "moves" in metric:
            return in_cell(e2e[metric["moves"]])
        return True

    return [m for m in manifest[group] if in_cell(m)]


def read_layer_metrics(manifest, cell_name, ctx, bench_dir=HERE):
    out = {}
    for metric in cell_metrics(manifest, cell_name, "per_layer"):
        value = layer_reader(metric["name"], bench_dir)(ctx)
        if value is None:
            log(f"per-layer metric {metric['name']}: nothing to read")
            continue
        out[metric["name"]] = {"value": float(value),
                               "unit": metric["unit"]}
    return out


def execute(run, bench_dir=HERE):
    """Drive one run through its runner and build the result object."""
    runner = importlib.import_module(
        "chipbench.runners." + run.mix["kind"])
    result = runner.run(run)
    manifest, name = run.manifest, run.cell["name"]
    device = result["device"]
    if run.trace:
        metrics = read_layer_metrics(
            manifest, name, result["layer_ctx"], bench_dir)
        trace = result["layer_ctx"].get("trace")
        if trace is not None:
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
    else:
        values = dict(result["end_to_end"], setup_s=result["setup_s"])
        metrics = {}
        for metric in cell_metrics(manifest, name, "end_to_end"):
            metrics[metric["name"]] = {
                "value": float(values[metric["name"]]),
                "unit": metric["unit"]}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if run.trace and result["layer_ctx"].get("trace") is not None:
        line["breakdown"] = result["layer_ctx"]["trace"].breakdown()
    return line
