import copy
import json
import os
import subprocess
import sys

from chipbench import check_manifest, harness

ROOT = harness.ROOT


def faults(manifest):
    return check_manifest.check(manifest, ROOT)


def test_the_manifest_passes():
    assert faults(harness.load_manifest()) == []


def test_a_pair_given_twice_is_refused():
    """What PR 22 was refused for."""
    m = harness.load_manifest()
    twin = dict(m["workloads"][0], name="twin")
    m["workloads"].append(twin)
    assert any("is given twice; every pair" in f for f in faults(m))


def test_names_units_sources_and_chips():
    m = harness.load_manifest()
    bad = copy.deepcopy(m)
    bad["end_to_end"][0]["unit"] = "tokens per second"
    bad["per_layer"][0]["name"] = "-starts.with.dash"
    bad["configs"][0]["source"] = "x" * 201
    bad["workloads"][0]["chips"] = 2
    bad["per_layer"][1]["moves"] = "no_such_metric"
    bad["per_layer"][3]["workloads"] = ["cgpt-train-dp4", "no-such-cell"]
    bad["per_layer"].extend(dict(bad["per_layer"][2], name=f"more.{i}")
                            for i in range(128))
    text = "\n".join(faults(bad))
    for needle in ("unit 'tokens per second'", "-starts.with.dash",
                   "source: 1 to 200", "chips must be 1 or 4",
                   "moves 'no_such_metric', not an end-to-end metric",
                   "unknown workload 'no-such-cell'", "per_layer: 1 to 128"):
        assert needle in text, (needle, text)


def test_share_of_four_chip_cells():
    """At most a quarter of the cells, rounded down (one always may): one
    more four-chip cell than that is refused, however many cells there
    are."""
    m = harness.load_manifest()
    allowed = max(1, len(m["workloads"]) // 4)
    one_chip = [w for w in m["workloads"] if w["chips"] == 1]
    have = len(m["workloads"]) - len(one_chip)
    for w in one_chip[:allowed - have]:
        w["chips"] = 4
    assert faults(m) == []
    one_chip[allowed - have]["chips"] = 4
    assert any("ask for 4 chips" in f for f in faults(m))


def test_reduced_may_not_name_a_width():
    m = harness.load_manifest()
    m["configs"][0]["reduced"] = ["n_layer", "n_embd"]
    assert any("reduced names a width" in f for f in faults(m))


def test_config_file_keeps_the_published_widths():
    c = harness.load_json(os.path.join(
        ROOT, "chipbench", "configs", "cgpt1p3b-train.json"))
    assert (c["n_embd"], c["n_head"], c["n_inner"], c["vocab_size"],
            c["n_positions"]) == (2048, 16, 8192, 50257, 2048)
    assert c["reduced"]["n_layer"]["here"] == c["n_layer"]
    # every optimizer number that is not the source's is listed
    assert "optimizer" in c["assumed"]


def test_perf_md_states_the_manifest_bounds_and_window():
    """PERF.md section 2 and the manifest say the same (a review of PR 23
    found 0.03 committed beside 0.01 described)."""
    m = harness.load_manifest()
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    for metric in m["end_to_end"]:
        rows = [line for line in text.splitlines()
                if line.startswith(f"| `{metric['name']}` |")]
        assert rows, metric["name"]
        cells = [c.strip() for c in rows[0].split("|")]
        assert repr(metric["bound"]) in cells, (metric, rows[0])
    assert f"`run_seconds` is {m['run_seconds']}" in text


def test_the_command_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "cgpt-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU was found" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_cell_metrics_follow_the_workloads_key():
    m = harness.load_manifest()
    names = [x["name"] for x in harness.cell_metrics(
        m, "cgpt-train-1chip", "per_layer")]
    assert "model.mfu" in names and "comm.exposed_ms" not in names
    names = [x["name"] for x in harness.cell_metrics(
        m, "cgpt-train-dp4", "per_layer")]
    assert "model.mfu" in names and "comm.exposed_ms" in names
    e2e = [x["name"] for x in harness.cell_metrics(
        m, "cgpt-train-dp4", "end_to_end")]
    assert sorted(e2e) == ["setup_s", "train_step_ms"]
    json.dumps(m)
