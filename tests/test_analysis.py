"""Static collective-correctness linter: seeded violations, clean passes,
suppression surfaces, the CLI, and the runtime hook.

The linter's contract has two halves and both are tested here: every
seeded-violation fixture (``chainermn_tpu.analysis.fixtures``) must be
flagged with its expected rule id, AND the default bucketed train step
must lint clean on every communicator backend — a linter that cries wolf
on the blessed path is worse than none.

Golden regen::

    python tests/test_analysis.py --regen
"""

import json
import os
import subprocess
import sys
import warnings

import jax.numpy as jnp
import optax
import pytest

GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "golden",
    "lint_fixtures.json",
)


def _flagged(report):
    return sorted({f.rule for f in report.findings})


def _analyze_fixture(t):
    from chainermn_tpu.analysis import analyze_fn, analyze_jaxpr, \
        analyze_plan

    if "source" in t:  # host-plane snippets (H001–H005)
        from chainermn_tpu.analysis import hostlint

        hf = hostlint.make_host_file(
            t["target"], t["source"],
            wire=t.get("wire", False), det=t.get("det", False),
        )
        return hostlint.analyze_host([hf], wire_lock=t.get("wire_lock"))
    if "audit" in t:  # pre-computed census (e.g. compiled-HLO fixtures)
        return analyze_jaxpr(
            t["audit"], comm=t["comm"], n_leaves=t.get("n_leaves")
        )
    if "plan" in t:  # sharding-plan coverage targets (R006)
        return analyze_plan(t["plan"], t["params"])
    return analyze_fn(t["fn"], *t["args"], comm=t["comm"], **t["kwargs"])


def _fixture_report(name):
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES[name]()
    return t, _analyze_fixture(t)


# ----------------------------------------------------------------------
# Seeded violations: every rule must catch its fixture
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "name",
    ["r001", "r002", "r003", "r003_bare_int8", "r004", "r005", "r006"],
)
def test_seeded_fixture_flagged(name):
    t, report = _fixture_report(name)
    assert t["expect"] in _flagged(report), report.render()
    assert not report.ok
    for f in report.findings:
        assert f.severity == "error"
        assert f.message and f.fix_hint  # findings must be actionable


def test_findings_are_structured():
    _, report = _fixture_report("r003")
    f = next(f for f in report.findings if f.rule == "R003")
    # bf16 payloads reduce over the mesh axes with their real byte count
    assert f.axes and f.bytes > 0 and "bfloat16" in f.message
    s = f.summary()
    assert set(s) == {
        "rule", "severity", "message", "eqn_path", "axes", "bytes",
        "fix_hint",
    }


# ----------------------------------------------------------------------
# Clean passes: the blessed path must not be flagged
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "communicator",
    ["naive", "flat", "xla_ici", "hierarchical", "two_dimensional"],
)
def test_default_train_step_lints_clean(communicator, lint_clean):
    from chainermn_tpu.analysis.fixtures import clean_train_step

    t = clean_train_step(communicator)
    report = lint_clean(t["fn"], *t["args"], comm=t["comm"])
    # all five rules actually ran — a clean pass by skipping is no pass
    assert set(report.rules_run) == {"R001", "R002", "R003", "R004", "R005"}


def test_scaled_quant_pattern_blessed_structurally():
    """R003 recognizes the scale→cast→reduce→cast→unscale wire by its
    amax pmax signature alone (the fixture carries no communicator),
    and also through the comm_dtype suppression gate when the
    communicator IS given."""
    from chainermn_tpu.analysis import analyze_fn
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES["quant_scaled_allreduce"]()
    report = analyze_fn(t["fn"], *t["args"], comm=None)
    assert "R003" not in _flagged(report), report.render()

    from chainermn_tpu.communicators import create_communicator
    from chainermn_tpu.analysis.fixtures import _mesh

    comm = create_communicator("xla_ici", mesh=_mesh(), comm_dtype="int8")
    report = analyze_fn(t["fn"], *t["args"], comm=comm)
    assert "R003" not in _flagged(report), report.render()


def test_bare_int8_reduction_fires_r003():
    """The bless is the pattern, not the dtype: an int8 psum with no
    amax exchange and no comm_dtype opt-in is still an error."""
    t, report = _fixture_report("r003_bare_int8")
    f = next(f for f in report.findings if f.rule == "R003")
    assert "int8" in f.message and "amax" in f.message
    assert "comm_dtype" in f.fix_hint


def test_allreduce_grad_dtype_sanctions_narrow_reduction():
    """R003 is about *unintentional* narrow reductions: the explicit
    allreduce_grad_dtype opt-in suppresses it."""
    from chainermn_tpu.analysis import analyze_fn
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES["r003"]()
    t["comm"].allreduce_grad_dtype = jnp.bfloat16
    report = analyze_fn(t["fn"], *t["args"], comm=t["comm"])
    assert "R003" not in _flagged(report)


# ----------------------------------------------------------------------
# Library surface
# ----------------------------------------------------------------------
def test_assert_lint_clean_raises_with_report():
    from chainermn_tpu.analysis import LintError, assert_lint_clean
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES["r005"]()
    with pytest.raises(LintError) as ei:
        assert_lint_clean(t["fn"], *t["args"], comm=t["comm"])
    assert "R005" in str(ei.value)
    assert "R005" in _flagged(ei.value.report)


def test_analyze_jaxpr_accepts_audit():
    """A bare CollectiveAudit still runs the audit-only rules; the
    jaxpr rules land in rules_skipped instead of erroring."""
    from chainermn_tpu.analysis import analyze_jaxpr
    from chainermn_tpu.analysis.fixtures import FIXTURES
    from chainermn_tpu.observability import audit_fn

    t = FIXTURES["r004"]()
    audit = audit_fn(t["fn"], *t["args"])
    report = analyze_jaxpr(audit, n_leaves=16)
    assert "R004" in _flagged(report)
    assert "R002" in report.rules_skipped


def test_trace_step_jit_aot_surface():
    """trace_step reads a jitted step's AOT trace — donation argnums
    come through instead of being lost to a make_jaxpr re-trace."""
    from chainermn_tpu.analysis.fixtures import clean_train_step
    from chainermn_tpu.observability import trace_step

    t = clean_train_step("naive", n_leaves=4)
    ts = trace_step(t["fn"], *t["args"])
    # jit's AOT trace reports donation over FLAT argument leaves: the
    # params + opt-state leaves are donated, so the set is non-empty and
    # starts at leaf 0.
    assert ts.donate_argnums and 0 in ts.donate_argnums


def test_trace_step_plain_fn_kwargs():
    from chainermn_tpu.observability import audit_fn, trace_step

    def f(x, *, scale):
        return x * scale

    ts = trace_step(f, jnp.ones((4,)), scale=2.0)
    assert ts.donate_argnums is None
    audit = audit_fn(f, jnp.ones((4,)), scale=2.0)
    assert sum(audit.counts.values()) == 0


def test_unknown_rule_id_errors():
    from chainermn_tpu.analysis import analyze_fn

    with pytest.raises(ValueError, match="R999"):
        analyze_fn(lambda x: x, jnp.ones(()), rules=["R999"])


def test_register_rule_extension_point():
    from chainermn_tpu.analysis import Finding, analyze_fn, register_rule
    from chainermn_tpu.analysis.core import RULES

    @register_rule("X901", "always-fires", "test-only rule")
    def check_x901(ctx):
        return [Finding(rule="X901", severity="warning", message="hi")]

    try:
        report = analyze_fn(lambda x: x + 1, jnp.ones((2,)), rules=["X901"])
        assert [f.rule for f in report.findings] == ["X901"]
        assert report.ok  # warnings do not fail the gate
    finally:
        del RULES["X901"]


# ----------------------------------------------------------------------
# Suppression surfaces
# ----------------------------------------------------------------------
def test_disable_kwarg_suppresses():
    from chainermn_tpu.analysis import analyze_fn
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES["r005"]()
    report = analyze_fn(
        t["fn"], *t["args"], comm=t["comm"], disable=("R005",)
    )
    assert report.ok and report.suppressed == 1


def test_env_disable_suppresses(monkeypatch):
    from chainermn_tpu.analysis import ENV_DISABLE, analyze_fn
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES["r005"]()
    monkeypatch.setenv(ENV_DISABLE, "R005")
    assert analyze_fn(t["fn"], *t["args"], comm=t["comm"]).ok


def test_source_comment_suppresses():
    from chainermn_tpu.analysis import analyze_fn
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES["r003"]()
    inner = t["fn"]

    def blessed(tree):  # lint: disable=R003
        return inner(tree)

    report = analyze_fn(blessed, *t["args"], comm=t["comm"])
    assert report.ok and report.suppressed == 1


def test_rules_allowlist_scopes_the_run():
    from chainermn_tpu.analysis import analyze_fn
    from chainermn_tpu.analysis.fixtures import FIXTURES

    t = FIXTURES["r005"]()
    report = analyze_fn(
        t["fn"], *t["args"], comm=t["comm"], rules=["R001", "R003"]
    )
    assert report.ok and set(report.rules_run) == {"R001", "R003"}


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------
def _golden_view(payload):
    """The stable cross-platform slice of the CLI's JSON: which rules
    flagged which fixture (messages/bytes may vary with device count)."""
    return {
        t["target"]: sorted({f["rule"] for f in t["findings"]})
        for t in payload["targets"]
    }


def test_cli_fixtures_json_matches_golden(capsys):
    from chainermn_tpu.tools import lint as lint_cli

    rc = lint_cli.main(["--fixtures", "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1 and payload["ok"] is False
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    assert _golden_view(payload) == golden["flagged_rules"], (
        f"regenerate with: python {__file__} --regen"
    )


def test_cli_list_rules_json(capsys):
    from chainermn_tpu.tools import lint as lint_cli

    assert lint_cli.main(["--list-rules", "--format", "json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert [r["id"] for r in data["rules"]] == [
        "H001", "H002", "H003", "H004", "H005",
        "R001", "R002", "R003", "R004", "R005", "R006",
    ]


def test_cli_rules_filter_and_exit_zero(capsys):
    from chainermn_tpu.tools import lint as lint_cli

    # R005's fixture is clean under every OTHER rule, so scoping the run
    # to R001 must exit 0.
    rc = lint_cli.main(["--fixtures", "r005", "--rules", "R001",
                        "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0 and payload["ok"] is True


def test_cli_self_check_is_clean(capsys):
    from chainermn_tpu.tools import lint as lint_cli

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    problems, engine = lint_cli._self_check(repo_root)
    assert problems == [], problems
    assert engine in ("ruff", "builtin-ast")


def test_cli_entry_point_subprocess():
    """Real `python -m chainermn_tpu.tools.lint` on one seeded fixture:
    nonzero exit and well-formed JSON through the actual entry point."""
    from conftest import subprocess_env

    proc = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.lint",
         "--fixtures", "r003", "--format", "json"],
        capture_output=True, text=True, timeout=240, env=subprocess_env(),
    )
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert _golden_view(payload)["r003"] == ["R003"]


# ----------------------------------------------------------------------
# Runtime hook (CHAINERMN_TPU_LINT)
# ----------------------------------------------------------------------
def _tiny_step(donate):
    from chainermn_tpu.analysis.fixtures import (
        _leafy_loss, _leafy_params, _mesh,
    )
    from chainermn_tpu.communicators import create_communicator
    from chainermn_tpu.optimizers import create_multi_node_optimizer

    comm = create_communicator("naive", mesh=_mesh())
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    params = _leafy_params(4, (8, 8))
    state = opt.init(params)
    step = opt.make_train_step(_leafy_loss, donate=donate)
    batch = jnp.ones((comm.device_size * 2, 4), jnp.float32)
    return step, params, state, batch


def test_runtime_hook_strict_raises(monkeypatch):
    from chainermn_tpu.analysis import LintError

    monkeypatch.setenv("CHAINERMN_TPU_LINT", "strict")
    step, params, state, batch = _tiny_step(donate=False)
    with pytest.raises(LintError, match="R005"):
        step(params, state, batch)


def test_runtime_hook_warns_once_and_reports(monkeypatch, tmp_path):
    from chainermn_tpu.observability import Reporter, recording, scope

    monkeypatch.setenv("CHAINERMN_TPU_LINT", "1")
    step, params, state, batch = _tiny_step(donate=False)
    log = tmp_path / "steps.jsonl"
    rep = Reporter()
    with scope(rep), recording(str(log)):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            params, state, _ = step(params, state, batch)
            step(params, state, batch)  # second call: hook already done
    msgs = [str(w.message) for w in caught]
    assert sum("R005" in m for m in msgs) == 1, msgs
    assert rep.summary()["counters"]["lint/errors"] >= 1
    rows = [json.loads(line) for line in log.read_text().splitlines()]
    lint_rows = [r for r in rows if r.get("event") == "lint"]
    assert len(lint_rows) == 1
    assert lint_rows[0]["findings"][0]["rule"] == "R005"


def test_runtime_hook_off_by_default(monkeypatch):
    monkeypatch.delenv("CHAINERMN_TPU_LINT", raising=False)
    step, params, state, batch = _tiny_step(donate=False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        step(params, state, batch)
    assert not any("R005" in str(w.message) for w in caught)


def test_runtime_hook_clean_step_silent(monkeypatch):
    monkeypatch.setenv("CHAINERMN_TPU_LINT", "strict")
    step, params, state, batch = _tiny_step(donate=True)
    params, state, loss = step(params, state, batch)
    assert jnp.isfinite(loss)


# ----------------------------------------------------------------------
# --regen
# ----------------------------------------------------------------------
def _regen():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    from chainermn_tpu.analysis.fixtures import FIXTURES

    flagged = {}
    for name in sorted(FIXTURES):
        t = FIXTURES[name]()
        report = _analyze_fixture(t)
        flagged[name] = _flagged(report)
        if t["expect"] is None:  # clean fixture: nothing may fire
            assert flagged[name] == [], (name, report.render())
        else:
            assert t["expect"] in flagged[name], (name, report.render())
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump({"flagged_rules": flagged}, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH}", file=sys.stderr)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="regenerate the lint-fixtures golden")
    if not ap.parse_args().regen:
        ap.error("run under pytest, or pass --regen to regenerate")
    _regen()
