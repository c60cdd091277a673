"""Test harness: force an 8-device virtual CPU mesh.

The reference's CI trick (SURVEY §4) is ``mpiexec -n 2 pytest`` on one box —
real SPMD over shared-memory MPI.  The TPU-native analogue is
``--xla_force_host_platform_device_count=8`` on the CPU platform: one
process, eight virtual devices, every collective exercised for real through
``shard_map``.

``jax.config.update`` here (before the first backend initialization)
lands the suite on the virtual CPU mesh whatever ``JAX_PLATFORMS`` says.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite's wall time is dominated by
# hundreds of small jit compiles; warm re-runs hit the cache instead.
_cache_dir = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".jax_compilation_cache"
)
jax.config.update("jax_compilation_cache_dir", _cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import pytest  # noqa: E402

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Where a telemetry file accidentally written with a relative path would
# land during the suite (tests run with cwd = repo root).
_LEAK_SCAN_DIRS = (
    _REPO_ROOT,
    os.path.join(_REPO_ROOT, "tests"),
    os.path.join(_REPO_ROOT, "examples"),
    os.path.join(_REPO_ROOT, "benchmarks"),
)
_LEAK_PATTERNS = (".jsonl", ".prom")


def _telemetry_files():
    found = set()
    for d in _LEAK_SCAN_DIRS:
        try:
            names = os.listdir(d)
        except OSError:
            continue
        for n in names:
            if n.endswith(_LEAK_PATTERNS) or ".jsonl." in n:
                found.add(os.path.join(d, n))
    return found


@pytest.fixture(autouse=True)
def _no_telemetry_leaks():
    """Fail any test that leaves a step log / Prometheus export outside
    tmp: StepRecorder paths in tests must go through tmp_path.  (Scan is
    non-recursive over the repo root and the dirs tests use as cwd —
    cheap enough to run autouse.)"""
    before = _telemetry_files()
    yield
    leaked = _telemetry_files() - before
    assert not leaked, (
        "test leaked telemetry files into the repo (write them under "
        f"tmp_path instead): {sorted(leaked)}"
    )


def pytest_collection_modifyitems(config, items):
    """Soak tests (long recorder/rotation runs) stay out of tier-1: any
    test with 'soak' in its name gets the ``slow`` marker implicitly, so
    forgetting the decorator cannot slow the gate."""
    for item in items:
        if "soak" in item.name:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture(scope="session", params=[(1, 8), (2, 4), (4, 2)])
def mesh(request, devices8):
    """Meshes factoring 8 devices into (inter, intra) shapes, exercising the
    single-host and simulated multi-host topologies."""
    from chainermn_tpu.communicators import build_mesh

    inter, intra = request.param
    return build_mesh(inter_size=inter, intra_size=intra, devices=devices8)


@pytest.fixture
def lint_clean():
    """The static collective linter's assertion surface
    (docs/static_analysis.md): ``lint_clean(step, params, state, batch,
    comm=comm)`` raises ``LintError`` with the full report when any rule
    R001–R005 flags the step."""
    from chainermn_tpu.analysis import assert_lint_clean

    return assert_lint_clean


def subprocess_env(n_devices: int = 8) -> dict:
    """Environment for spawning REAL worker/example subprocesses on the
    virtual CPU mesh: force the CPU platform, and put the repo root on
    PYTHONPATH so the in-repo package imports without an install."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH")) if p
    )
    return env
