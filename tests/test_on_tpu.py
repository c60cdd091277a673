"""On-TPU test tier (SURVEY §4: the reference gated GPU-only tests with
``@attr.gpu`` markers run on GPU CI; this is the TPU counterpart).

The suite's conftest forces the virtual CPU mesh in-process, so these
tests spawn SUBPROCESSES with the *default* environment — JAX on the TPU
— and skip cleanly when no real chip answers.  They assert the COMPILED
(non-interpret) Pallas kernel path, a real train step on the chip, and
the whole main path through ``chip_smoke.py``.
"""

import functools
import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.tpu, pytest.mark.slow]

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_on_tpu_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tpu_env():
    env = dict(os.environ)
    # Undo the CPU forcing the test process may have exported.
    env.pop("JAX_PLATFORMS", None)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split() if "host_platform_device_count" not in f
    )
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_REPO, env.get("PYTHONPATH")) if p
    )
    return env


def _run(args, timeout):
    return subprocess.run(
        [sys.executable, _WORKER, *args],
        env=_tpu_env(), capture_output=True, text=True, timeout=timeout,
    )


@functools.cache
def _tpu_available() -> bool:
    try:
        r = _run(["probe"], timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return False
    return r.returncode == 0 and r.stdout.strip() == "tpu"


def _require_tpu():
    if not _tpu_available():
        pytest.skip("no real TPU device (probe subprocess)")


def test_flash_attention_compiled_on_tpu():
    """The compiled Mosaic kernel (fwd + explicit-vjp bwd) must match the
    XLA oracle ON THE CHIP — interpret-mode agreement (the CPU suite)
    does not cover Mosaic lowering."""
    _require_tpu()
    r = _run(["flash"], timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "OK" in r.stdout


def test_train_step_chip_matches_cpu():
    """One real data-parallel train-step trajectory on the chip must match
    the same trajectory computed on CPU (fp32, 3 steps)."""
    _require_tpu()
    r_tpu = _run(["trainstep"], timeout=900)
    assert r_tpu.returncode == 0, r_tpu.stderr[-4000:]

    env = _tpu_env()
    env["JAX_PLATFORMS"] = "cpu"
    r_cpu = subprocess.run(
        [sys.executable, _WORKER, "trainstep"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r_cpu.returncode == 0, r_cpu.stderr[-4000:]

    def losses(out):
        return [
            float(line.split(":")[1]) for line in out.splitlines()
            if line.startswith("loss ")
        ]

    lt, lc = losses(r_tpu.stdout), losses(r_cpu.stdout)
    assert len(lt) == len(lc) == 3, (r_tpu.stdout, r_cpu.stdout)
    for a, b in zip(lt, lc):
        assert abs(a - b) <= 1e-5 * max(1.0, abs(b)), (lt, lc)


def test_chip_smoke():
    """The whole main path at full width: ``python chip_smoke.py`` from
    the checkout, one process on every chip it sees."""
    _require_tpu()
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=_tpu_env(), capture_output=True, text=True, timeout=1200,
        cwd=_REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    report, verdict = map(json.loads, r.stdout.splitlines()[-2:])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "tpu"
    assert set(report["phases"]) == {"lm_train", "resnet50_train", "lm_serve"}
    assert report["claim"] is None

