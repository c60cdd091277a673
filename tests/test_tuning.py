"""Kernel-autotuning subsystem: cache round-trips, search-space validity,
the pytest/off-TPU determinism guards, miss -> static-default fallback,
numerics parity of searched configs, and the CLI's --dry-run mode.

Everything here runs on the CPU harness — by design the tuner must be
INERT in this context (no timing, no cache reads in the ops, no files
written into the repo), and these tests pin that contract.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.tuning import (
    DEFAULT_CACHE_PATH,
    ENV_CACHE_PATH,
    TuneCache,
    autotune_enabled,
    bucket_pow2,
    runtime_lookup_enabled,
)
from chainermn_tpu.tuning import autotune as autotune_mod
from chainermn_tpu.tuning.cache import CACHE_VERSION, make_key
from chainermn_tpu.tuning.search_space import (
    ce_search_space,
    flash_cache_key,
    flash_default_config,
    flash_search_space,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Cache mechanics.
# ---------------------------------------------------------------------------


def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "tune.json")
    c = TuneCache(path)
    key = make_key("flash_fwd", "TPU v5e", "bfloat16",
                   (("q", 4096), ("k", 4096), ("d", 128)),
                   {"causal": True, "window": 0})
    c.put(key, {"block_q": 256, "block_k": 512, "seconds": 1.5e-3})
    c.save()

    reread = TuneCache(path).get(key)
    assert reread is not None
    assert reread["block_q"] == 256 and reread["block_k"] == 512
    # The file itself is versioned JSON.
    with open(path) as f:
        data = json.load(f)
    assert data["version"] == CACHE_VERSION and key in data["entries"]


def test_cache_corrupt_file_degrades_to_empty(tmp_path):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        f.write("{not json")
    c = TuneCache(path)
    assert c.get("anything") is None and len(c) == 0
    # Wrong version: also a miss everywhere, not an error.
    with open(path, "w") as f:
        json.dump({"version": CACHE_VERSION + 999,
                   "entries": {"k": {"block_q": 1}}}, f)
    assert TuneCache(path).get("k") is None
    # Missing file: same.
    assert TuneCache(str(tmp_path / "absent.json")).get("k") is None


def test_cache_save_is_atomic_no_temp_left(tmp_path):
    path = str(tmp_path / "sub" / "tune.json")
    c = TuneCache(path)
    c.put("k", {"chunk": 256})
    c.save()
    assert TuneCache(path).get("k") == {"chunk": 256}
    leftovers = [f for f in os.listdir(tmp_path / "sub")
                 if f != "tune.json"]
    assert leftovers == []


def test_bucket_pow2():
    assert bucket_pow2(1) == 1
    assert bucket_pow2(2) == 2
    assert bucket_pow2(3) == 4
    assert bucket_pow2(4096) == 4096
    assert bucket_pow2(4097) == 8192
    assert bucket_pow2(3072) == 4096


def test_make_key_deterministic_flag_order():
    a = make_key("k", "dev", "bfloat16", (("q", 8),),
                 {"b": True, "a": 0})
    b = make_key("k", "dev", "bfloat16", (("q", 8),),
                 {"a": 0, "b": True})
    assert a == b and "b=1" in a


# ---------------------------------------------------------------------------
# Determinism guards: under pytest the whole subsystem is inert.
# ---------------------------------------------------------------------------


def test_tuner_is_inert_under_pytest():
    assert not autotune_enabled()
    assert not runtime_lookup_enabled()
    # Runtime lookups short-circuit to None before touching any file.
    assert autotune_mod.lookup_flash_blocks(
        "fwd", Sq=4096, Sk=4096, D=128, dtype="bfloat16", causal=True
    ) is None
    assert autotune_mod.lookup_ce_chunk(
        N=4096, V=32768, D=2048, dtype="bfloat16"
    ) is None
    # And the measurement harness refuses outright.
    with pytest.raises(RuntimeError, match="disabled"):
        autotune_mod.tune_fused_ce(N=256, V=64, D=32)


def test_default_cache_path_outside_repo():
    assert DEFAULT_CACHE_PATH.startswith("/tmp/")
    assert not os.path.abspath(DEFAULT_CACHE_PATH).startswith(REPO_ROOT)


def test_env_disable_wins(monkeypatch):
    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE", "0")
    assert not autotune_enabled()


# ---------------------------------------------------------------------------
# Runtime lookup validation (simulating the on-TPU path).
# ---------------------------------------------------------------------------


def _enable_lookups(monkeypatch, tmp_path):
    """Point the shared cache at a tmp file and force the backend gate
    open — the only way to exercise the lookup path on the CPU harness."""
    monkeypatch.setenv(ENV_CACHE_PATH, str(tmp_path / "tune.json"))
    monkeypatch.setattr(autotune_mod, "runtime_lookup_enabled", lambda: True)


def test_lookup_returns_tuned_blocks(monkeypatch, tmp_path):
    _enable_lookups(monkeypatch, tmp_path)
    from chainermn_tpu.tuning.cache import device_kind, shared_cache

    key = flash_cache_key("fwd", device_kind(), "float32",
                          512, 512, 64, True, None)
    c = TuneCache(str(tmp_path / "tune.json"))
    c.put(key, {"block_q": 128, "block_k": 64})
    c.save()
    assert shared_cache().get(key) is not None
    got = autotune_mod.lookup_flash_blocks(
        "fwd", Sq=512, Sk=512, D=64, dtype="float32", causal=True
    )
    assert got == (128, 64)


def test_lookup_rejects_entry_invalid_for_actual_shape(monkeypatch, tmp_path):
    """pow2 bucketing means S=384 hits the 512 bucket; an entry whose
    blocks do not divide 384 must be ignored, not crash the kernel."""
    _enable_lookups(monkeypatch, tmp_path)
    from chainermn_tpu.tuning.cache import device_kind

    key = flash_cache_key("fwd", device_kind(), "float32",
                          384, 384, 64, True, None)
    c = TuneCache(str(tmp_path / "tune.json"))
    c.put(key, {"block_q": 512, "block_k": 512})
    c.save()
    assert autotune_mod.lookup_flash_blocks(
        "fwd", Sq=384, Sk=384, D=64, dtype="float32", causal=True
    ) is None


def test_lookup_miss_is_none(monkeypatch, tmp_path):
    _enable_lookups(monkeypatch, tmp_path)
    assert autotune_mod.lookup_ce_chunk(
        N=1024, V=999, D=7, dtype="float32"
    ) is None


# ---------------------------------------------------------------------------
# Search spaces.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,sub", [("bfloat16", 16), ("float32", 8)])
def test_flash_search_space_validity(dtype, sub):
    Sq = Sk = 2048
    space = flash_search_space(Sq, Sk, 128, dtype, which="fwd")
    assert space
    for cfg in space:
        assert Sq % cfg["block_q"] == 0 and Sk % cfg["block_k"] == 0
        assert cfg["block_q"] % sub == 0 and cfg["block_k"] % sub == 0
    assert flash_default_config(Sq, Sk, 128, dtype, "fwd") in space
    # The VMEM model prunes: a giant head dim shrinks the space.
    big_d = flash_search_space(Sq, Sk, 2048, dtype, which="fwd")
    assert len(big_d) < len(space)
    # The default stays a member where the filters exclude it.
    tight = flash_search_space(Sq, Sk, 128, dtype, which="fwd",
                               vmem_budget=1)
    assert tight == [flash_default_config(Sq, Sk, 128, dtype, "fwd")]


def test_flash_bwd_space_tighter_than_fwd():
    fwd = flash_search_space(4096, 4096, 128, "bfloat16", which="fwd")
    bwd = flash_search_space(4096, 4096, 128, "bfloat16", which="bwd")
    assert bwd and len(bwd) <= len(fwd)


def test_ce_search_space_divisors_and_default():
    from chainermn_tpu.ops.fused_ce import DEFAULT_CHUNK, _pick_chunk

    N = 16384
    space = ce_search_space(N, 32768, 2048)
    assert space and all(N % c["chunk"] == 0 for c in space)
    assert {"chunk": _pick_chunk(N, DEFAULT_CHUNK)} in space
    # Non-pow2 row count: the default _pick_chunk divisor still appears.
    odd = ce_search_space(96, 64, 32)
    assert {"chunk": _pick_chunk(96, DEFAULT_CHUNK)} in odd


# ---------------------------------------------------------------------------
# Op fallback + parity: a miss (or any off-TPU call) is the static default.
# ---------------------------------------------------------------------------


def test_fused_ce_chunk_none_is_static_default():
    from chainermn_tpu.ops.fused_ce import DEFAULT_CHUNK, fused_cross_entropy

    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(96, 32).astype(np.float32))
    e = jnp.asarray(rng.randn(50, 32).astype(np.float32) * 0.1)
    lab = jnp.asarray(rng.randint(0, 50, size=96), jnp.int32)
    got = fused_cross_entropy(h, e, lab)  # chunk=None -> tuned-or-default
    want = fused_cross_entropy(h, e, lab, chunk=DEFAULT_CHUNK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_ce_rejects_bad_chunk():
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    h = jnp.zeros((8, 4))
    e = jnp.zeros((6, 4))
    lab = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError):
        fused_cross_entropy(h, e, lab, chunk=0)


def test_flash_default_blocks_match_explicit():
    """block_q=block_k=None off-TPU must be EXACTLY the static auto
    geometry — no cache consulted, bit-identical output."""
    from chainermn_tpu.ops.flash_attention import (
        auto_block_size,
        flash_attention,
    )

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 2, 64), jnp.float32)
               for kk in ks)
    b = auto_block_size(256, 64, jnp.float32, "fwd")
    assert b == 256  # the rule's largest fitting tile: the whole axis
    out_auto = flash_attention(q, k, v, causal=True)
    out_pinned = flash_attention(q, k, v, causal=True, block_q=b, block_k=b)
    np.testing.assert_array_equal(np.asarray(out_auto),
                                  np.asarray(out_pinned))
    # ... and so is the backward's default geometry.
    bb = auto_block_size(256, 64, jnp.float32, "bwd")

    def grads(**blocks):
        return jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, **blocks).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    for a, p in zip(grads(), grads(block_q=b, block_k=b, block_q_bwd=bb,
                                   block_k_bwd=bb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))


def test_flash_candidate_configs_numerically_match_default():
    """Every searched geometry computes the same attention (the tuner
    only ever changes speed, never values)."""
    from chainermn_tpu.ops.flash_attention import flash_attention

    S, D = 256, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (1, S, 2, D), jnp.float32)
               for kk in ks)
    ref = flash_attention(q, k, v, causal=True)
    for cfg in flash_search_space(S, S, D, "float32", which="fwd"):
        out = flash_attention(
            q, k, v, causal=True,
            block_q=cfg["block_q"], block_k=cfg["block_k"],
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"config {cfg} diverged",
        )


def test_flash_bwd_blocks_numerics_match():
    """A tuned backward geometry different from the forward's must give
    the same gradients."""
    from chainermn_tpu.ops.flash_attention import flash_attention

    S, D = 128, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (1, S, 2, D), jnp.float32)
               for kk in ks)

    def loss(q, k, v, **kw):
        return jnp.sum(flash_attention(q, k, v, causal=True, **kw) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1, 2))(
        q, k, v, block_q=64, block_k=64)
    g_tuned = jax.grad(loss, argnums=(0, 1, 2))(
        q, k, v, block_q=64, block_k=64, block_q_bwd=32, block_k_bwd=32)
    for a, b in zip(g_ref, g_tuned):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Dry-run enumeration + CLI.
# ---------------------------------------------------------------------------


def test_serve_group_search_space_validity_and_default():
    from chainermn_tpu.tuning import serve_group_search_space

    space = serve_group_search_space(8, 4096, 1024, n_devices=4,
                                     max_batch=4)
    assert space[0] == {"group_size": 1, "pp_stages": 1}  # static default
    assert {"group_size": 4, "pp_stages": 4} in space
    for cfg in space:
        assert cfg["group_size"] <= 4 and 8 % cfg["group_size"] == 0
        assert cfg["pp_stages"] <= 4
    # geometry gates: odd head count kills K=2/4; device count caps K
    assert all(c["group_size"] == 1 for c in
               serve_group_search_space(3, 4096, 1024, 8, 4))
    assert all(c["group_size"] <= 2 for c in
               serve_group_search_space(8, 4096, 1024, 2, 4))
    # batch of 1 leaves no microbatches to pipeline
    assert all(c["pp_stages"] == 1 for c in
               serve_group_search_space(8, 4096, 1024, 4, 1))


def test_tune_serve_group_dry_run_enumerates_without_timing(tmp_path,
                                                            monkeypatch):
    from chainermn_tpu.tuning import tune_serve_group

    cache_file = tmp_path / "tune.json"
    monkeypatch.setenv(ENV_CACHE_PATH, str(cache_file))
    out = tune_serve_group(dry_run=True)
    assert out["dry_run"] and out["kernel"] == "serve_group"
    assert out["default"] == {"group_size": 1, "pp_stages": 1}
    assert out["default"] in out["candidates"]
    assert not cache_file.exists()


def test_tune_lm_shapes_dry_run_times_nothing(tmp_path, monkeypatch):
    """dry_run enumerates the spaces with no compilation, no timing and
    no cache writes — and is allowed even where tuning is disabled."""
    from chainermn_tpu.tuning import tune_lm_shapes

    cache_file = tmp_path / "tune.json"
    monkeypatch.setenv(ENV_CACHE_PATH, str(cache_file))
    out = tune_lm_shapes(
        batch=2, seq=1024, n_heads=4, d_model=256, vocab=512,
        dry_run=True,
    )
    assert out["flash"]["dry_run"] and out["fused_ce"]["dry_run"]
    assert out["flash"]["fwd"]["candidates"]
    assert out["flash"]["bwd"]["candidates"]
    assert out["fused_ce"]["candidates"]
    assert not cache_file.exists()


def test_autotune_cli_dry_run_smoke(tmp_path):
    """The shipped CLI must enumerate without a TPU and without writing
    anything (the CI determinism guard for the tool itself)."""
    from conftest import subprocess_env

    env = subprocess_env()
    env[ENV_CACHE_PATH] = str(tmp_path / "cli_tune.json")
    proc = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.autotune",
         "--dry-run", "--quiet",
         "--batch", "1", "--seq", "512", "--heads", "2",
         "--d-model", "128", "--vocab", "256"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    kernels = set()
    for rec in lines:
        kernels.update(rec)
    assert kernels == {"flash", "fused_ce"}
    assert not (tmp_path / "cli_tune.json").exists()


def test_autotune_cli_refuses_cpu_timing():
    """Asked to actually TIME kernels on a CPU backend, the CLI must bail
    (exit 2) rather than persist meaningless configs."""
    from conftest import subprocess_env

    proc = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.tools.autotune", "--quiet"],
        capture_output=True, text=True, timeout=240,
        env=subprocess_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 2, (proc.stdout, proc.stderr[-2000:])
    assert "error" in json.loads(proc.stdout.splitlines()[-1])
