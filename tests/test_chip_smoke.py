"""``chip_smoke.py`` on the CPU mesh: the three phases at a tiny width
(interpret-mode kernel), the multi-device checks, and the off-TPU guard.
The full-width run is the ``tpu`` tier's (tests/test_on_tpu.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke

LM_TINY = dict(
    vocab=32, d_model=16, n_heads=1, d_ff=32, n_layers=1,
    seq=32, per_chip_batch=1, ce_chunk=16,
)
RESNET_TINY = dict(
    stage_sizes=(1,), num_filters=4, num_classes=4,
    image=8, per_chip_batch=1,
)
SERVE_TINY = dict(
    vocab=64, d_model=32, n_heads=2, d_ff=64, n_layers=1,
    max_len=64, requests=3, prompt_len=8, new_tokens=4,
    max_batch=2, block_size=4, n_blocks=64,
)


def test_lm_train_tiny(devices8):
    r = chip_smoke.lm_train(LM_TINY)
    assert np.isfinite(r["first_loss"]) and r["last_loss"] < r["first_loss"]


def test_resnet50_train_tiny(devices8):
    r = chip_smoke.resnet50_train(RESNET_TINY)
    assert np.isfinite(r["last_loss"])


def test_lm_serve_tiny():
    r = chip_smoke.lm_serve(SERVE_TINY)
    assert r["finished"] == SERVE_TINY["requests"]
    assert r["compiled_programs"]["prefill"] >= 1


def test_placement_checks_fire_for_a_batch_left_on_one_device(devices8):
    import chainermn_tpu

    comm = chainermn_tpu.create_communicator("xla_ici")
    devices = list(comm.mesh.devices.flat)
    host = np.zeros((16, 4), np.float32)
    chip_smoke.check_batch_spread(comm.global_batch((host,)), devices)
    with pytest.raises(AssertionError, match="lives on 1 device"):
        chip_smoke.check_batch_spread((jnp.asarray(host),), devices)
    chip_smoke.check_replicated(
        jax.device_put(host, jax.sharding.NamedSharding(
            comm.mesh, jax.sharding.PartitionSpec())),
        devices, "x",
    )
    with pytest.raises(AssertionError, match="not replicated"):
        chip_smoke.check_replicated(jnp.asarray(host), devices, "x")


def test_verdict_line_has_exactly_the_contract_keys():
    v = chip_smoke.verdict(jax.devices())
    assert set(v) == {"ok", "device"} and v["ok"] is True
    assert set(v["device"]) == {"platform", "kind", "count"}
    d = jax.devices()[0]
    assert v["device"] == {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def test_main_refuses_to_run_off_tpu(capsys):
    with pytest.raises(SystemExit, match="no TPU was found"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""
