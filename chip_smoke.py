#!/usr/bin/env python
"""Chip smoke: the training and serving main paths, once, on the TPU.

    python chip_smoke.py          # from the checkout; no install needed

Three phases through the entry points a user calls
(``create_communicator("xla_ici")``, ``create_multi_node_optimizer``,
``make_train_step[_with_state]``, ``serving.InferenceEngine`` +
``ContinuousBatchingScheduler`` + ``ServeFrontend``), in ONE process on
every chip that process sees, at full width (``LM_FULL``,
``RESNET_FULL``, ``SERVE_FULL`` below) with random weights from a seed:

* ``lm_train`` — the 470M dense LM (flash attention + fused CE, AdamW).
* ``resnet50_train`` — ResNet-50 at 224x224, SGD+momentum, cross-replica
  BatchNorm.
* ``lm_serve`` — the same LM geometry answering 8 greedy requests.

Any failed check raises: nothing is caught and reported as data.  Stdout
is two JSON lines: the report (versions, cache directory, per-phase
compile seconds, step times and losses, ``"claim": null``), then, last,
the verdict ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
the device as JAX reports it and no other key.  This is a smoke, not a
benchmark — it prints times, never a rate or a utilization.  ``__main__``
refuses to run without a TPU, and nothing makes it pass off-chip; the
phase functions take a width so the tier-1 suite can run them tiny on the
CPU mesh.
"""

import json
import statistics
import sys
import time

# Full width: the two training flagships, and the LM geometry again for
# the server.
LM_FULL = dict(
    vocab=32768, d_model=2048, n_heads=16, d_ff=8192, n_layers=8,
    seq=4096, per_chip_batch=4, ce_chunk=1024,
)
RESNET_FULL = dict(
    stage_sizes=(3, 4, 6, 3), num_filters=64, num_classes=1000,
    image=224, per_chip_batch=256,
)
SERVE_FULL = dict(
    vocab=32768, d_model=2048, n_heads=16, d_ff=8192, n_layers=8,
    max_len=512, requests=8, prompt_len=128, new_tokens=32,
    max_batch=4, block_size=16, n_blocks=512,
)

N_STEPS = 5  # timed steps after the compiling one
MEMORY_SPREAD_LIMIT = 0.25  # fullest vs emptiest chip, bytes_in_use


def check(ok, message):
    """A failed check fails the smoke (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(message)


def log(message):
    print(f"[chip_smoke] {message}", file=sys.stderr, flush=True)


def require_tpu():
    import jax

    from chainermn_tpu.observability import startup

    with startup.phase("backend"):
        platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU was found (JAX reports platform "
            f"{platform!r}); this script runs on the chip only"
        )


def check_batch_spread(batch, devices):
    """Every batch leaf has a shard on each device BEFORE the first step:
    a batch left where ``jnp.asarray`` puts it sits on the first chip and
    is re-sharded off it at every step."""
    import jax

    for leaf in jax.tree.leaves(batch):
        holders = {s.device for s in leaf.addressable_shards}
        check(
            holders == set(devices),
            f"batch leaf {leaf.shape} lives on {len(holders)} device(s), "
            f"not on all {len(devices)}",
        )


def check_replicated(tree, devices, what):
    import jax

    for leaf in jax.tree.leaves(tree):
        check(
            leaf.sharding.is_fully_replicated
            and leaf.sharding.device_set == set(devices),
            f"{what} leaf {leaf.shape} is not replicated on all "
            f"{len(devices)} devices: {leaf.sharding}",
        )


def check_memory_balance(devices):
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    spread = (max(in_use) - min(in_use)) / max(in_use)
    check(
        spread < MEMORY_SPREAD_LIMIT,
        f"per-chip bytes_in_use differ by {spread:.0%} "
        f"(limit {MEMORY_SPREAD_LIMIT:.0%}): {in_use}",
    )
    return round(spread, 4)


def _train_phase(comm, step, carry, batch, split):
    """Compile ``step``, run it 1 + ``N_STEPS`` times on ``batch``, and
    apply the checks every training phase shares.  ``carry`` is the tuple
    of donated step arguments; ``split(outputs) -> (carry, loss)``."""
    import jax
    import numpy as np

    devices = list(comm.mesh.devices.flat)
    multi = len(devices) > 1
    if multi:
        check_batch_spread(batch, devices)

    # Compile ahead of the first call for the HLO text; with the
    # persistent cache on, that call then loads what was compiled here.
    t0 = time.perf_counter()
    hlo = step.lower(*carry, batch).compile().as_text()
    compile_s = time.perf_counter() - t0
    if multi:
        from chainermn_tpu.observability import audit_hlo_text

        census = audit_hlo_text(hlo)
        check(census.counts.get("psum", 0) > 0,
              "no all-reduce in the compiled multi-device step")

    losses, step_s = [], []
    for i in range(1 + N_STEPS):
        t0 = time.perf_counter()
        carry, loss = split(step(*carry, batch))
        losses.append(float(jax.block_until_ready(loss)))
        step_s.append(time.perf_counter() - t0)
        if i == 0:
            # Where the start went: phases, marks, every program's trace /
            # lower / compile seconds and how the cache answered.
            from chainermn_tpu.observability import startup

            for line in startup.report_lines():
                log(line)
    check(np.isfinite(losses).all(), f"non-finite loss: {losses}")

    report = {
        "compile_s": round(compile_s, 2),
        "first_step_s": round(step_s[0], 2),
        "median_step_ms": round(statistics.median(step_s[1:]) * 1e3, 2),
        "first_loss": losses[0],
        "last_loss": losses[-1],
    }
    if multi:
        check_replicated(carry, devices, "params/state")
        # How the compiler lowered the gradient exchange: all-reduces in
        # the step, and how many collectives became start/done pairs.
        report["all_reduce_ops"] = census.counts["psum"]
        report["all_reduce_async_pairs"] = census.async_pairs
        if devices[0].platform == "tpu":
            report["memory_spread"] = check_memory_balance(devices)
    return hlo, losses, report


def lm_train(width):
    """Dense decoder LM train step: flash attention + chunked fused CE,
    AdamW, donated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.observability import startup
    from chainermn_tpu.ops import make_flash_attention_fn
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    comm = chainermn_tpu.create_communicator("xla_ici")
    S = width["seq"]
    model = TransformerLM(
        vocab=width["vocab"], d_model=width["d_model"],
        n_heads=width["n_heads"], d_ff=width["d_ff"],
        n_layers=width["n_layers"], max_len=S,
        attention_fn=make_flash_attention_fn(causal=True),
    )
    rng = np.random.RandomState(0)
    shape = (width["per_chip_batch"] * comm.device_size, S)
    batch = comm.global_batch(tuple(
        rng.randint(0, width["vocab"], size=shape).astype(np.int32)
        for _ in range(2)
    ))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adamw(3e-4, weight_decay=0.1), comm
    )
    with startup.phase("weights"):
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, S), jnp.int32)
        )["params"]
        state = opt.init(params)

    def loss_fn(p, batch):
        tokens, labels = batch
        h = model.apply({"params": p}, tokens, return_hidden=True)
        return fused_cross_entropy(
            h, p["embed"]["embedding"], labels, chunk=width["ce_chunk"]
        )

    step = opt.make_train_step(loss_fn, donate=True)
    hlo, losses, report = _train_phase(
        comm, step, (params, state), batch, lambda out: (out[:2], out[2])
    )
    check(losses[-1] < losses[1],
          f"loss did not fall on a fixed batch: {losses}")
    if jax.devices()[0].platform == "tpu":
        # The compiled Pallas kernel, not interpret mode and not the XLA
        # attention fallback.
        check("tpu_custom_call" in hlo,
              "no Mosaic custom call in the compiled LM step")
    return report


def resnet50_train(width):
    """ResNet train step with cross-replica BatchNorm, SGD+momentum,
    donated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu
    from chainermn_tpu.models.resnet import ResNet50
    from chainermn_tpu.observability import startup

    comm = chainermn_tpu.create_communicator("xla_ici")
    image = (width["image"], width["image"], 3)
    model = ResNet50(
        num_classes=width["num_classes"], num_filters=width["num_filters"],
        stage_sizes=list(width["stage_sizes"]),
    )
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm
    )
    with startup.phase("weights"):
        variables = jax.jit(model.init, static_argnames="train")(
            jax.random.PRNGKey(0), jnp.zeros((1, *image), jnp.float32),
            train=True,
        )
        params, batch_stats = variables["params"], variables["batch_stats"]
        state = opt.init(params)

    def loss_fn(params, batch_stats, batch):
        x, y = batch
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats},
            x, train=True, mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, updates["batch_stats"]

    step = opt.make_train_step_with_state(loss_fn, donate=True)
    rng = np.random.RandomState(0)
    n = width["per_chip_batch"] * comm.device_size
    batch = comm.global_batch((
        rng.randn(n, *image).astype(np.float32),
        rng.randint(0, width["num_classes"], size=n).astype(np.int32),
    ))
    _, _, report = _train_phase(
        comm, step, (params, state, batch_stats), batch,
        lambda out: (out[:3], out[3]),
    )
    return report


def lm_serve(width):
    """One replica on one chip answers ``requests`` greedy requests, twice
    over: the second, identical pass must add no compilation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.observability import startup
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler,
        EngineConfig,
        InferenceEngine,
        SamplingParams,
        ServeFrontend,
    )

    model = TransformerLM(
        vocab=width["vocab"], d_model=width["d_model"],
        n_heads=width["n_heads"], d_ff=width["d_ff"],
        n_layers=width["n_layers"], max_len=width["max_len"],
    )
    with startup.phase("weights"):
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )
    engine = InferenceEngine(model, params, EngineConfig(
        block_size=width["block_size"], n_blocks=width["n_blocks"],
        max_len=width["max_len"], max_batch=width["max_batch"],
    ))
    frontend = ServeFrontend(
        ContinuousBatchingScheduler(engine), max_queue=width["requests"] + 1
    )
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(0, width["vocab"], size=width["prompt_len"]).tolist()
        for _ in range(width["requests"])
    ]
    N = width["new_tokens"]

    def compiled():
        stats = engine.stats()
        return {k: stats[f"{k}_jit_cache_size"]
                for k in ("prefill", "decode", "chunk")}

    def serve_all():
        stamps = {}

        def on_token(request_id, token):
            stamps.setdefault(request_id, []).append(time.perf_counter())

        t0 = time.perf_counter()
        handles = [
            frontend.submit(p, N, sampling=SamplingParams(),
                            on_token=on_token)
            for p in prompts
        ]
        frontend.run_until_idle()
        wall = time.perf_counter() - t0
        for h in handles:
            check(h.status == "finished",
                  f"request {h.request_id}: {h.status} ({h.error})")
            check(len(h.tokens) == N
                  and all(0 <= t < width["vocab"] for t in h.tokens),
                  f"request {h.request_id}: bad stream {h.tokens}")
        gaps = [b - a for ts in stamps.values() for a, b in zip(ts, ts[1:])]
        return wall, gaps, [h.tokens for h in handles]

    first_wall, _, first_streams = serve_all()
    after_first = compiled()
    # Same state as before the first pass: with the prefix index kept,
    # the same prompts would take the prefix-hit path, another program.
    engine.reset()
    wall, gaps, streams = serve_all()
    check(compiled() == after_first,
          f"the second pass compiled: {after_first} -> {compiled()}")
    check(streams == first_streams,
          "the two identical greedy passes produced different streams")
    return {
        # What the first pass spent beyond a warm one: compilation.
        "compile_s": round(first_wall - wall, 2),
        "first_pass_s": round(first_wall, 2),
        "second_pass_s": round(wall, 2),
        "median_token_ms": round(statistics.median(gaps) * 1e3, 2),
        "finished": len(streams),
        "compiled_programs": after_first,
    }


def verdict(devices):
    """The last stdout line: exactly these keys, the device as JAX
    reports it.  Reached only when every phase passed."""
    return {
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }


def main():
    require_tpu()

    import jax
    import jaxlib
    from importlib.metadata import version

    from chainermn_tpu.utils.profiling import setup_compilation_cache

    cache_dir = setup_compilation_cache()
    devices = jax.devices()
    phases = {}
    for name, phase, width in (
        ("lm_train", lm_train, LM_FULL),
        ("resnet50_train", resnet50_train, RESNET_FULL),
        ("lm_serve", lm_serve, SERVE_FULL),
    ):
        log(f"{name} ...")
        phases[name] = phase(width)
        log(f"{name} ok: {phases[name]}")
    print(json.dumps({
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": version("libtpu"),
        },
        "cache_dir": cache_dir,
        "phases": phases,
        "claim": None,
    }))
    print(json.dumps(verdict(devices)), flush=True)


if __name__ == "__main__":
    main()
