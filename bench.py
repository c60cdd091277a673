#!/usr/bin/env python
"""Headline benchmarks, printed as ONE JSON line.

Two flagships, both FULL training steps on every TPU chip this process
sees (a device kind without a peak in ``PEAK_BF16_FLOPS`` is an error —
there is no MFU of a CPU):

* **ResNet-50 ImageNet-shape** (the reference's own headline): forward,
  backward, gradient allreduce via the xla_ici communicator, SGD+momentum,
  cross-replica BatchNorm sync — images/sec/chip, the metric BASELINE.json
  tracks.  ``vs_baseline``: the reference stack's public record is
  ResNet-50/ImageNet in 15 min on 1024 P100s (arXiv:1711.04325) → 1.28M
  images × 90 epochs / 900 s / 1024 chips ≈ 125 images/sec/chip.
* **Decoder-only transformer LM** (this framework's own kernels): flash
  attention (Pallas) + chunked fused cross-entropy (no materialized
  logits) + per-layer remat, bf16 compute, AdamW — tokens/sec/chip and
  model-FLOPs utilization against the chip's bf16 peak.  This is the
  number the long-context/sequence-parallel tier is built to move; the
  reference has no comparable headline, so its ``mfu`` IS the claim.

The headline line keeps the ResNet metric for baseline continuity and
embeds the LM result under ``"lm"``.  ``--only {resnet,lm}`` runs one.

``--pipeline`` measures the ResNet step fed by the REAL host input
pipeline — ``datasets.MultiprocessBatchLoader`` (worker processes
assembling batches into shared-memory slots) staged through
``create_prefetch_iterator`` (background device_put thread) — instead of
a resident synthetic batch, so the number includes host batch assembly
and host→device transfer overlapped with compute.
"""

import argparse
import contextlib
import json
import os
import time

import jax

from chainermn_tpu.utils.profiling import setup_compilation_cache

# Persistent compilation cache: these are big step programs (minutes to
# compile); the first run pays, reruns start in seconds.
setup_compilation_cache()

import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.utils.profiling import median_slope, sync

REFERENCE_IMAGES_PER_SEC_PER_CHIP = 125.0  # P100, ChainerMN pure_nccl era
_ROOT = os.path.dirname(os.path.abspath(__file__))


def _cpu_child_env(n_devices):
    """Environment for a CPU-only child process.  Its ``XLA_FLAGS`` is
    built from nothing but the device-count flag: a flag meant for this
    process's TPU compiler aborts a CPU backend at init."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env
# bf16 peak FLOP/s per chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).  A
# kind that is not here is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_bf16_flops():
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"no bf16 peak recorded for device kind {kind!r} (known: "
            f"{sorted(PEAK_BF16_FLOPS)}): the training benches report "
            "utilization of a chip and refuse to run without one"
        )
    return kind, PEAK_BF16_FLOPS[kind]


class SyntheticItems:
    """Picklable item source for the pipeline bench: 8 distinct base images
    keep host RAM small while every batch still pays the full per-batch
    assembly + transfer cost.  Module-level so the spawn-based loader
    workers can unpickle it."""

    def __init__(self, base, n, n_classes=1000):
        self.base = base
        self.n = n
        self.n_classes = n_classes

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self.base[i % len(self.base)], np.int32(i % self.n_classes)


def _compiled_flops_per_device(lowerable, *args):
    """Per-device executed FLOPs from XLA's cost model on the compiled
    step (post-SPMD-partitioned module)."""
    ca = lowerable.lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"])


def _allreduce_overlap(lowerable, *args):
    """Async-pair census of the compiled step (hlo_audit): how many
    collectives the compiler split into ``-start``/``-done`` pairs and
    what fraction have real compute scheduled between the two — the
    overlap the backward-staged schedule exists to expose.  Zeroes where
    the compiler emits no async pairs (one device, CPU)."""
    from chainermn_tpu.observability import audit_hlo_text

    audit = audit_hlo_text(lowerable.lower(*args).compile().as_text())
    return {
        "async_pairs": audit.async_pairs,
        "overlap_fraction": round(audit.overlap_fraction, 4),
    }


def _flagship_gauges(flagship: str, mfu, overlap_rec) -> None:
    """Publish the headline efficiency numbers as Reporter gauges so the
    tools.obs Prometheus path exports them next to the serving metrics
    (``bench/mfu/<flagship>``, ``bench/overlap_fraction/<flagship>``)."""
    from chainermn_tpu.observability import get_reporter

    rep = get_reporter()
    if rep is None:
        return
    if mfu is not None:
        rep.gauge(f"bench/mfu/{flagship}", float(mfu))
    if overlap_rec and overlap_rec.get("overlap_fraction") is not None:
        rep.gauge(f"bench/overlap_fraction/{flagship}",
                  float(overlap_rec["overlap_fraction"]))


def _plan_layout_report(plan_name, params):
    """Resolve a registry sharding plan against this bench's parameter
    tree and record the layout it assigns: per-rule leaf counts, the
    mesh axes the plan names, and how many leaves actually shard.  The
    flagship benches run the explicit-collective data plane, so the
    plan is recorded alongside the numbers, not applied to the step
    (``applied: false`` says exactly that in the JSON)."""
    from chainermn_tpu.sharding import get_plan, tree_path_str

    plan = get_plan(plan_name)
    rules = {}
    sharded = total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        total += 1
        p = tree_path_str(path)
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0:
            rules["<scalar>"] = rules.get("<scalar>", 0) + 1
            continue
        rule = plan.match(p, shape)
        name = rule.name if rule else "<UNMATCHED>"
        rules[name] = rules.get(name, 0) + 1
        if rule and any(ax is not None for ax in tuple(rule.spec)):
            sharded += 1
    return {
        "axes": list(plan.axes),
        "rules": rules,
        "sharded_leaves": sharded,
        "total_leaves": total,
        "applied": False,
    }


def bench_resnet(comm, args):
    from chainermn_tpu.models.resnet import ResNet50

    device_kind, peak = _peak_bf16_flops()
    n_dev = comm.device_size
    # 256/chip: measured optimum on a v5e-class chip (slope-timed r2:
    # 256→2638, 512→2448 img/s; the r1 sweep's 64→1908, 128→2206 low end
    # stands).
    per_chip_batch = args.per_chip_batch
    global_batch = per_chip_batch * n_dev
    image = (224, 224, 3)

    model = ResNet50(num_classes=1000)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *image), jnp.float32), train=True
    )
    params, batch_stats = variables["params"], variables["batch_stats"]

    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm
    )
    state = opt.init(params)

    def loss_fn(params, batch_stats, batch):
        x, y = batch
        if x.dtype == jnp.uint8:
            # On-device decode: the uint8-wire mode ships raw bytes
            # (4x less host->device traffic than fp32) and normalizes
            # on-chip — the standard image-input recipe when the feed
            # link, not compute, is the bottleneck.
            x = x.astype(jnp.bfloat16) / 127.5 - 1.0
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats},
            x, train=True, mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return loss, updates["batch_stats"]

    step = opt.make_train_step_with_state(loss_fn, donate=True)

    rng = np.random.RandomState(0)

    def synth_images(n):
        if args.input_dtype == "uint8":
            return rng.randint(0, 256, size=(n, *image), dtype=np.uint8)
        return rng.randn(n, *image).astype(np.dtype(args.input_dtype))

    if args.pipeline:
        # The resident batch would only serve the lowering below; shapes
        # and dtypes are all the lowering needs.
        x = jax.ShapeDtypeStruct(
            (global_batch, *image), jnp.dtype(args.input_dtype)
        )
        y = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    else:
        # Placed with the world sharding once, not re-sharded off the
        # first chip at every step.
        x, y = comm.global_batch((
            synth_images(global_batch),
            rng.randint(0, 1000, size=global_batch).astype(np.int32),
        ))

    batch_source = None
    loader = None
    if args.pipeline:
        # Real host pipeline: worker PROCESSES assemble each batch into
        # shared-memory slots (datasets.MultiprocessBatchLoader — the
        # reference ImageNet example's MultiprocessIterator role), and the
        # prefetch thread stages slots to the device.  copy=True: the
        # prefetch thread's device_put is async (and on the CPU backend it
        # zero-copy ALIASES the source buffer), so handing it recyclable
        # slot views would corrupt in-flight batches; the fresh-array copy
        # is the honest cost of a real pipeline, as Chainer's
        # MultiprocessIterator also returned fresh arrays.
        from chainermn_tpu.datasets.multiprocess_iterator import (
            MultiprocessBatchLoader,
        )
        from chainermn_tpu.iterators import create_prefetch_iterator

        base = synth_images(8)
        loader = MultiprocessBatchLoader(
            SyntheticItems(base, global_batch * 4),
            global_batch,
            n_workers=args.loader_workers,
            shuffle=False,
            repeat=True,
        )
        # close_join_timeout=None: teardown must WAIT for the producer
        # thread (the loader's next() is bounded), because loader.close()
        # unmaps the shared-memory slots the producer may still be copying.
        batch_source = create_prefetch_iterator(
            iter(loader), size=2, close_join_timeout=None
        )

    # Model FLOPs for MFU — PER-DEVICE convention throughout: XLA's cost
    # model on the compiled step reports the post-SPMD-partitioned
    # (per-device) module (~23.9 GFLOP/image at batch 256, consistent
    # with the analytic ~3x4.1 GMACs/image incl. backward + update).
    # Lowering the jitted `step` itself (not a fresh wrapper) reuses the
    # same executable-cache entry the timed loop runs.
    step_flops_per_dev = _compiled_flops_per_device(
        step, params, state, batch_stats, (x, y)
    )

    def next_batch():
        if batch_source is None:
            return (x, y)
        return next(batch_source)

    # Warmup (compile + stabilize).  Each step consumes the previous
    # step's (donated) params, so sync()'s readback of the last loss
    # transitively waits for the whole timed chain.
    for _ in range(3):
        params, state, batch_stats, loss = step(
            params, state, batch_stats, next_batch()
        )
    sync(loss)

    def run(n):
        nonlocal params, state, batch_stats
        t0 = time.perf_counter()
        for _ in range(n):
            params, state, batch_stats, loss = step(
                params, state, batch_stats, next_batch()
            )
        sync(loss)
        return time.perf_counter() - t0

    step_time, samples = median_slope(run)
    ips_samples = sorted(
        (per_chip_batch / s for s in samples), reverse=True
    )

    per_chip = per_chip_batch / step_time
    mfu = step_flops_per_dev / step_time / peak
    if loader is not None:
        # Stop the prefetch producer thread FIRST (its generator close
        # joins the thread — unbounded, see close_join_timeout above), so
        # loader.close() never races an active iteration.
        batch_source.close()
        loader.close()
    metric = "images/sec/chip ResNet-50 ImageNet train step"
    if args.pipeline:
        metric += " (host pipeline)"
    overlap_rec = _allreduce_overlap(
        step, params, state, batch_stats, (x, y)
    )
    _flagship_gauges("resnet", mfu, overlap_rec)
    result = {
        "metric": metric,
        "overlap": comm.resolve_overlap(),
        "allreduce_overlap": overlap_rec,
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / REFERENCE_IMAGES_PER_SEC_PER_CHIP, 3),
        "device_kind": device_kind,
        "mfu": round(mfu, 4),
        "model_tflops_per_sec_per_chip": round(
            step_flops_per_dev / step_time / 1e12, 2
        ),
        "runs_img_per_sec": [round(v, 1) for v in ips_samples],
        "spread_pct": round(
            100.0 * (ips_samples[0] - ips_samples[-1]) / ips_samples[-1], 1
        ),
    }
    if comm.resolve_comm_dtype() is not None:
        # The images/sec above were measured over the quantized wire;
        # the full A/B (baseline rerun + measured error) lives in the
        # LM bench — here we just label the number so it is never
        # mistaken for a full-precision-wire measurement.
        result["comm_dtype"] = comm.resolve_comm_dtype()
    if args.plan:
        result["plan"] = args.plan
        result["plan_layout"] = _plan_layout_report(args.plan, params)
    return result


def bench_lm(comm, args):
    """Decoder-only LM train step: flash attention + fused CE + remat,
    AdamW, bf16 compute with fp32 params.  Per-chip batch x S tokens."""
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.ops import make_flash_attention_fn
    from chainermn_tpu.ops.fused_ce import fused_cross_entropy

    device_kind, peak = _peak_bf16_flops()
    n_dev = comm.device_size
    B, S = args.lm_batch, args.lm_seq
    cfg = dict(
        vocab=args.lm_vocab, d_model=args.lm_d_model,
        n_heads=args.lm_heads, d_ff=args.lm_d_ff,
        n_layers=args.lm_layers, max_len=S,
    )
    use_remat = args.lm_remat

    model = TransformerLM(
        **cfg, remat=use_remat,
        attention_fn=make_flash_attention_fn(
            causal=True, window=args.lm_window
        ),
    )
    rng = np.random.RandomState(0)
    tokens, labels = comm.global_batch(tuple(
        rng.randint(0, cfg["vocab"], size=(B * n_dev, S)).astype(np.int32)
        for _ in range(2)
    ))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, S), jnp.int32)
    )["params"]
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adamw(3e-4, weight_decay=0.1), comm
    )
    state = opt.init(params)

    def loss_fn(p, batch):
        toks, labs = batch
        h = model.apply({"params": p}, toks, return_hidden=True)
        return fused_cross_entropy(
            h, p["embed"]["embedding"], labs, chunk=args.lm_ce_chunk
        )

    step = opt.make_train_step(loss_fn, donate=True)

    # MODEL FLOPs (the Megatron MFU convention — excludes remat
    # recompute): 6 * n_params per token (2 fwd + 4 bwd) plus attention
    # 12 * span_avg * d per token per layer (QK^T + AV = 4*span*d fwd,
    # backward 2x forward), where span_avg is the MEAN number of keys a
    # query attends (each query sees min(i+1, W) keys, self inclusive):
    # mean over i of i+1 = (S+1)/2 for full causal, and exactly
    # W - W(W-1)/(2S) for a width-W sliding window (the first W-1
    # queries see fewer than W keys; summing the ramp gives the W(W-1)/2
    # deficit).  Full causal is exactly the W = S specialization.
    if args.lm_window:
        W = min(S, args.lm_window)
        span_avg = W - W * (W - 1) / (2.0 * S)
    else:
        span_avg = (S + 1) / 2.0
    model_flops = B * S * (
        6.0 * n_params
        + 12.0 * span_avg * cfg["d_model"] * cfg["n_layers"]
    )
    # EXECUTED FLOPs from XLA's cost model on the compiled step —
    # includes the remat recompute, so it measures hardware utilization
    # rather than model efficiency.
    step_flops_per_dev = _compiled_flops_per_device(
        step, params, state, (tokens, labels)
    )

    for _ in range(3):
        params, state, loss = step(params, state, (tokens, labels))
    sync(loss)

    def run(n):
        nonlocal params, state
        t0 = time.perf_counter()
        for _ in range(n):
            params, state, loss = step(params, state, (tokens, labels))
        sync(loss)
        return time.perf_counter() - t0

    step_time, samples = median_slope(run)
    tok_per_chip = B * S / step_time
    mfu = model_flops / step_time / peak
    hw_util = step_flops_per_dev / step_time / peak
    overlap_rec = _allreduce_overlap(
        step, params, state, (tokens, labels)
    )
    _flagship_gauges("lm", mfu, overlap_rec)
    result = {
        "metric": "tokens/sec/chip decoder-LM train step "
                  "(flash attention + fused CE"
                  + (" + remat" if use_remat else "") + ", AdamW)",
        "overlap": comm.resolve_overlap(),
        "allreduce_overlap": overlap_rec,
        "value": round(tok_per_chip, 1),
        "unit": "tokens/sec/chip",
        "device_kind": device_kind,
        "mfu": round(mfu, 4),
        "hw_flops_utilization": round(hw_util, 4),
        "model_tflops_per_sec_per_chip": round(
            model_flops / step_time / 1e12, 2
        ),
        "executed_tflops_per_sec_per_chip": round(
            step_flops_per_dev / step_time / 1e12, 2
        ),
        "params_millions": round(n_params / 1e6, 1),
        "config": {**cfg, "per_chip_batch": B, "remat": use_remat,
                   "window": args.lm_window, "optimizer": "adamw"},
        "runs_tok_per_sec": [
            round(B * S / s, 1) for s in sorted(samples)
        ],
        "spread_pct": round(
            100.0 * (max(samples) - min(samples)) / min(samples), 1
        ),
    }
    if comm.resolve_comm_dtype() is not None:
        # --comm-dtype A/B: same model, same traffic, a second optimizer
        # over a full-precision-wire communicator; the measured
        # quantization error (max |quantized - fp32 allreduce| over the
        # live param tree) rides along so the speedup is never quoted
        # without its accuracy cost.  Runs only when the wire actually
        # resolves quantized, so the default output shape is untouched.
        from chainermn_tpu.communicators import quant as quant_mod

        quant_err = quant_mod.measure_comm_quant_error(comm, params)
        base_comm = chainermn_tpu.create_communicator(
            "xla_ici", overlap=False if args.no_overlap else None,
            comm_dtype="none",
        )
        base_opt = chainermn_tpu.create_multi_node_optimizer(
            optax.adamw(3e-4, weight_decay=0.1), base_comm
        )
        base_state = base_opt.init(params)
        base_step = base_opt.make_train_step(loss_fn, donate=True)
        bparams = params
        for _ in range(3):
            bparams, base_state, loss = base_step(
                bparams, base_state, (tokens, labels))
        sync(loss)

        def run_base(n):
            nonlocal bparams, base_state
            t0 = time.perf_counter()
            for _ in range(n):
                bparams, base_state, loss = base_step(
                    bparams, base_state, (tokens, labels))
            sync(loss)
            return time.perf_counter() - t0

        base_time, _ = median_slope(run_base)
        result["comm_dtype"] = {
            "wire": comm.resolve_comm_dtype(),
            "step_time_ms": round(step_time * 1e3, 3),
            "full_precision_step_time_ms": round(base_time * 1e3, 3),
            "tokens_per_sec_per_chip": round(tok_per_chip, 1),
            "full_precision_tokens_per_sec_per_chip": round(
                B * S / base_time, 1),
            "speedup": round(base_time / step_time, 3),
            "quant_abs_err": quant_err,
        }
    if args.plan:
        result["plan"] = args.plan
        result["plan_layout"] = _plan_layout_report(args.plan, params)
    return result


def bench_serve(comm, args):
    """Decode throughput through the serving stack: synthetic request
    traffic into the queue frontend, continuous-batched decode via the
    scheduler, tokens/sec and per-token latency percentiles per decode
    batch size.  Greedy sampling (the RNG never runs) so the measured
    path is exactly the jitted prefill/decode data plane.

    Unlike the train benches this sweep is host-loop inclusive by
    design: serving throughput IS prefill+decode+scheduling, and the
    per-token p50/p99 spread is the continuous-batching story (token
    gaps stay flat as the batch grows until the decode step saturates).
    """
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler,
        EngineConfig,
        InferenceEngine,
        QueueFull,
        SamplingParams,
        ServeFrontend,
    )
    from chainermn_tpu.models.transformer import TransformerLM

    cfg = dict(
        vocab=args.lm_vocab, d_model=args.lm_d_model,
        n_heads=args.lm_heads, d_ff=args.lm_d_ff,
        n_layers=args.lm_layers, max_len=args.serve_max_len,
    )
    model = TransformerLM(**cfg)
    rng = np.random.RandomState(0)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )

    P, N = args.serve_prompt_len, args.serve_new_tokens
    dup = min(max(args.serve_prefix_dup, 0.0), 1.0)
    # --serve-prefix-dup D: the leading D-fraction of every prompt is a
    # shared template (the few-shot-system-prompt workload the prefix
    # cache exists for); 0 keeps every prompt fully random.
    shared = rng.randint(0, cfg["vocab"], size=int(P * dup)).tolist()
    prompts = [
        shared + rng.randint(0, cfg["vocab"],
                             size=P - len(shared)).tolist()
        for _ in range(args.serve_requests)
    ]
    batch_sizes = [int(b) for b in args.serve_batch_sizes.split(",")]
    if args.serve_queue is None:
        # default: every synthetic request fits — the sweep measures
        # decode, not admission backpressure
        args.serve_queue = len(prompts) + 1

    sweep = []
    for bs in batch_sizes:
        # A/B at every sweep point: speculative decoding ON vs OFF on
        # identical traffic (greedy, so the streams are bit-identical —
        # only the wall clock may differ).
        on = _serve_sweep_point(args, model, params, prompts, bs,
                                spec_tokens=args.serve_spec_tokens)
        off = _serve_sweep_point(args, model, params, prompts, bs,
                                 spec_tokens=0)
        on["tokens_per_sec_no_spec"] = off["tokens_per_sec"]
        on["p99_no_spec_ms"] = off["p99_token_latency_ms"]
        sweep.append(on)

    best = max(sweep, key=lambda r: r["tokens_per_sec"])
    out = {
        "metric": "decode tokens/sec, continuous-batched serving "
                  "(paged KV + jitted decode)",
        "value": best["tokens_per_sec"],
        "unit": "tokens/sec",
        "trace": _bench_serve_traced(args, model, params, best,
                                     prompts),
        "best_batch_size": best["batch_size"],
        "config": {**cfg, "prompt_len": P, "new_tokens": N,
                   "n_requests": args.serve_requests,
                   "block_size": args.serve_block_size,
                   "n_blocks": args.serve_blocks,
                   "max_queue": args.serve_queue,
                   "prefix_dup": dup,
                   "spec_tokens": args.serve_spec_tokens},
        "sweep": sweep,
    }
    if dup > 0:
        # The acceptance number for prefix sharing: same traffic, same
        # batch size, prefix cache disabled — the sharing speedup is
        # value / baseline.
        base = _serve_sweep_point(
            args, model, params, prompts, best["batch_size"],
            spec_tokens=args.serve_spec_tokens, prefix_cache=False,
        )
        out["no_sharing_baseline"] = {
            "tokens_per_sec": base["tokens_per_sec"],
            "p99_token_latency_ms": base["p99_token_latency_ms"],
            "speedup": round(
                best["tokens_per_sec"]
                / max(base["tokens_per_sec"], 1e-9), 3),
        }
    if args.serve_draft:
        out["draft_ab"] = _serve_draft_ab(args, model, params, prompts,
                                          best)
    if args.serve_prefill_chunk > 0:
        out["prefill_chunk"] = _serve_prefill_chunk_ab(
            args, model, params, best)
    if args.kv_dtype:
        from chainermn_tpu.communicators.quant import canonical_kv_dtype

        kd = canonical_kv_dtype(args.kv_dtype)
        if kd is not None:
            out["kv_dtype"] = _serve_kv_ab(args, model, params, prompts,
                                           best, kd)
    if args.serve_tp:
        out["tp"] = _serve_tp_bench(args, model, params, prompts, best)
    if args.serve_replicas > 1:
        out["cluster"] = bench_serve_cluster(args, model, params)
    if args.serve_traffic:
        out["traffic"] = _serve_traffic_bench(args)
    if args.serve_long_context:
        out["long_context"] = _serve_long_context_bench(args)
    return out


def _serve_sweep_point(args, model, params, prompts, bs, *,
                       spec_tokens, prefix_cache=True, kv_dtype=None,
                       draft=None, draft_layers=None, tp=1):
    """One measured serving run: fresh engine at decode batch ``bs``,
    all ``prompts`` through the queue frontend, tokens/sec plus
    per-token latency percentiles and the prefix/speculation counters.
    With ``tp`` > 1 the engine's params and KV pages are committed
    through the registry ``tp`` plan over that many local devices, so
    the jitted data plane runs GSPMD tensor-parallel.
    """
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler,
        EngineConfig,
        InferenceEngine,
        QueueFull,
        SamplingParams,
        ServeFrontend,
    )

    N = args.serve_new_tokens
    ecfg = EngineConfig(
        block_size=args.serve_block_size,
        n_blocks=args.serve_blocks,
        max_len=args.serve_max_len,
        max_batch=bs,
        prefix_cache=prefix_cache,
        kv_dtype=kv_dtype,
        draft=draft,
        draft_layers=draft_layers,
    )
    plan = mesh = None
    if tp > 1:
        from jax.sharding import Mesh

        plan = "tp"
        mesh = Mesh(np.asarray(jax.devices()[:tp]), ("model",))
    engine = InferenceEngine(model, params, ecfg, plan=plan, mesh=mesh)
    sched = ContinuousBatchingScheduler(engine, spec_tokens=spec_tokens)
    fe = ServeFrontend(sched, max_queue=args.serve_queue)

    # warmup: compile the buckets this sweep point will touch (and,
    # with sharing on, seed the prefix index the way a warm replica is)
    fe.submit(prompts[0], N, sampling=SamplingParams())
    fe.run_until_idle()

    stamps = {}  # request_id -> [perf_counter per token]

    def on_token(rid, tok, _s=stamps):
        _s.setdefault(rid, []).append(time.perf_counter())

    handles = []
    t0 = time.perf_counter()
    for p in prompts:
        while True:
            try:
                handles.append(
                    fe.submit(p, N, sampling=SamplingParams(),
                              on_token=on_token)
                )
                break
            except QueueFull:
                # bounded --serve-queue: drain by stepping (the
                # bench IS the only driver; sleeping would just
                # stall the engine the hint is waiting on)
                fe.step()
    fe.run_until_idle()
    wall = time.perf_counter() - t0

    total_tokens = sum(len(h.tokens) for h in handles)
    gaps = []
    for ts in stamps.values():
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    gaps.sort()

    def pct(q):
        if not gaps:
            return None
        return gaps[min(len(gaps) - 1, int(q * len(gaps)))]

    st = engine.stats()
    res = sched.results()
    row = {
        "batch_size": bs,
        "tokens_per_sec": round(total_tokens / wall, 1),
        "p50_token_latency_ms": round(pct(0.50) * 1e3, 3)
        if gaps else None,
        "p99_token_latency_ms": round(pct(0.99) * 1e3, 3)
        if gaps else None,
        "requests": len(handles),
        "finished": sum(1 for h in handles
                        if h.status == "finished"),
        "preemptions": sum(r.preemptions for r in res.values()),
        "prefill_compiles": st["prefill_compiles"],
        "decode_compiles": st["decode_compiles"],
        "chunk_compiles": st["chunk_compiles"],
        "spec_tokens": spec_tokens,
        "prefix_cache": prefix_cache,
        "tokens_prefix_cached": st["tokens_prefix_cached"],
        "cow_splits": st["cow_splits"],
    }
    if sched._prefix_lookup_tokens:
        row["prefix_hit_rate"] = round(
            sched._prefix_hit_tokens / sched._prefix_lookup_tokens, 4)
    if sched._spec_rows:
        row["spec_accept_len"] = round(
            sched._spec_emitted / sched._spec_rows, 3)
    if draft is not None:
        row["draft_source"] = engine.draft_source
    if "kv_quant_err" in st:
        row["kv_dtype"] = st["kv_dtype"]
        row["kv_quant_err"] = st["kv_quant_err"]
    if tp > 1:
        row["group_size"] = tp
    return row


def _serve_tp_bench(args, model, params, prompts, best):
    """``--serve-tp``: decode tokens/sec versus tensor-parallel group
    size at the winning batch size — the scaling curve behind the
    shard-group design (``docs/serving.md``).  Each point reruns the
    identical greedy traffic with the engine's params and KV pages
    committed through the registry ``tp`` plan over K local devices
    (K=1 is the unsharded baseline).  Sizes that don't divide the
    model's heads/FFN or exceed the local device count are skipped and
    reported, never silently dropped."""
    sizes = [int(k) for k in args.serve_tp_sizes.split(",")]
    n_dev = len(jax.devices())
    curve, skipped = [], []
    for k in sizes:
        if (k > n_dev or args.lm_heads % k
                or args.lm_d_ff % k or args.lm_d_model % k):
            skipped.append({"group_size": k, "reason": (
                "exceeds local device count" if k > n_dev
                else "does not divide model geometry")})
            continue
        row = _serve_sweep_point(args, model, params, prompts,
                                 best["batch_size"], spec_tokens=0,
                                 tp=k)
        row["group_size"] = k
        curve.append(row)
    base = next((r for r in curve if r["group_size"] == 1), None)
    if base is not None:
        for r in curve:
            r["speedup"] = round(
                r["tokens_per_sec"]
                / max(base["tokens_per_sec"], 1e-9), 3)
    return {"devices": n_dev, "batch_size": best["batch_size"],
            "curve": curve, "skipped": skipped}


def _serve_draft_ab(args, model, params, prompts, best):
    """--serve-draft: both speculative draft sources at the winning
    batch size, identical traffic.  Exact-match acceptance pins the
    streams identical across the pair; what differs is the accept
    length (tokens banked per verify row) and the wall clock — the
    draft choice is a pure throughput decision."""
    spec = max(1, args.serve_spec_tokens)
    bs = best["batch_size"]
    rows = []
    for src in ("ngram", "model"):
        row = _serve_sweep_point(
            args, model, params, prompts, bs, spec_tokens=spec,
            draft=src, draft_layers=args.serve_draft_layers,
        )
        rows.append(row)
    by = {r["draft_source"]: r for r in rows}
    return {
        "spec_tokens": spec,
        "batch_size": bs,
        "rows": rows,
        "accept_len": {
            s: by[s].get("spec_accept_len") for s in by
        },
        "tokens_per_sec": {
            s: by[s]["tokens_per_sec"] for s in by
        },
    }


def _serve_prefill_chunk_ab(args, model, params, best):
    """--serve-prefill-chunk N: the decode-p99 story chunked prefill
    exists for.  Short requests stream while one near-budget prompt
    arrives mid-flight; monolithic prefill charges the whole prompt to
    a single scheduler step (every streaming request stalls behind it),
    chunked prefill slices it between decode steps.  Reported: the
    short requests' token-gap p99/max, sliced vs monolithic, same
    traffic (streams identical either way — chunking only re-times the
    prefill work)."""
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler,
        EngineConfig,
        InferenceEngine,
        SamplingParams,
        ServeFrontend,
    )

    N = args.serve_new_tokens
    n_short = max(2, best["batch_size"])
    rng = np.random.RandomState(7)
    long_len = min(args.serve_max_len - N - 1,
                   args.serve_prompt_len * 8)
    shorts = [
        rng.randint(0, args.lm_vocab,
                    size=args.serve_prompt_len).tolist()
        for _ in range(n_short)
    ]
    long_prompt = rng.randint(0, args.lm_vocab, size=long_len).tolist()

    def one(chunk):
        ecfg = EngineConfig(
            block_size=args.serve_block_size,
            n_blocks=args.serve_blocks,
            max_len=args.serve_max_len,
            max_batch=n_short + 1,
            prefix_cache=False,
            prefill_chunk=chunk,
        )
        engine = InferenceEngine(model, params, ecfg)
        sched = ContinuousBatchingScheduler(engine)
        fe = ServeFrontend(sched, max_queue=n_short + 2)

        def workload():
            stamps = {}

            def on_token(rid, tok, _s=stamps):
                _s.setdefault(rid, []).append(time.perf_counter())

            for p in shorts:
                fe.submit(p, N, sampling=SamplingParams(),
                          on_token=on_token)
            for _ in range(3):  # decode cadence established first
                fe.step()
            fe.submit(long_prompt, 4, sampling=SamplingParams())
            fe.run_until_idle()
            gaps = []
            for ts in stamps.values():
                gaps.extend(b - a for a, b in zip(ts, ts[1:]))
            gaps.sort()
            return gaps

        workload()  # warm: compile every bucket this shape touches
        gaps = workload()
        if not gaps:
            return {"p99_ms": None, "max_ms": None}
        p99 = gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))]
        return {
            "p99_ms": round(p99 * 1e3, 3),
            "max_ms": round(gaps[-1] * 1e3, 3),
        }

    chunked = one(args.serve_prefill_chunk)
    mono = one(0)
    return {
        "chunk_tokens": args.serve_prefill_chunk,
        "long_prompt_len": long_len,
        "short_requests": n_short,
        "chunked": chunked,
        "monolithic": mono,
        "p99_improvement": (
            round(mono["p99_ms"] / chunked["p99_ms"], 3)
            if chunked["p99_ms"] and mono["p99_ms"] else None
        ),
    }


def _serve_long_context_bench(args):
    """``--serve-long-context``: the giant-prompt serving story.

    Three measurements, one JSON blob:

    * **p99 vs prompt length** — per-token gap p99 and time-to-first-
      token at each ``--serve-long-lens`` point, chunked prefill on, so
      the curve shows decode latency staying flat while prompts grow
      through lazily-added buckets (``bucket_growths`` is reported per
      point — no fleet-wide recompile, just one new program per rung).
    * **streaming-registration A/B** — two interleaved requests over
      ONE shared document.  With ``stream_prefix`` on, the second
      request adopts the slices the first already published mid-prefill
      and computes only the unregistered suffix; with it off it
      recomputes the whole document.  Reported: prefill slices
      computed, ``dup_prefill_slices``, and ``stream_hit_tokens`` for
      both arms — the acceptance bar is ON strictly below OFF on both
      slice counts.
    * **oracle parity** — the interleaved shared-document streams match
      a fresh single-request engine bit-for-bit under greedy AND
      temperature/top-k sampling, including a run where the second
      request is preempted mid-prefill and replays through the
      streamed pages.

    Defaults are CPU-sane (hundreds of tokens); the real 100k story is
    the same code path with ``--serve-long-lens 32768,65536,98304``.
    """
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler,
        EngineConfig,
        InferenceEngine,
        SamplingParams,
        ServeFrontend,
    )

    lens = sorted(int(x) for x in args.serve_long_lens.split(","))
    N = min(args.serve_new_tokens, 8)  # decode length is not the story
    bs = args.serve_block_size
    chunk = (args.serve_prefill_chunk if args.serve_prefill_chunk > 0
             else max(2 * bs, 16))
    D = lens[-1]
    max_len = max(args.serve_max_len, D + N + 1)
    pages_per_seq = -(-(D + N) // bs)
    n_blocks = max(args.serve_blocks, 2 * pages_per_seq + 8)

    model = TransformerLM(
        vocab=args.lm_vocab, d_model=args.lm_d_model,
        n_heads=args.lm_heads, d_ff=args.lm_d_ff,
        n_layers=args.lm_layers, max_len=max_len,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    rng = np.random.RandomState(11)
    doc = rng.randint(0, args.lm_vocab, size=D).tolist()

    def make_stack(*, stream, max_batch=2):
        ecfg = EngineConfig(
            block_size=bs, n_blocks=n_blocks, max_len=max_len,
            max_batch=max_batch, prefill_chunk=chunk,
        )
        engine = InferenceEngine(model, params, ecfg)
        sched = ContinuousBatchingScheduler(engine,
                                            stream_prefix=stream)
        fe = ServeFrontend(sched, max_queue=max_batch + 2)
        return engine, sched, fe

    # -- p99 vs prompt length -----------------------------------------
    curve = []
    for L in lens:
        engine, sched, fe = make_stack(stream=True)
        prompts = [rng.randint(0, args.lm_vocab, size=L).tolist()
                   for _ in range(2)]

        def run_point():
            stamps = {}
            submit_t = {}

            def on_token(rid, tok, _s=stamps):
                _s.setdefault(rid, []).append(time.perf_counter())

            for p in prompts:
                h = fe.submit(p, N, sampling=SamplingParams(),
                              on_token=on_token)
                submit_t[h.request_id] = time.perf_counter()
            fe.run_until_idle()
            gaps, ttfts = [], []
            for rid, ts in stamps.items():
                ttfts.append(ts[0] - submit_t[rid])
                gaps.extend(b - a for a, b in zip(ts, ts[1:]))
            gaps.sort()
            return gaps, ttfts

        run_point()  # warm: compile this length's buckets
        gaps, ttfts = run_point()
        st = engine.stats()
        p99 = (gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))]
               if gaps else None)
        curve.append({
            "prompt_len": L,
            "p99_token_gap_ms": round(p99 * 1e3, 3) if p99 else None,
            "ttft_ms": round(max(ttfts) * 1e3, 3) if ttfts else None,
            "bucket_growths": st.get("bucket_growths", 0),
            "chunk_compiles": st["chunk_compiles"],
        })

    # -- streaming-registration A/B over one shared document ----------
    def shared_doc_run(stream, *, sampling=None, preempt=False):
        engine, sched, fe = make_stack(stream=stream)
        slices = [0]
        real_chunk = engine.chunk

        def spy(token_rows, seq_ids, start_lens, *a, **k):
            slices[0] += sum(1 for s in start_lens if int(s) >= 0)
            return real_chunk(token_rows, seq_ids, start_lens, *a, **k)

        engine.chunk = spy
        try:
            sp = sampling or SamplingParams()
            ha = fe.submit(doc, N, sampling=sp)
            for _ in range(3):  # first request gets a few slices in
                fe.step()
            hb = fe.submit(doc, N, sampling=sp)
            if preempt:
                fe.step()
                sched._preempt_one()
            fe.run_until_idle()
        finally:
            engine.chunk = real_chunk
        return {
            "prefill_slices": slices[0],
            "dup_prefill_slices": sched._dup_prefill_slices,
            "stream_hit_tokens": sched._stream_hit_tokens,
            "tokens": (list(ha.tokens), list(hb.tokens)),
        }

    def oracle(sampling):
        engine, sched, fe = make_stack(stream=False, max_batch=1)
        h = fe.submit(doc, N, sampling=sampling)
        fe.run_until_idle()
        return list(h.tokens)

    on = shared_doc_run(True)
    off = shared_doc_run(False)
    ab = {
        "doc_len": D,
        "chunk_tokens": chunk,
        "streaming": {k: on[k] for k in
                      ("prefill_slices", "dup_prefill_slices",
                       "stream_hit_tokens")},
        "no_streaming": {k: off[k] for k in
                         ("prefill_slices", "dup_prefill_slices",
                          "stream_hit_tokens")},
        "dup_slices_reduced": (on["dup_prefill_slices"]
                               < off["dup_prefill_slices"]),
        "slices_reduced": (on["prefill_slices"]
                           < off["prefill_slices"]),
    }

    # -- oracle parity -------------------------------------------------
    greedy = SamplingParams()
    sampled = SamplingParams(temperature=0.8, top_k=8, seed=123)
    og, os_ = oracle(greedy), oracle(sampled)
    pre = shared_doc_run(True, preempt=True)
    samp = shared_doc_run(True, sampling=sampled)
    parity = {
        "greedy": "ok" if on["tokens"] == (og, og) else "FAIL",
        "sampled": "ok" if samp["tokens"] == (os_, os_) else "FAIL",
        "preempted_mid_prefill": (
            "ok" if pre["tokens"] == (og, og) else "FAIL"),
    }

    return {
        "p99_vs_prompt_len": curve,
        "shared_doc_ab": ab,
        "parity": parity,
        "config": {"block_size": bs, "n_blocks": n_blocks,
                   "max_len": max_len, "new_tokens": N,
                   "prompt_lens": lens},
    }


def _serve_kv_ab(args, model, params, prompts, best, kv_dtype):
    """--kv-dtype A/B at the winning batch size: quantized pages vs the
    full-precision run on identical traffic (tokens/s, p99, speculative
    accept length, and the measured per-element quantization error),
    plus the capacity point the narrow pages buy.

    The capacity point is computed from the engines' REAL page byte
    sizes, not a formula: at a fixed pool byte budget (the bytes the
    full-precision pool occupies), how many decode sequences of this
    workload's footprint (prompt + new tokens) fit?  int8 pages store
    one byte per element plus one f32 amax scale per token per KV head,
    so vs d-byte full-precision elements the ratio approaches
    d / (1 + 4 / d_head); at the bench default geometry (d_head 128)
    that is ~1.94x vs bf16 and ~3.9x vs fp32 pages.
    """
    from chainermn_tpu.serving import EngineConfig, InferenceEngine

    bs = best["batch_size"]

    def pool_bytes(kd):
        eng = InferenceEngine(model, params, EngineConfig(
            block_size=args.serve_block_size, n_blocks=args.serve_blocks,
            max_len=args.serve_max_len, max_batch=bs, kv_dtype=kd,
        ))
        return sum(l.nbytes for l in jax.tree.leaves(eng._cache))

    q = _serve_sweep_point(args, model, params, prompts, bs,
                           spec_tokens=args.serve_spec_tokens,
                           kv_dtype=kv_dtype)
    full_bytes = pool_bytes(None)
    quant_bytes = pool_bytes(kv_dtype)
    # Max admissible decode batch at the full-precision pool's byte
    # budget: every sequence pins ceil((P + N) / block_size) pages for
    # its whole lifetime, and narrow pages mean more pages in the pool.
    seq_tokens = args.serve_prompt_len + args.serve_new_tokens
    pages_per_seq = -(-seq_tokens // args.serve_block_size)
    quant_blocks = int(full_bytes * args.serve_blocks // quant_bytes)
    batch_full = args.serve_blocks // pages_per_seq
    batch_quant = quant_blocks // pages_per_seq
    rec = {
        "kv_dtype": kv_dtype,
        "batch_size": bs,
        "tokens_per_sec": q["tokens_per_sec"],
        "tokens_per_sec_full_precision": best["tokens_per_sec"],
        "p99_token_latency_ms": q["p99_token_latency_ms"],
        "p99_full_precision_ms": best["p99_token_latency_ms"],
        "kv_quant_err": q.get("kv_quant_err"),
        "capacity_at_fixed_pool_bytes": {
            "pool_bytes": full_bytes,
            "page_bytes_full_precision": round(
                full_bytes / args.serve_blocks, 1),
            "page_bytes_quantized": round(
                quant_bytes / args.serve_blocks, 1),
            "pages_per_sequence": pages_per_seq,
            "max_decode_batch_full_precision": batch_full,
            "max_decode_batch_quantized": batch_quant,
            "capacity_ratio": round(
                batch_quant / max(batch_full, 1), 3),
        },
    }
    # Speculative decoding drafts against quantized pages and verifies
    # against them too — the accept-length delta is the knock-on cost.
    if "spec_accept_len" in best or "spec_accept_len" in q:
        rec["spec_accept_len"] = q.get("spec_accept_len")
        rec["spec_accept_len_full_precision"] = best.get(
            "spec_accept_len")
        if (q.get("spec_accept_len") is not None
                and best.get("spec_accept_len") is not None):
            rec["spec_accept_len_delta"] = round(
                q["spec_accept_len"] - best["spec_accept_len"], 3)
    return rec


def _bench_serve_traced(args, model, params, best, prompts):
    """Rerun the winning sweep point with the request tracer installed:
    per-stage p50/p99 measured from real spans, plus the zero-overhead
    guard — the traced run must compile exactly as many prefill/decode
    buckets as the untraced one (tracing never touches jit inputs), and
    the throughput delta is reported so regressions are visible."""
    from chainermn_tpu.observability import tracing
    from chainermn_tpu.serving import (
        ContinuousBatchingScheduler,
        EngineConfig,
        InferenceEngine,
        QueueFull,
        SamplingParams,
        ServeFrontend,
    )

    N = args.serve_new_tokens
    bs = best["batch_size"]
    engine = InferenceEngine(model, params, EngineConfig(
        block_size=args.serve_block_size, n_blocks=args.serve_blocks,
        max_len=args.serve_max_len, max_batch=bs,
    ))
    sched = ContinuousBatchingScheduler(engine)
    fe = ServeFrontend(sched, max_queue=args.serve_queue)
    fe.submit(prompts[0], N, sampling=SamplingParams())
    fe.run_until_idle()

    tr = tracing.Tracer()
    tracing.install(tr)
    try:
        handles = []
        t0 = time.perf_counter()
        for p in prompts:
            while True:
                try:
                    handles.append(
                        fe.submit(p, N, sampling=SamplingParams())
                    )
                    break
                except QueueFull:
                    fe.step()
        fe.run_until_idle()
        wall = time.perf_counter() - t0
    finally:
        tracing.uninstall(tr)
    recs = tr.records()
    tr.close()

    st = engine.stats()
    total = sum(len(h.tokens) for h in handles)
    traced_tps = total / wall if wall > 0 else 0.0
    off_tps = best["tokens_per_sec"]
    return {
        "batch_size": bs,
        "traced_tokens_per_sec": round(traced_tps, 1),
        "untraced_tokens_per_sec": off_tps,
        "overhead_pct": round(100.0 * (1.0 - traced_tps / off_tps), 2)
        if off_tps else None,
        "extra_compiles": (
            (st["prefill_compiles"] - best["prefill_compiles"])
            + (st["decode_compiles"] - best["decode_compiles"])
        ),
        "stages": {
            name: {"count": s["count"],
                   "p50_ms": round(s["p50_s"] * 1e3, 3),
                   "p99_ms": round(s["p99_s"] * 1e3, 3)}
            for name, s in sorted(
                tracing.stage_percentiles(recs).items()
            )
        },
    }


def bench_serve_cluster(args, model, params):
    """Multi-replica tier numbers: routed throughput across
    ``--serve-replicas`` threaded replicas, plus the disaggregation
    proof — mixing one long prompt into a stream of short decoders on a
    single replica stalls their per-token p99 (prefill occupies the
    engine for whole iterations); splitting the same fleet into a
    prefill role and a decode role must bring the decoders' p99 back
    down, because the long prompt never enters the decode replica's
    step loop until its KV pages migrate over."""
    from chainermn_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        QueueFull,
    )
    from chainermn_tpu.serving.cluster import (
        Replica,
        ReplicaRouter,
        ThreadedClusterDriver,
    )

    R = args.serve_replicas
    N = args.serve_new_tokens
    rng = np.random.RandomState(1)
    short_prompts = [
        rng.randint(0, args.lm_vocab, size=args.serve_prompt_len)
        .tolist()
        for _ in range(args.serve_requests)
    ]
    long_len = min(args.serve_max_len - N - 1,
                   args.serve_prompt_len * 8)
    long_prompt = rng.randint(0, args.lm_vocab, size=long_len).tolist()

    def make_engine():
        return InferenceEngine(model, params, EngineConfig(
            block_size=args.serve_block_size,
            n_blocks=args.serve_blocks,
            max_len=args.serve_max_len,
            max_batch=max(int(b) for b in
                          args.serve_batch_sizes.split(",")),
        ))

    def run_point(roles, prompts, prefill_threshold=None,
                  traced=False):
        from chainermn_tpu.observability import tracing

        tr = None
        if traced:
            tr = tracing.Tracer()
            tracing.install(tr)
        reps = [
            Replica(i, make_engine(), role=roles[i],
                    max_queue=args.serve_queue)
            for i in range(len(roles))
        ]
        router = ReplicaRouter(reps,
                               prefill_threshold=prefill_threshold)
        stamps = {}

        def on_token_for(key):
            def cb(_rid, _tok):
                stamps.setdefault(key, []).append(time.perf_counter())
            return cb

        t0 = time.perf_counter()
        with ThreadedClusterDriver(router) as drv:
            handles = []
            for i, p in enumerate(prompts):
                while True:
                    try:
                        handles.append(router.submit(
                            p, N, on_token=on_token_for(i)))
                        break
                    except QueueFull as e:
                        # bounded-queue backpressure: honor the
                        # frontend's throughput-derived hint
                        router.step(drive_replicas=False)
                        time.sleep(min(e.retry_after_s or 0.01, 0.25))
            drv.run_until_idle(timeout_s=600)
        wall = time.perf_counter() - t0
        total = sum(len(h.tokens) for h in handles)
        # p99 over SHORT requests only: the long prompt's own latency
        # is the price of its length; the proof is about bystanders.
        gaps = []
        for i, p in enumerate(prompts):
            if len(p) == long_len and long_len != len(short_prompts[0]):
                continue
            ts = stamps.get(i, [])
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        gaps.sort()
        p99 = (gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))]
               if gaps else None)
        point = {
            "tokens_per_sec": round(total / wall, 1),
            "finished": sum(1 for h in handles
                            if h.status == "finished"),
            "requests": len(handles),
            "short_p99_token_latency_ms":
                round(p99 * 1e3, 3) if p99 is not None else None,
        }
        if tr is not None:
            tracing.uninstall(tr)
            point["trace_stages"] = {
                name: {"count": s["count"],
                       "p50_ms": round(s["p50_s"] * 1e3, 3),
                       "p99_ms": round(s["p99_s"] * 1e3, 3)}
                for name, s in sorted(
                    tracing.stage_percentiles(tr.records()).items()
                )
            }
            tr.close()
        return point

    # Routed throughput: all replicas decode-capable, short traffic.
    routed = run_point(["both"] * R, short_prompts)

    mixed = [long_prompt] + short_prompts
    # Baseline: ONE replica takes the long prompt and the decoders.
    baseline = run_point(["both"], mixed)
    # Disagg: one prefill-role replica absorbs the long prompt; the
    # decode fleet never runs its prefill.
    roles = ["prefill"] + ["decode"] * (R - 1)
    # Traced: the disagg point's span tree is where queue/prefill/
    # handoff/decode stage latencies all appear at once.
    disagg = run_point(roles, mixed,
                       prefill_threshold=long_len, traced=True)
    proof = None
    if (baseline["short_p99_token_latency_ms"] is not None
            and disagg["short_p99_token_latency_ms"] is not None):
        proof = (disagg["short_p99_token_latency_ms"]
                 <= baseline["short_p99_token_latency_ms"])
    return {
        "replicas": R,
        "routed": routed,
        "disagg_proof": {
            "long_prompt_len": long_len,
            "single_replica_mixed": baseline,
            "disaggregated": disagg,
            "p99_improved_or_equal": proof,
        },
    }


def _serve_traffic_point(args, model, params, spec, *, n_replicas,
                         min_replicas, max_replicas,
                         chaos_schedule=None, force_drain=False):
    """One traffic replay over a fresh autoscaled fleet; returns the
    workload summary plus the autoscaler/burn evidence for that point."""
    from chainermn_tpu.elastic.chaos import ChaosSchedule, TimedChaos
    from chainermn_tpu.observability import tracing
    from chainermn_tpu.observability.reporter import Reporter
    from chainermn_tpu.serving import EngineConfig, InferenceEngine
    from chainermn_tpu.serving import workload
    from chainermn_tpu.serving.cluster import (
        Autoscaler,
        AutoscalerConfig,
        HeartbeatMonitor,
        Replica,
        ReplicaRouter,
        ThreadedClusterDriver,
    )

    reporter = Reporter()
    slo_targets = {}
    for item in (args.serve_slo or "").split(","):
        if "=" in item:
            k, v = item.split("=", 1)
            slo_targets[k.strip()] = float(v)
    tr = None
    if slo_targets:
        tr = tracing.Tracer(
            reporter=reporter,
            slo=tracing.SLOConfig(targets=slo_targets),
        )
        tracing.install(tr)

    def make_engine():
        return InferenceEngine(model, params, EngineConfig(
            block_size=args.serve_block_size,
            n_blocks=args.serve_blocks,
            max_len=args.serve_max_len,
            max_batch=max(int(b) for b in
                          args.serve_batch_sizes.split(",")),
        ))

    def make_replica(rid):
        return Replica(rid, make_engine(), role="both",
                       reporter=reporter, max_queue=args.serve_queue)

    reps = [make_replica(i) for i in range(n_replicas)]
    router = ReplicaRouter(
        reps, reporter=reporter,
        health=HeartbeatMonitor([r.replica_id for r in reps],
                                miss_after_s=30.0),
    )
    scaler = Autoscaler(
        router, make_replica,
        AutoscalerConfig(min_replicas=min_replicas,
                         max_replicas=max_replicas,
                         k_up=2, cooldown_s=0.5),
        reporter=reporter,
    )
    chaos = None
    if chaos_schedule:
        chaos = TimedChaos(ChaosSchedule.parse(chaos_schedule))

    arrivals = workload.generate(spec)
    handles = []
    drain_fired = []

    def submit(a):
        h = router.submit(list(a.prompt), a.max_new_tokens,
                          timeout_s=600.0, priority=a.priority,
                          tenant=a.tenant)
        handles.append(h)
        return h

    def fire(fault):
        rid = fault.replica
        if rid is None or rid not in router.replicas:
            alive = [r.replica_id for r in router.replicas.values()
                     if r.alive]
            rid = alive[0] if alive else None
        if rid is None:
            return
        if fault.kind == "kill":
            router.fail_replica(rid, reason="chaos kill")
        elif fault.kind == "term":
            scaler.force_drain(rid)

    try:
        with ThreadedClusterDriver(router) as drv:
            def pump():
                drv.ensure_threads()
                router.step(drive_replicas=False)
                scaler.step()
                if chaos is not None:
                    for f in chaos.due():
                        fire(f)
                if (force_drain and not drain_fired
                        and sum(len(h.tokens) for h in handles) >= 2):
                    # Scale-down mid-load: live KV pages must migrate,
                    # not drop.  Victim = the newest seed replica.
                    if scaler.force_drain(n_replicas - 1):
                        drain_fired.append(n_replicas - 1)

            report = workload.replay(
                arrivals, submit, pump=pump, drain_timeout_s=600.0)
            # Let an in-flight drain finish retiring before teardown.
            for _ in range(200):
                if scaler._draining is None:
                    break
                pump()
                time.sleep(0.01)
            drv.run_until_idle(timeout_s=600)
    finally:
        if tr is not None:
            tracing.uninstall(tr)
            tr.close()

    point = workload.summarize(report)
    point["dropped"] = (point["offered"] - point["finished"]
                        - point["shed"] - point["rejected"])
    gauges = reporter.summary().get("gauges", {})
    point["burn_rates"] = {
        k.split("/", 2)[2]: round(float(v["value"]), 4)
        for k, v in gauges.items() if k.startswith("slo/burn_rate/")
    }
    point["autoscaler_events"] = [
        {k: (round(v, 3) if isinstance(v, float) else v)
         for k, v in ev.items() if k != "t"}
        for ev in scaler.events
    ]
    point["replicas_final"] = len(router.replicas)
    point["_report"] = report  # stripped by the caller
    return point


def _serve_traffic_bench(args):
    """``--serve-traffic``: goodput and p99 versus offered load over an
    autoscaled fleet, a chaos point (replica SIGKILL-equivalent at peak
    load, autoscaler backfills, streams stay bit-exact, SLO burn stays
    under 1), and a drain-based scale-down point with zero dropped
    streams.  Pure host orchestration — no communicator required."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import EngineConfig, InferenceEngine
    from chainermn_tpu.serving import workload

    model = TransformerLM(
        vocab=args.lm_vocab, d_model=args.lm_d_model,
        n_heads=args.lm_heads, d_ff=args.lm_d_ff,
        n_layers=args.lm_layers, max_len=args.serve_max_len,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    spec = workload.TrafficSpec.parse(args.serve_traffic)
    if spec.vocab >= args.lm_vocab:
        raise SystemExit(
            f"--serve-traffic vocab={spec.vocab} must stay below "
            f"--lm-vocab {args.lm_vocab}")
    if args.serve_queue is None:
        args.serve_queue = max(4, spec.requests // 2)
    R = max(args.serve_replicas, 1)
    mults = sorted(float(m) for m in
                   args.serve_load_mults.split(","))

    def strip(point):
        point.pop("_report", None)
        return point

    sweep = []
    for mult in mults:
        p = strip(_serve_traffic_point(
            args, model, params, spec.scaled(mult),
            n_replicas=R, min_replicas=R, max_replicas=R + 2,
        ))
        p["load_mult"] = mult
        p["offered_rate"] = round(spec.rate * mult, 2)
        sweep.append(p)
    curves = {
        "goodput_vs_offered_load": [
            [p["offered_rate"], round(p["goodput_tps"], 2)]
            for p in sweep],
        "p99_vs_load": [
            [p["offered_rate"], round(p["latency_p99_s"], 4)]
            for p in sweep],
    }
    out = {
        "spec": spec.format(),
        "replicas": R,
        "load_sweep": sweep,
        "curves": curves,
    }

    # Chaos point: kill a replica at peak load; the autoscaler
    # backfills and every surviving stream must match the oracle.
    schedule = args.serve_chaos
    if schedule == "auto":
        schedule = f"kill:replica={R - 1}:at=0.75"
    if schedule and schedule != "none":
        p = _serve_traffic_point(
            args, model, params, spec.scaled(mults[-1]),
            n_replicas=R, min_replicas=R, max_replicas=R + 2,
            chaos_schedule=schedule,
        )
        report = p.pop("_report")
        oracle = InferenceEngine(model, params, EngineConfig(
            block_size=args.serve_block_size,
            n_blocks=args.serve_blocks,
            max_len=args.serve_max_len, max_batch=1,
        ))
        mismatches = [
            o.arrival.index for o in report.outcomes if o.finished
            and list(o.handle.tokens) != oracle.generate(
                list(o.arrival.prompt), o.arrival.max_new_tokens)
        ]
        burn = max(p["burn_rates"].values(), default=0.0)
        out["chaos"] = {
            "schedule": schedule,
            "point": p,
            "backfilled": any(ev["action"] == "spawn"
                              and ev.get("reason") == "backfill"
                              for ev in p["autoscaler_events"]),
            "parity": "ok" if not mismatches else "FAIL",
            "parity_mismatches": mismatches,
            "slo_green": burn < 1.0,
        }

    # Scale-down point: one extra replica at the lightest load; the
    # autoscaler drains it mid-stream (live KV migrates) and retires
    # it — zero dropped streams is the acceptance bar.
    p = strip(_serve_traffic_point(
        args, model, params, spec.scaled(mults[0]),
        n_replicas=R + 1, min_replicas=R, max_replicas=R + 1,
        force_drain=True,
    ))
    out["scale_down"] = {
        "point": p,
        "drained": any(ev["action"] == "drain"
                       for ev in p["autoscaler_events"]),
        "retired": any(ev["action"] == "retire"
                       for ev in p["autoscaler_events"]),
        "dropped_streams": p["dropped"],
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["resnet", "lm"], default=None,
                    help="run a single flagship (default: both)")
    ap.add_argument(
        "--pipeline", action="store_true",
        help="feed the ResNet step through the real host input pipeline "
             "(multiprocess shared-memory loader + prefetch) instead of a "
             "resident batch",
    )
    ap.add_argument(
        "--loader-workers", type=int, default=2,
        help="worker processes for --pipeline batch assembly",
    )
    ap.add_argument(
        "--per-chip-batch", type=int, default=256,
        help="ResNet per-device batch (256 = measured optimum)",
    )
    ap.add_argument(
        "--input-dtype", choices=["float32", "bfloat16", "uint8"],
        default="float32",
        help="dtype of the fed ResNet batch (model casts to bf16 "
             "internally either way; uint8 = raw-bytes wire + on-device "
             "decode, 4x less feed traffic — the lever for "
             "transfer-bound --pipeline runs)",
    )
    # 4 sequences/chip without remat: measured optimum (27.2k tok/s, 0.7%
    # spread; B=8+remat 22.2k; B=8 no-remat 26.4k but unstable — one run
    # collapsed to 7k tok/s under memory pressure).
    ap.add_argument("--lm-batch", type=int, default=4,
                    help="LM per-device batch (sequences)")
    ap.add_argument("--lm-seq", type=int, default=4096)
    ap.add_argument("--lm-vocab", type=int, default=32768)
    ap.add_argument("--lm-d-model", type=int, default=2048)
    ap.add_argument("--lm-heads", type=int, default=16)
    ap.add_argument("--lm-d-ff", type=int, default=8192)
    ap.add_argument("--lm-layers", type=int, default=8)
    ap.add_argument("--lm-ce-chunk", type=int, default=1024)
    ap.add_argument("--lm-window", type=int, default=None,
                    help="sliding-window attention size (the flash "
                         "kernel skips tiles outside the band: O(S*W) "
                         "attention — the long-context single-chip knob)")
    ap.add_argument("--lm-remat", action="store_true",
                    help="enable per-layer remat (less activation memory, "
                         "~1/3 extra forward FLOPs; lets --lm-batch grow)")
    ap.add_argument("--plan", default=None, metavar="NAME",
                    help="record a registry sharding plan (dp, tp, fsdp, "
                         "zero, dp_tp) against the benched model: the "
                         "result JSON gains \"plan\" and \"plan_layout\" "
                         "(per-rule leaf counts, axes, sharded/total "
                         "leaves); absent, the output is unchanged")
    ap.add_argument("--serve", action="store_true",
                    help="decode-throughput mode: synthetic request "
                         "traffic through the serving stack (paged KV "
                         "cache + continuous batching), tokens/sec and "
                         "p50/p99 per-token latency per decode batch "
                         "size; the LM geometry comes from the --lm-* "
                         "flags")
    ap.add_argument("--serve-batch-sizes", default="1,2,4,8",
                    help="comma-separated decode batch sizes to sweep")
    ap.add_argument("--serve-requests", type=int, default=16,
                    help="synthetic requests per sweep point")
    ap.add_argument("--serve-prompt-len", type=int, default=64)
    ap.add_argument("--serve-new-tokens", type=int, default=32)
    ap.add_argument("--serve-block-size", type=int, default=16,
                    help="KV page size in tokens")
    ap.add_argument("--serve-blocks", type=int, default=512,
                    help="KV pages in the pool")
    ap.add_argument("--serve-max-len", type=int, default=512,
                    help="serving max sequence length (prompt + "
                         "generated; also the model max_len)")
    ap.add_argument("--serve-replicas", type=int, default=1,
                    help="with --serve: also run the multi-replica "
                         "tier (threaded replicas behind the router) "
                         "and the prefill/decode disaggregation p99 "
                         "proof at this replica count")
    ap.add_argument("--serve-tp", action="store_true",
                    help="with --serve: also sweep decode tokens/sec "
                         "versus tensor-parallel group size (the "
                         "registry 'tp' plan over local devices) at "
                         "the winning batch size — the shard-group "
                         "scaling curve")
    ap.add_argument("--serve-tp-sizes", default="1,2,4",
                    help="comma-separated group sizes for --serve-tp")
    ap.add_argument("--serve-queue", type=int, default=None,
                    help="bounded frontend queue size per "
                         "replica/engine (default: fits all requests)")
    ap.add_argument("--serve-prefix-dup", type=float, default=0.0,
                    help="fraction of each prompt drawn from a shared "
                         "template (duplicate-prefix load for the "
                         "prefix cache); >0 also reports the "
                         "no-sharing baseline and speedup")
    ap.add_argument("--serve-traffic", default=None, metavar="SPEC",
                    help="SLO-guarded degradation curves: replay a "
                         "seeded heavy-tailed workload (MMPP bursts, "
                         "Zipf shared prefixes, priority classes — "
                         "serving.workload.TrafficSpec 'key=value,...' "
                         "or 'default') over an autoscaled fleet at "
                         "each --serve-load-mults point, emitting "
                         "goodput-vs-offered-load and p99-vs-load "
                         "curves plus a chaos point (replica killed at "
                         "peak load, autoscaler backfills, streams "
                         "bit-exact) and a drain-based scale-down "
                         "point with zero dropped streams; alone it "
                         "is its own bench mode, with --serve it "
                         "rides along as a \"traffic\" section")
    ap.add_argument("--serve-load-mults", default="0.5,1,2",
                    help="offered-load multipliers on the traffic "
                         "spec's base rate for the --serve-traffic "
                         "sweep")
    ap.add_argument("--serve-chaos", default="auto", metavar="SCHEDULE",
                    help="timed fault schedule for the --serve-traffic "
                         "chaos point (docs/fault_tolerance.md grammar "
                         "with replica=/at= coordinates, e.g. "
                         "'kill:replica=1:at=0.75'); 'auto' kills the "
                         "last seed replica at peak load, 'none' "
                         "skips the chaos point")
    ap.add_argument("--serve-slo", default="queue=30,decode=30",
                    help="per-stage latency targets 'stage=seconds,...'"
                         " for the --serve-traffic burn-rate gauges "
                         "(lenient defaults suit compile-dominated CPU "
                         "runs); empty string disables SLO tracking")
    ap.add_argument("--serve-spec-tokens", type=int, default=3,
                    help="speculative draft length for the serve "
                         "sweep's spec-ON column (OFF column always "
                         "runs alongside)")
    ap.add_argument("--serve-draft", action="store_true",
                    help="A/B the speculative draft sources at the "
                         "winning batch size: n-gram prompt lookup vs "
                         "the layer-truncated self-draft model, same "
                         "traffic (streams identical by exact-match "
                         "acceptance; only accept length and wall "
                         "clock differ)")
    ap.add_argument("--serve-draft-layers", type=int, default=None,
                    help="self-draft depth for --serve-draft "
                         "(default: half the target's layers)")
    ap.add_argument("--serve-prefill-chunk", type=int, default=0,
                    help="when > 0, prove chunked prefill: a "
                         "long-prompt arrival mid-decode, short "
                         "requests' token-gap p99 with prompts "
                         "sliced at this many tokens vs monolithic "
                         "prefill")
    ap.add_argument("--serve-long-context", action="store_true",
                    help="long-context serving section: p99-vs-prompt-"
                         "length curve through lazily-grown buckets, "
                         "streaming-prefix-registration A/B (two "
                         "interleaved requests over one shared "
                         "document — duplicate prefill slices with "
                         "streaming ON vs OFF), and oracle parity "
                         "under greedy + temperature/top-k sampling "
                         "incl. mid-prefill preemption; alone it is "
                         "its own bench mode, with --serve it rides "
                         "along as a \"long_context\" section")
    ap.add_argument("--serve-long-lens", default="64,128,256",
                    help="comma-separated prompt lengths for the "
                         "--serve-long-context curve (CPU-sane "
                         "default; the 100k story is e.g. "
                         "'32768,65536,98304' on real hardware)")
    ap.add_argument("--comm-dtype", default=None,
                    choices=["none", "int8", "fp8"],
                    help="quantized gradient wire for the train benches "
                         "(scaled int8/fp8 allreduce); when set to a "
                         "narrow dtype the LM result gains a "
                         "\"comm_dtype\" A/B section (step time and "
                         "tokens/s vs the full-precision wire, plus the "
                         "measured max-abs quantization error); unset "
                         "leaves the output shape unchanged")
    ap.add_argument("--kv-dtype", default=None, choices=["none", "int8"],
                    help="with --serve: also measure the int8 paged KV "
                         "cache — the serve result gains a \"kv_dtype\" "
                         "A/B section (tokens/s and p99 vs full-precision "
                         "pages, kv quantization error, speculative "
                         "accept-length delta, and the max-admissible "
                         "decode batch at the SAME pool byte budget); "
                         "unset leaves the output shape unchanged")
    ap.add_argument("--no-overlap", action="store_true",
                    help="pin the eager pack-all-then-reduce-all "
                         "gradient schedule (overlap=False on the "
                         "communicator) — the A/B lever against the "
                         "default backward-overlapped schedule; both "
                         "runs report allreduce_overlap (async pairs + "
                         "overlap fraction from the compiled HLO) next "
                         "to the step time")
    ap.add_argument("--step-log", default=None, metavar="PATH",
                    help="write a JSONL event log of the bench run "
                         "(compile events, instrumented-step spans, the "
                         "final result row); summarize with `python -m "
                         "chainermn_tpu.tools.obs summarize PATH`")
    ap.add_argument("--chaos", default=None, metavar="SCHEDULE",
                    help="fault-injection soak: run the elastic "
                         "supervisor over a deterministic training "
                         "worker twice — once clean, once under this "
                         "chaos schedule (docs/fault_tolerance.md "
                         "grammar, e.g. 'kill:rank=1:step=5') — and "
                         "report restarts/preemptions/resume generation "
                         "plus whether the faulted run's final params "
                         "digest matches the uninterrupted oracle; "
                         "alone it is its own bench mode, with "
                         "--only/--serve it rides along as a \"chaos\" "
                         "section")
    ap.add_argument("--chaos-nproc", type=int, default=2,
                    help="world size for the --chaos soak")
    ap.add_argument("--fabric-diurnal", action="store_true",
                    help="resource-fabric soak: one chip ledger shared "
                         "by an elastic training job (subprocess ranks) "
                         "and an in-process serving fleet under diurnal "
                         "traffic — the arbiter preempts trainer ranks "
                         "at the peak (SIGTERM-grace-checkpoint path, "
                         "serving backfill from the freed chips) and "
                         "returns them in the trough (replica drained "
                         "with zero dropped streams); reported against "
                         "a no-arbiter baseline: tokens/s lost vs p99 "
                         "defended, bit-exact training digest, ledger "
                         "conservation; alone it is its own bench mode "
                         "(additive JSON, default shape untouched)")
    ap.add_argument("--fabric-traffic", default=None, metavar="SPEC",
                    help="TrafficSpec for --fabric-diurnal (default: "
                         "the fabric CLI's diurnal two-tenant spec)")
    ap.add_argument("--fabric-nproc", type=int, default=2,
                    help="initial trainer world for --fabric-diurnal")
    ap.add_argument("--fabric-replicas", type=int, default=2,
                    help="initial fleet size for --fabric-diurnal")
    ap.add_argument("--fabric-steps", type=int, default=240,
                    help="trainer steps for --fabric-diurnal")
    args = ap.parse_args(argv)
    if args.chaos and not args.serve and not args.serve_traffic \
            and args.only is None:
        # Chaos-only mode: pure process orchestration, no device bench
        # (and no backend init in THIS process).
        print(json.dumps({"chaos": _chaos_soak(args)}))
        return
    if args.serve_traffic and not args.serve and args.only is None:
        # Traffic-only mode: host-side serving orchestration; no
        # communicator, default JSON shape untouched.
        print(json.dumps({"serve_traffic": _serve_traffic_bench(args)}))
        return
    if args.serve_long_context and not args.serve and args.only is None:
        # Long-context-only mode: single-replica serving measurements;
        # no communicator, default JSON shape untouched.
        print(json.dumps(
            {"serve_long_context": _serve_long_context_bench(args)}))
        return
    if args.fabric_diurnal and not args.serve and args.only is None:
        # Fabric-only mode: subprocess orchestration of both planes;
        # no backend init here, default JSON shape untouched.
        print(json.dumps({"fabric_diurnal": _fabric_diurnal_bench(args)}))
        return
    comm = chainermn_tpu.create_communicator(
        "xla_ici", overlap=False if args.no_overlap else None,
        comm_dtype=args.comm_dtype,
    )

    telemetry = contextlib.ExitStack()
    recorder = None
    reporter = None
    if args.step_log:
        from chainermn_tpu.observability import Reporter, StepRecorder
        from chainermn_tpu.observability import reporter as reporter_mod

        recorder = telemetry.enter_context(StepRecorder(args.step_log))
        # Reporter scope so the flagship MFU / overlap-fraction gauges
        # (and any serving-stage histograms) have somewhere to land;
        # the summary is flushed into the step log at exit.
        reporter = Reporter()
        telemetry.enter_context(reporter_mod.scope(reporter))

    if args.serve:
        out = bench_serve(comm, args)
    elif args.only == "lm":
        out = bench_lm(comm, args)
    elif args.only == "resnet":
        out = bench_resnet(comm, args)
    else:
        out = bench_resnet(comm, args)
        out["lm"] = bench_lm(comm, args)
        out["allreduce_static_bytes_per_leg"] = _static_allreduce_table()
        out["allreduce_tree"] = _allreduce_tree_table()
    if args.chaos:
        out["chaos"] = _chaos_soak(args)
    if recorder is not None:
        recorder.step()  # flush buffered compile events and step spans
        if reporter is not None:
            recorder.record("reporter", summary=reporter.summary())
        recorder.record("bench_result", result=out)
    telemetry.close()
    print(json.dumps(out))


def _chaos_soak(args):
    """Deterministic fault-injection soak (``--chaos SCHEDULE``): the
    elastic supervisor drives the soak training worker in a CPU
    subprocess world, once uninterrupted (the oracle) and once under the
    schedule.  The pinned evidence is the supervisor report pair —
    restarts/preemptions/resume generation under fault, and whether the
    faulted run's final params digest is bit-identical to the oracle's
    (it must be whenever the schedule keeps the world size fixed)."""
    import subprocess
    import sys
    import tempfile

    env = _cpu_child_env(1)
    worker = os.path.join(_ROOT, "tests", "_elastic_train_worker.py")

    def run(tag, *extra):
        d = tempfile.mkdtemp(prefix=f"bench_chaos_{tag}_")
        cmd = [
            sys.executable, "-m", "chainermn_tpu.tools.elastic",
            "--nproc", str(args.chaos_nproc),
            "--workdir", os.path.join(d, "work"),
            "--hb-timeout", "60", "--grace", "10", *extra, "--",
            sys.executable, worker, "--ckpt", os.path.join(d, "ckpt"),
        ]
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=600,
                env=env,
            )
        except Exception as e:  # pragma: no cover - environment-specific
            return {"error": f"{type(e).__name__}: {e}"}
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("ELASTIC_REPORT ")]
        if proc.returncode != 0 or not lines:
            return {
                "error": (proc.stdout + proc.stderr).strip()[-800:]
                or f"exit {proc.returncode}",
            }
        return json.loads(lines[-1].split(" ", 1)[1])

    oracle = run("oracle")
    chaos = run("chaos", "--chaos", args.chaos)
    out = {
        "schedule": args.chaos,
        "nproc": args.chaos_nproc,
        "oracle": oracle,
        "chaos": chaos,
    }
    if "error" not in oracle and "error" not in chaos:
        out["digest_match"] = bool(
            chaos.get("params_digest")
            and chaos["params_digest"] == oracle.get("params_digest")
        )
    return out


def _fabric_diurnal_bench(args):
    """``--fabric-diurnal``: the one-resource-fabric soak, twice.

    Both runs replay the same diurnal traffic over the same fleet
    geometry with the same elastic training job underneath; the
    baseline pins the fleet and leaves training untouched
    (``--no-arbiter``), the fabric run lets the arbiter trade chips.
    The pinned evidence is the pair: what serving p99 the borrowed
    chips defended at the peak versus what training tokens/s the loan
    cost — plus the invariants (training digest bit-identical to the
    uninterrupted baseline, zero dropped streams, ledger conserved,
    burn rates back under 1 after the backfill, and at least one chip
    round trip: preempt-for-serving AND return-to-training)."""
    import subprocess
    import sys
    import tempfile

    env = _cpu_child_env(1)

    def run(tag, *extra):
        d = tempfile.mkdtemp(prefix=f"bench_fabric_{tag}_")
        cmd = [
            sys.executable, "-m", "chainermn_tpu.tools.fabric",
            "--nproc", str(args.fabric_nproc),
            "--replicas", str(args.fabric_replicas),
            "--train-steps", str(args.fabric_steps),
            "--workdir", d,
            *extra,
        ]
        if args.fabric_traffic:
            cmd += ["--traffic", args.fabric_traffic]
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=900,
                env=env,
            )
        except Exception as e:  # pragma: no cover - environment-specific
            return {"error": f"{type(e).__name__}: {e}"}
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("FABRIC_REPORT ")]
        if not lines:
            return {
                "error": (proc.stdout + proc.stderr).strip()[-800:]
                or f"exit {proc.returncode}",
            }
        rep = json.loads(lines[-1].split(" ", 1)[1])
        rep["exit_code"] = proc.returncode
        return rep

    baseline = run("baseline", "--no-arbiter")
    fabric = run("fabric")
    out = {
        "nproc": args.fabric_nproc,
        "replicas": args.fabric_replicas,
        "baseline": baseline,
        "fabric": fabric,
    }
    if "error" not in baseline and "error" not in fabric:
        tr = fabric.get("transitions", {})
        burn = max(fabric.get("burn_rates", {}).values(), default=0.0)
        b_p99 = (baseline.get("serve") or {}).get("latency_p99_s")
        f_p99 = (fabric.get("serve") or {}).get("latency_p99_s")
        b_wall = (baseline.get("train") or {}).get("incarnations", 1)
        f_wall = (fabric.get("train") or {}).get("incarnations", 1)
        out["verdict"] = {
            # the trade: what the borrowed chips cost training...
            "train_extra_incarnations": f_wall - b_wall,
            "train_lease_rescales":
                (fabric.get("train") or {}).get("lease_rescales", 0),
            # ...versus what they defended in serving tail latency.
            "p99_baseline_s": b_p99,
            "p99_fabric_s": f_p99,
            "p99_defended": (
                b_p99 is not None and f_p99 is not None
                and f_p99 <= b_p99
            ),
            # invariants the fabric must not trade away:
            "digest_match": bool(
                (fabric.get("train") or {}).get("params_digest")
                and (fabric["train"]["params_digest"]
                     == (baseline.get("train") or {}).get("params_digest"))
            ),
            "preempted_for_serving": tr.get("preempt_for_serving", 0),
            "returned_to_training": tr.get("return_to_training", 0),
            "round_trip": (tr.get("preempt_for_serving", 0) >= 1
                           and tr.get("return_to_training", 0) >= 1),
            "dropped_streams": fabric.get("dropped_streams"),
            "ledger_conserved": fabric.get("ledger_conserved"),
            "parity": ("ok" if not fabric.get("parity", {}).get(
                "mismatches") else "FAIL"),
            "max_burn_rate": burn,
            "slo_green": burn < 1.0,
        }
    return out


def _allreduce_bench_child(*bench_args):
    """Run ``benchmarks/allreduce_bench.py --static-only`` in a CPU-mesh
    child (the analysis needs an 8-device mesh; this process holds the
    chip) and return its JSON rows.  A child that fails fails the run."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable,
         os.path.join(_ROOT, "benchmarks", "allreduce_bench.py"),
         "--static-only", *bench_args,
         "--communicators",
         "flat,two_dimensional,hierarchical,xla_ici,naive"],
        capture_output=True, text=True, timeout=300, env=_cpu_child_env(8),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"allreduce_bench child exited {proc.returncode}:\n"
            + proc.stderr.strip()[-2000:]
        )
    return [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]


def _static_allreduce_table():
    """Jaxpr-level per-axis collective bytes for each backend.
    Environment-independent evidence for the communicator algorithms'
    wire structure — including the asserted two_dimensional inter-leg =
    flat/intra_size claim — recorded next to the measured numbers.

    The census itself lives in
    :mod:`chainermn_tpu.observability.hlo_audit` (``audit_allreduce``);
    ``allreduce_bench.py --static-only`` is a thin consumer, so these
    numbers and the library API cannot drift apart."""
    return _allreduce_bench_child("--sizes-mb", "4")


def _allreduce_tree_table():
    """Many-leaf gradient-tree allreduce: bucketed (GradPacker fusion,
    communicators/packing.py) vs unbucketed lowering of a 64-leaf
    mixed-shape tree per communicator.  Static-only: the pinned evidence
    is the collective census becoming independent of leaf count
    (reduction ops per dtype bucket, not per leaf) and the per-bucket
    operand bytes; timing a virtual CPU mesh would prove nothing about
    ICI."""
    return _allreduce_bench_child(
        "--tree-leaves", "64", "--tree-total-mb", "8")


if __name__ == "__main__":
    main()
