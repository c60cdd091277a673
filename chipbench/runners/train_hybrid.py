"""Runner of ``kind: train_hybrid`` traffic: a ``granitemoehybrid``
configuration (Mamba-2 mixers with an attention layer among every few)
trained through the same path as ``kind: train`` — ``create_communicator``
-> ``create_multi_node_optimizer`` -> ``make_train_step``, flash attention,
fused cross-entropy — the model built from the configuration's published
keys by the program's own ``block_table.table_from_config``.

:class:`HybridJob` is ``train.TrainJob`` with another model, other seeded
weights and another reference: the step, the feed, and what ``correct``
reads of them (losses, the first gradient from AdamW's first moment, every
leaf's change) are ``train.py``'s own, and the window below is timed by
the same statements as ``train.run``'s (``dispatch_ahead``, the profiler's
slice at step 3, one ``block_until_ready`` at the end), so that
``train_step_ms`` means here what it means in the cgpt cells.
"""

import math
import time

import numpy as np

from chipbench import flops_hybrid, harness, traffic, weights_hybrid
from chipbench.refs import granite_hybrid as reference
from chipbench.runners import train


class HybridJob(train.TrainJob):
    """The compiled step with its state: what set-up builds and the
    window drives."""

    def __init__(self, config, mix, devices):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec

        import chainermn_tpu
        from chainermn_tpu.communicators import build_mesh
        from chainermn_tpu.models.block_table import table_from_config
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.ops import make_flash_attention_fn
        from chainermn_tpu.ops.fused_ce import fused_cross_entropy

        prog = config["program"]
        c = prog["communicator"]
        self.mesh = build_mesh(inter_size=1, intra_size=len(devices),
                               devices=devices)
        self.comm = chainermn_tpu.create_communicator(
            c["name"], mesh=self.mesh, bucket_bytes=c["bucket_bytes"],
            overlap=c["overlap"],
            overlap_granularity=c["overlap_granularity"],
            comm_dtype=c["comm_dtype"])
        self.replicated = NamedSharding(self.mesh, PartitionSpec())
        self.rows = NamedSharding(
            self.mesh, PartitionSpec(self.mesh.axis_names))
        self.config, self.mix, self.devices = config, mix, devices
        if prog["attention"] != "flash" or prog["loss"] != "fused_ce":
            raise ValueError("this runner builds flash attention + fused "
                             "CE, as the configuration must say")
        table = table_from_config(config, n_layers=config["n_layer"])
        model = TransformerLM(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            table=table, remat=prog["remat"],
            attention_fn=make_flash_attention_fn(
                causal=True, block_q=prog["flash_block_q"],
                block_k=prog["flash_block_k"],
                scale=config["attention_multiplier"]))
        o = config["optimizer"]
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                        eps=o["eps"], weight_decay=o["weight_decay"]),
            self.comm)
        self.b1, self.opt = o["b1"], opt

        def loss_fn(p, batch):
            tokens, labels = batch
            h = model.apply({"params": p}, tokens, return_hidden=True)
            return fused_cross_entropy(
                h, p["embed"]["embedding"], labels, chunk=prog["ce_chunk"])

        self.step_fn = opt.make_train_step(loss_fn, donate=prog["donate"])
        self._norms = jax.jit(lambda tree: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree))
        self._delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))

    def make_weights(self, seed):
        return weights_hybrid.make(self.config, seed, self.replicated)

    def reset(self, seed):
        """Seeded weights, a fresh optimizer state and the seed's feed."""
        import jax

        self.seed = seed
        self.params = self.state = None
        self.params = self.make_weights(seed)
        self.state = jax.device_put(
            self.opt.init(self.params), self.replicated)
        self.batches = traffic.train_batches(
            self.mix, self.config["vocab_size"], seed)

    def change_norms(self):
        import jax

        return jax.device_get(self._delta(
            self.params, self.make_weights(self.seed)))

    def scope_table(self):
        """The compiled step's scope table, lowered from abstract
        parameters and state and one placed batch (the window's own
        program: its compilation is a cache hit)."""
        import jax

        from chainermn_tpu.observability import device_trace

        def placed(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=self.replicated), tree)

        params = placed(jax.eval_shape(
            lambda: weights_hybrid.make(self.config, 0)))
        state = placed(jax.eval_shape(self.opt.init, params))
        compiled = self.step_fn.lower(params, state, self.feed(0)).compile()
        return device_trace.scope_table(compiled)


def reference_readings(run, job_like, precision="float32"):
    """Follow the first steps with the plain reference (or a control)."""
    import jax

    config, mix, seed = run.config, run.mix, run.seed
    batches = traffic.train_batches(mix, config["vocab_size"], seed)
    steps = [batches(i) for i in range(int(mix["reference_steps"]))]
    return reference.train_steps(
        lambda: weights_hybrid.make(config, seed, job_like["replicated"]),
        steps, config, precision=precision, block_rows=len(run.devices),
        place=lambda x: jax.device_put(x, job_like["rows"]))


def run(run):
    import jax
    from jax.profiler import TraceAnnotation

    config, mix, devices = run.config, run.mix, run.devices
    n_ref = int(mix["reference_steps"])
    run.stage("imports done, building the job")
    job = HybridJob(config, mix, devices)
    job.reset(run.seed)
    run.stage("weights and state made; first steps (compile when cold)")
    readings = train.first_steps(job, n_ref)
    run.stage("first steps done: the window opens")
    setup_s = time.perf_counter() - run.t_start

    ahead = int(mix["dispatch_ahead"])
    trace_at = 3 if run.trace else None
    trace_steps = int(mix["trace_steps"])
    profiler, traced_window = harness.ProfilerSlice(), None
    losses, last_batch = [], None
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < run.seconds:
        if n == trace_at:
            jax.block_until_ready(losses[-1])
            t_slice = time.perf_counter()
            profiler.start()
        with TraceAnnotation("chipbench:global_batch"):
            last_batch = job.feed(n_ref + n)
        with TraceAnnotation("chipbench:train_step"):
            losses.append(job.step(last_batch))
        n += 1
        if len(losses) > ahead:
            with TraceAnnotation("chipbench:wait_step"):
                jax.block_until_ready(losses[-1 - ahead])
        if trace_at is not None and n == trace_at + trace_steps:
            jax.block_until_ready(losses[-1])
            profiler.stop()
            slice_s = time.perf_counter() - t_slice
            traced_window = (profiler.t0, profiler.t1)
    jax.block_until_ready((losses[-1], job.params))
    elapsed = time.perf_counter() - t0
    step_ms = elapsed / n * 1e3
    clear_step_ms = step_ms if traced_window is None else (
        (elapsed - slice_s) / (n - trace_steps) * 1e3)

    host_losses = [float(x) for x in jax.device_get(losses)]
    finite = [x for x in host_losses if math.isfinite(x)]
    failed = n - len(finite)
    run.check("window_nonfinite_losses", failed, 0)
    k = min(5, max(1, n // 2))
    head, tail = np.mean(host_losses[:k]), np.mean(host_losses[-k:])
    run.check("window_loss_last_minus_first", float(tail - head), 0.0,
              ok=bool(tail < head) or n < 2 * k)
    if len(devices) > 1:
        train.check_placement(run, job, last_batch)
    device = harness.device_report(devices)

    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    n_params = weights_hybrid.n_params(config)
    harness.say(
        f"train_hybrid: steps={n} window_s={elapsed:.4f} "
        f"step_ms={step_ms:.4f} step_ms_outside_trace={clear_step_ms:.4f} "
        f"tokens_per_s_per_chip={tokens / (step_ms / 1e3) / len(devices):.1f}"
        f" model_tflop_per_step="
        f"{flops_hybrid.train_flops_per_step(config, mix) / 1e12:.3f} "
        f"n_params={n_params} first_losses={readings['losses']} "
        f"window_loss_first={head:.4f} window_loss_last={tail:.4f}")

    job_like = {"replicated": job.replicated, "rows": job.rows}
    job.release()
    del losses, last_batch
    scope_table = job.scope_table() if run.trace else None
    del job
    run.stage("window closed; reference")
    t_ref = time.perf_counter()
    ref = reference_readings(run, job_like)
    harness.say(f"reference: {n_ref} steps in "
                f"{time.perf_counter() - t_ref:.1f} s, losses "
                f"{ref['losses']}")
    train.compare(run, readings, ref)

    layer_ctx = {
        "kind": "train_hybrid", "config": config, "mix": mix,
        "devices": devices, "device_kind": devices[0].device_kind,
        "steps": n, "window_s": elapsed, "step_ms": step_ms,
        "clear_step_ms": clear_step_ms, "n_params": n_params,
        "trace_steps": trace_steps, "trace": None,
    }
    if run.trace:
        from chipbench import trace_reduce

        if traced_window is None:
            raise RuntimeError(
                f"the window of {run.seconds} s was too short to trace "
                f"{trace_steps} steps")
        layer_ctx["trace"] = trace_reduce.TraceData.from_file(
            profiler.path(), n_devices=len(devices))
        profiler.remove()
        layer_ctx["scope_table"] = scope_table
    return {"correct": run.correct, "attempted": n, "failed": failed,
            "end_to_end": {"train_step_ms": step_ms}, "setup_s": setup_s,
            "device": device, "layer_ctx": layer_ctx}
