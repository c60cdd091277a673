"""Runner of ``kind: train`` traffic: the data-parallel LM train step the
source paper's API builds (``create_communicator`` ->
``create_multi_node_optimizer`` -> ``make_train_step``), on as many chips
as the cell asks for, fed a fresh global batch through
``comm.global_batch`` every step.

Set-up builds ONE job (compiled step + state), drives it from the seed
through its first ``reference_steps`` steps by the window's own call and
feed, and hands the same job to the window.  After the window the
program's state is freed and the plain reference follows those first
steps from the same seeded weights; ``correct`` compares each step's loss,
the norm of every leaf of the first gradient as the optimizer got it (read
back from AdamW's first moment after one step) and the norm of every
leaf's change after the last of them.
"""

import math
import statistics
import time

import numpy as np

from chipbench import flops, harness, traffic, weights
from chipbench.refs import gpt2_dense as reference


class TrainJob:
    """The compiled step with its state: what set-up builds and the
    window drives."""

    def __init__(self, config, mix, devices):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec

        import chainermn_tpu
        from chainermn_tpu.communicators import build_mesh
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.ops import make_flash_attention_fn
        from chainermn_tpu.ops.fused_ce import fused_cross_entropy

        prog, z = config["program"], weights.sizes(config)
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
        model = TransformerLM(
            vocab=z["vocab"], d_model=z["d"], n_heads=z["heads"],
            d_ff=z["d_ff"], n_layers=z["layers"],
            max_len=config["n_positions"], remat=prog["remat"],
            attention_fn=make_flash_attention_fn(
                causal=True, block_q=prog["flash_block_q"],
                block_k=prog["flash_block_k"]))
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

    def reset(self, seed):
        """Seeded weights, a fresh optimizer state and the seed's feed."""
        self.seed = seed
        self.params = self.state = None
        import jax

        self.params = weights.make(self.config, seed, self.replicated)
        # Committed to the sharding the step returns its state in, so that
        # the second call finds the program of the first (left where
        # ``opt.init`` puts it, the step is traced and loaded twice: ~12 s
        # of set-up each, my chip run, PR 23).
        self.state = jax.device_put(
            self.opt.init(self.params), self.replicated)
        self.batches = traffic.train_batches(
            self.mix, self.config["vocab_size"], seed)

    def feed(self, index):
        return self.comm.global_batch(self.batches(index))

    def step(self, batch):
        self.params, self.state, loss = self.step_fn(
            self.params, self.state, batch)
        return loss

    def first_moment_norms(self):
        """Per-leaf norm of AdamW's first moment (after one step it is
        (1 - b1) x the gradient the optimizer got)."""
        import jax

        inner = self.state.inner
        mu = next(s.mu for s in jax.tree.leaves(
            inner, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu"))
        return jax.device_get(self._norms(mu))

    def change_norms(self):
        import jax

        start = weights.make(self.config, self.seed, self.replicated)
        return jax.device_get(self._delta(self.params, start))

    def release(self):
        self.params = self.state = None


def worst_leaf_gap(program, ref):
    """The widest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    p, r = weights.flatten(program), weights.flatten(ref)
    floor = statistics.median(float(x) for x in r.values())
    worst, where = 0.0, None
    for path in r:
        gap = abs(float(p[path]) - float(r[path])) / max(
            float(r[path]), floor)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= worst:
            worst, where = gap, weights.leaf_name(path)
    return worst, where


def reference_readings(run, job_like, precision="float32"):
    """Follow the first steps with the plain reference (or a control)."""
    import jax

    config, mix, seed = run.config, run.mix, run.seed
    sharding = job_like["replicated"]
    rows = job_like["rows"]
    n_dev = len(run.devices)
    batches = traffic.train_batches(
        mix, config["vocab_size"], seed)
    steps = [batches(i) for i in range(int(mix["reference_steps"]))]
    return reference.train_steps(
        lambda: weights.make(config, seed, sharding), steps,
        config["optimizer"], precision=precision, block_rows=n_dev,
        place=lambda x: jax.device_put(x, rows))


def compare(run, readings, ref):
    """Every number compared, each beside its limit."""
    lim = run.limits
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(readings["losses"], ref["losses"]))
    run.check("first_steps_loss_rel_gap", loss_gap, lim["loss_rel_gap"])
    g, where = worst_leaf_gap(readings["grad_norms"], ref["grad_norms"])
    harness.say(f"worst gradient leaf: {where}")
    run.check("first_grad_norm_worst_leaf_gap", g, lim["grad_norm_gap"])
    d, where = worst_leaf_gap(readings["delta_norms"], ref["delta_norms"])
    harness.say(f"worst parameter-change leaf: {where}")
    run.check("param_change_norm_worst_leaf_gap", d,
              lim["delta_norm_gap"])


def first_steps(job, n_steps):
    """Drive the job through its first steps, by the window's own call
    and feed; read what ``correct`` compares."""
    import jax

    losses, grad_norms = [], None
    for i in range(n_steps):
        loss = job.step(job.feed(i))
        losses.append(float(jax.block_until_ready(loss)))
        if i == 0:
            mu = job.first_moment_norms()
            grad_norms = jax.tree.map(lambda x: x / (1.0 - job.b1), mu)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": job.change_norms()}


def check_placement(run, job, batch):
    """On several chips: the batch has a shard on every chip before the
    step, parameters and state are replicated on all of them after."""
    import jax

    devices = set(run.devices)
    spread = all({s.device for s in leaf.addressable_shards} == devices
                 for leaf in jax.tree.leaves(batch))
    run.check("batch_on_every_chip", int(not spread), 0)
    replicated = all(
        leaf.sharding.is_fully_replicated
        and leaf.sharding.device_set == devices
        for leaf in jax.tree.leaves((job.params, job.state)))
    run.check("state_replicated_after_window", int(not replicated), 0)


def run(run):
    import jax
    from jax.profiler import TraceAnnotation

    config, mix, devices = run.config, run.mix, run.devices
    n_ref = int(mix["reference_steps"])
    run.stage("imports done, building the job")
    job = TrainJob(config, mix, devices)
    job.reset(run.seed)
    run.stage("weights and state made; first steps (compile when cold)")
    readings = first_steps(job, n_ref)
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
    # The slice begins and ends with the device drained, so it holds
    # ``trace_steps`` whole steps and all of the profiler's start and stop;
    # the steps outside it are timed as an untraced run's are.
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
        check_placement(run, job, last_batch)
    device = harness.device_report(devices)

    z = weights.sizes(config)
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    n_params = weights.n_params(config)
    per_token = flops.lm_train_flops_per_token(
        n_params, int(mix["seq_len"]), z["d"], z["layers"])
    harness.say(
        f"train: steps={n} window_s={elapsed:.4f} step_ms={step_ms:.4f} "
        f"step_ms_outside_trace={clear_step_ms:.4f} "
        f"tokens_per_s_per_chip={tokens / (step_ms / 1e3) / len(devices):.1f}"
        f" model_tflop_per_step={per_token * tokens / 1e12:.3f} "
        f"first_losses={readings['losses']} "
        f"window_loss_first={head:.4f} window_loss_last={tail:.4f}")

    job_like = {"replicated": job.replicated, "rows": job.rows}
    job.release()
    del job, losses, last_batch
    run.stage("window closed; reference")
    t_ref = time.perf_counter()
    ref = reference_readings(run, job_like)
    harness.say(f"reference: {n_ref} steps in "
                f"{time.perf_counter() - t_ref:.1f} s, losses "
                f"{ref['losses']}")
    compare(run, readings, ref)

    layer_ctx = {
        "kind": "train", "config": config, "mix": mix, "devices": devices,
        "device_kind": devices[0].device_kind, "steps": n,
        "window_s": elapsed, "step_ms": step_ms,
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
    return {"correct": run.correct, "attempted": n, "failed": failed,
            "end_to_end": {"train_step_ms": step_ms}, "setup_s": setup_s,
            "device": device, "layer_ctx": layer_ctx}
