"""Runner of ``kind: train_kda_mla_moe`` traffic: a ``bailing_hybrid``
configuration (Ling-3.0: Kimi-Delta-Attention rows to one latent-attention
row, leading dense FFNs, then a group-limited sigmoid top-k sparse-expert
FFN with a shared expert; an untied head) trained through the same path as
the other training cells — ``create_communicator`` ->
``create_multi_node_optimizer`` -> ``make_train_step``, flash attention
(the latent row's scores 192 wide, its values 128), fused cross-entropy
over the head's own matrix — the model built from the configuration's
published keys by the program's own ``block_table.table_from_config``,
told which experts this chip holds.  The runner sets no width, no group
and no expert count of its own.

:class:`KdaMlaMoeJob` is ``train_moe_hybrid.MoeHybridJob`` (``reset``,
``feed``, ``change_norms``, ``release``) with another model, other seeded
weights, another reference and, beside the optimizer's update, the
balancing controller's on the expert biases, as ``train_cca_moe.CcaMoeJob``
has it; the window below is timed by the same statements as the other
runners', so that ``train_step_ms`` means here what it means in the other
cells; and ``correct`` is what the ``qwen3_next`` cell's is
(``train_moe_hybrid``'s docstring says what and why): the timed step hands
its routers' choice back, no held pair may lie past the row buffer's
bound on any step, the reference follows the first steps WITH the step's
own choice and under the same controller, and the share of (token,
choice) pairs its own routers would have settled otherwise is compared
with a limit of its own.
"""

import math
import time

import numpy as np

from chipbench import flops_ling3, harness, traffic, weights_ling3
from chipbench.refs import ling3 as reference
from chipbench.runners import train
from chipbench.runners.train_cca_moe import held_experts
from chipbench.runners.train_gdn_moe import say_owners, tile_fill
from chipbench.runners.train_moe_hybrid import (
    MoeHybridJob,
    chosen_from_masks,
    compare,
    differing_pairs_share,  # noqa: F401  (the control tool reads it here)
    held_pairs,
)


def build_table(config):
    """The program's block table from the published keys (the file's own
    keys — name, deployment, reckoning, ... — are not the reader's, which
    refuses a key it does not know): the router keeps its published
    width; the layers kept and the experts held are the deployment's."""
    from chainermn_tpu.models.block_table import (
        BAILING_HYBRID_KEYS,
        table_from_config,
    )

    published = {k: v for k, v in config.items() if k in BAILING_HYBRID_KEYS}
    published["num_experts"] = config["num_experts_published"]
    return table_from_config(
        published, n_layers=config["n_layer"],
        experts_held=held_experts(config))


class KdaMlaMoeJob(MoeHybridJob):
    """The compiled step with its state: what set-up builds and the
    window drives."""

    def __init__(self, config, mix, devices):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec

        import chainermn_tpu
        from chainermn_tpu.communicators import build_mesh
        from chainermn_tpu.models.transformer import (
            TransformerLM,
            rebalance_routers,
        )
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
        if len(devices) != 1:
            raise ValueError("the step hands back one chip's choice of "
                             "experts: this runner drives one chip")
        if prog["attention"] != "flash" or prog["loss"] != "fused_ce":
            raise ValueError("this runner builds flash attention + fused "
                             "CE, as the configuration must say")
        if config["tie_word_embeddings"]:
            raise ValueError("this runner hands the loss the untied head")
        self.table = build_table(config)
        model = TransformerLM(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            table=self.table, remat=prog["remat"],
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
            h, seen = model.apply({"params": p}, tokens, return_hidden=True,
                                  mutable=["intermediates"])
            chosen = {name: layer["ExpertLayer_0"]["chosen"][0]
                      for name, layer in seen["intermediates"].items()}
            return fused_cross_entropy(
                h, p["lm_head"], labels, chunk=prog["ce_chunk"]), chosen

        self.step_fn = opt.make_train_step(loss_fn, donate=prog["donate"],
                                           has_aux=True)
        rate = config["balancing"]["rate"]
        self._rebalance = jax.jit(
            lambda p, chosen: rebalance_routers(p, chosen, rate),
            donate_argnums=(0,), out_shardings=self.replicated)
        self._norms = jax.jit(lambda tree: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree))
        self._delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))

    def make_weights(self, seed):
        return weights_ling3.make(self.config, seed, self.replicated)

    def step(self, batch):
        """One step and, beside the optimizer's update, the balancing
        controller's by the experts the step chose; ``routed`` gains
        them."""
        self.params, self.state, loss, chosen = self.step_fn(
            self.params, self.state, batch)
        self.params = self._rebalance(self.params, chosen)
        self.routed.append(chosen)
        return loss

    def scope_table(self):
        """The compiled step's scope table, lowered from abstract
        parameters and state and one placed batch (the window's own
        program: its compilation is a cache hit); prints what the
        compiler counted of its memory."""
        import jax

        from chainermn_tpu.observability import device_trace

        def placed(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=self.replicated), tree)

        params = placed(jax.eval_shape(
            lambda: weights_ling3.make(self.config, 0)))
        state = placed(jax.eval_shape(self.opt.init, params))
        compiled = self.step_fn.lower(params, state, self.feed(0)).compile()
        harness.say(f"memory_analysis: {compiled.memory_analysis()}")
        return device_trace.scope_table(compiled)


def routing_load(config, chosen):
    """One step's load on the held experts, a sparse layer, in layer
    order, and the share of tokens that kept the held experts' group."""
    from chainermn_tpu.parallel import moe_dropless

    return {name: moe_dropless.load_stats(
        chosen[name], config["num_experts_published"],
        held_experts(config), n_group=config["n_group"])
        for name in sorted(chosen, key=lambda n: int(n.split("_")[1]))}


def control_readings(run, job_like, precision):
    """The control: the reference in ``precision``, choosing for itself,
    as ``readings``; and the float32 reference that took its choice."""
    low = reference_readings(run, job_like, precision)
    low["chosen"] = chosen_from_masks(
        low["chosen"], run.config["num_experts_per_tok"])
    return low, reference_readings(run, job_like, forced=low["chosen"])


def reference_readings(run, job_like, precision="float32", forced=None):
    """Follow the first steps with the plain reference (or a control),
    its expert layers taking ``forced`` in place of their own choice."""
    import jax

    config, mix, seed = run.config, run.mix, run.seed
    batches = traffic.train_batches(mix, config["vocab_size"], seed)
    steps = [batches(i) for i in range(int(mix["reference_steps"]))]
    return reference.train_steps(
        lambda: weights_ling3.make(config, seed, job_like["replicated"]),
        steps, config, precision=precision, block_rows=len(run.devices),
        place=lambda x: jax.device_put(x, job_like["rows"]), forced=forced)


def first_steps(run, job):
    """Set-up's part on the device: the first steps by the window's own
    call and feed, with the experts each chose.  A Reporter is installed
    for as long (and no longer: the window runs without telemetry, as the
    other cells' do), so that the program's trace-time ``kda/*``,
    ``flash/*`` and ``moe/*`` gauges and the first batch's load are there
    to print (a program without them prints none)."""
    import jax

    from chainermn_tpu.observability import reporter
    from chainermn_tpu.ops.ssd import publish_geometry

    rep = reporter.Reporter()
    with reporter.scope(rep):
        readings = train.first_steps(job, int(run.mix["reference_steps"]))
        chosen = jax.device_get(job.routed)
        for name, load in routing_load(run.config, chosen[0]).items():
            publish_geometry("moe_load", f"moe/{name}", load)
            harness.say(f"moe load {name}: {load}")
    gauges = {k: v["value"] for k, v in rep.summary()["gauges"].items()
              if k.startswith(("moe/", "kda/", "flash/"))
              and "/layer_" not in k}
    harness.say(f"kda, flash and moe geometry (program gauges): {gauges}")
    return dict(readings, chosen=chosen)


def run(run):
    import jax
    from jax.profiler import TraceAnnotation

    config, mix, devices = run.config, run.mix, run.devices
    n_ref = int(mix["reference_steps"])
    run.stage("imports done, building the job")
    job = KdaMlaMoeJob(config, mix, devices)
    job.reset(run.seed)
    run.stage("weights and state made; first steps (compile when cold)")
    readings = first_steps(run, job)
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
    # Every step of the run, the first ones and the window's: where its
    # pairs went.  (A pair past the bound also makes the step's loss NaN.)
    loads = [routing_load(config, chosen)
             for chosen in jax.device_get(job.routed)]
    run.check("moe_pairs_past_bound", sum(
        s["pairs_past_bound"] for load in loads for s in load.values()), 0)
    traced = loads[n_ref + trace_at:n_ref + trace_at + trace_steps] if (
        run.trace) else None
    k = min(5, max(1, n // 2))
    head, tail = np.mean(host_losses[:k]), np.mean(host_losses[-k:])
    run.check("window_loss_last_minus_first", float(tail - head), 0.0,
              ok=bool(tail < head) or n < 2 * k)
    device = harness.device_report(devices)

    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    n_params = weights_ling3.n_params(config)
    window = [s for load in loads[n_ref:] for s in load.values()]
    harness.say(
        f"train_kda_mla_moe: steps={n} window_s={elapsed:.4f} "
        f"step_ms={step_ms:.4f} step_ms_outside_trace={clear_step_ms:.4f} "
        f"tokens_per_s_per_chip={tokens / (step_ms / 1e3) / len(devices):.1f}"
        f" model_tflop_per_step="
        f"{flops_ling3.train_flops_per_step(config, mix) / 1e12:.3f} "
        f"n_params={n_params} held_pairs_first_batch={held_pairs(loads[:1])}"
        f" held_pairs_traced_steps={traced and held_pairs(traced)} "
        f"held_pairs_last_step={held_pairs(loads[-1:])} "
        f"held_pairs_window_mean={held_pairs(loads[n_ref:])} "
        f"max_load_over_mean_window="
        f"{max(s['max_load_over_mean'] for s in window):.3f} "
        f"held_group_token_share_window="
        f"{np.mean([s['held_group_token_share'] for s in window]):.4f} "
        f"tile_fill_window={tile_fill(loads[n_ref:]):.4f} "
        f"first_losses={readings['losses']} "
        f"window_loss_first={head:.4f} window_loss_last={tail:.4f}")

    job_like = {"replicated": job.replicated, "rows": job.rows}
    job.release()
    del losses, last_batch
    scope_table = job.scope_table() if run.trace else None
    del job
    run.stage("window closed; reference")
    t_ref = time.perf_counter()
    ref = reference_readings(run, job_like, forced=readings["chosen"])
    harness.say(f"reference: {n_ref} steps in "
                f"{time.perf_counter() - t_ref:.1f} s, losses "
                f"{ref['losses']}")
    compare(run, readings, ref)

    layer_ctx = {
        "kind": "train_kda_mla_moe", "config": config, "mix": mix,
        "devices": devices, "device_kind": devices[0].device_kind,
        "steps": n, "window_s": elapsed, "step_ms": step_ms,
        "clear_step_ms": clear_step_ms, "n_params": n_params,
        "trace_steps": trace_steps, "trace": None,
        # What the grouped matmuls' roofline share counts its rows from:
        # the traced steps' own.
        "moe_held_pairs": traced and held_pairs(traced),
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
        say_owners(layer_ctx)
    return {"correct": run.correct, "attempted": n, "failed": failed,
            "end_to_end": {"train_step_ms": step_ms}, "setup_s": setup_s,
            "device": device, "layer_ctx": layer_ctx}
