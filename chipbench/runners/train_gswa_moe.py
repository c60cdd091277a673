"""Runner of ``kind: train_gswa_moe`` traffic: a ``laguna`` configuration
(one full-attention row to three sliding-window rows whose query-head
counts differ, a sigmoid gate a head on every attention output, a leading
dense SwiGLU FFN, then a sigmoid top-k sparse-expert FFN with a shared
expert; an untied head) trained through the same path as the other
training cells — ``create_communicator`` ->
``create_multi_node_optimizer`` -> ``make_train_step``, flash attention,
fused cross-entropy over the head's own matrix — the model built from the
configuration's published keys by the program's own
``block_table.table_from_config``, told which experts this chip holds.
The runner sets no head count, no window, no frequency, no gate and no
expert count of its own: each attention row hands its own shape and
window to the one flash adapter.

:class:`GswaMoeJob` is ``train_swa_moe.SwaMoeJob`` (the experts placed by
load at set-up) with another table, other seeded weights and another
reference; the window is timed by the same statements as the other
runners', so that ``train_step_ms`` means here what it means in the
other cells; and ``correct`` is what the other expert cells' is
(``train_moe_hybrid``'s docstring says what and why): the timed step
hands its routers' choice back, no held pair may lie past the row
buffer's bound on any step, the reference follows the first steps WITH
the step's own choice, and the share of (token, choice) pairs its own
routers would have settled otherwise is compared with a limit of its
own.

One check more, the attention rows' own, FROM THE TIMED STEP.  Under
seeded weights a branch's output is a hundredth of the residual stream
(``PERF.md`` section 7), so a row that lost its window, its gate, its
half-head rotation or its ``attention_factor`` moves the loss and the
norms of the first steps by little.  So the compiled step the window
drives hands back, beside its routers' choice, what the attention rows
the configuration names (``attention_rows_compared``: one sliding row,
one full row, each after an expert layer) added to the stream (flax's
``capture_intermediates`` on ``layer_<i>/MultiHeadAttention_0``: the
projections, the rotation, the flash kernel under the row's own window,
the gate and the output projection), and the first steps' are compared
row by row with the reference's (``train_bd_moe.worst_row_gap``).
"""

import math
import time

import numpy as np

from chipbench import flops_laguna, harness, traffic, weights_laguna
from chipbench.refs import laguna as reference
from chipbench.runners import train
from chipbench.runners.train_bd_moe import like, worst_row_gap  # noqa: F401
from chipbench.runners.train_cca_moe import held_experts, routing_load
from chipbench.runners.train_gdn_moe import say_owners, tile_fill
from chipbench.runners.train_moe_hybrid import (
    MoeHybridJob,
    chosen_from_masks,
    compare,
    differing_pairs_share,  # noqa: F401  (the control tool reads it here)
    held_pairs,
)

ATTENTION = "MultiHeadAttention_0"


def build_table(config):
    """The program's block table from the published keys: the router
    keeps its published width; the layers kept and the experts held are
    the deployment's."""
    from chainermn_tpu.models.block_table import table_from_config

    published = dict(config, num_experts=config["num_experts_published"])
    return table_from_config(
        published, n_layers=config["n_layer"],
        experts_held=held_experts(config))


class GswaMoeJob(MoeHybridJob):
    """The compiled step with its state: what set-up builds and the
    window drives."""

    placement = None            # (seed, its experts' placement)

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
        compared = tuple(config["attention_rows_compared"])
        paths = {(name, ATTENTION) for name in compared}

        def loss_fn(p, batch):
            tokens, labels = batch
            h, seen = model.apply(
                {"params": p}, tokens, return_hidden=True,
                mutable=["intermediates"],
                capture_intermediates=lambda module, _: (
                    module.path in paths))
            seen = seen["intermediates"]
            chosen = {name: layer["ExpertLayer_0"]["chosen"][0]
                      for name, layer in seen.items()
                      if "ExpertLayer_0" in layer}
            added = {name: seen[name][ATTENTION]["__call__"][0]
                     for name in compared}
            return fused_cross_entropy(
                h, p["lm_head"], labels, chunk=prog["ce_chunk"]), (
                    chosen, added)

        self.step_fn = opt.make_train_step(loss_fn, donate=prog["donate"],
                                           has_aux=True)
        self._norms = jax.jit(lambda tree: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree))
        self._delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))

    def make_weights(self, seed):
        """The seed's tree with every sparse layer's experts placed on
        the layer's chips by their load under the seed's first batch
        (``weights_laguna.placement``): found once a seed, kept in
        ``placement`` for the reference to start from the same."""
        params = weights_laguna.make(self.config, seed, self.replicated)
        if self.placement is None or self.placement[0] != seed:
            tokens, _ = traffic.train_batches(
                self.mix, self.config["vocab_size"], seed)(0)
            self.placement = (seed, weights_laguna.placement(
                params, tokens, self.config))
            harness.say(f"experts placed: {self.placement[1]}")
        return weights_laguna.with_placement(
            params, self.placement[1], self.replicated)

    def reset(self, seed):
        super().reset(seed)
        self.attention = []

    def step(self, batch):
        """One step; ``routed`` gains ``{layer: (tokens, top_k) int}``,
        the experts the step's routers chose, and — for the first steps,
        those the reference follows — ``attention`` what the compared
        attention rows added to the stream."""
        (self.params, self.state, loss, (chosen, added)) = self.step_fn(
            self.params, self.state, batch)
        self.routed.append(chosen)
        if len(self.attention) < int(self.mix["reference_steps"]):
            self.attention.append(added)
        return loss

    def lowered(self):
        """The window's own program lowered from abstract parameters and
        state and one placed batch."""
        import jax

        def placed(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=self.replicated), tree)

        params = placed(jax.eval_shape(
            lambda: weights_laguna.make(self.config, 0)))
        state = placed(jax.eval_shape(self.opt.init, params))
        return self.step_fn.lower(params, state, self.feed(0))

    def scope_table(self):
        """The compiled step's scope table (its compilation is a cache
        hit); prints what the compiler counted of its memory."""
        from chainermn_tpu.observability import device_trace

        compiled = self.lowered().compile()
        harness.say(f"memory_analysis: {compiled.memory_analysis()}")
        return device_trace.scope_table(compiled)


def control_readings(run, job_like, precision):
    """The control: the reference in ``precision``, choosing for itself,
    as ``readings``; and the float32 reference that took its choice."""
    low = reference_readings(run, job_like, precision)
    low["chosen"] = chosen_from_masks(
        low["chosen"], run.config["num_experts_per_tok"])
    return low, reference_readings(run, job_like, forced=low["chosen"])


def seeded(run, job_like):
    """``(make_params, steps)``: the job's own start — the seed's tree
    under ``job_like``'s placement (found here where a caller brings
    none) — and the batches the reference follows."""
    config, mix, seed = run.config, run.mix, run.seed
    batches = traffic.train_batches(mix, config["vocab_size"], seed)
    steps = [batches(i) for i in range(int(mix["reference_steps"]))]
    held = job_like.get("placement")
    if held is None or held[0] != seed:
        held = (seed, weights_laguna.placement(
            weights_laguna.make(config, seed, job_like["replicated"]),
            steps[0][0], config))
        job_like["placement"] = held
    return (lambda: weights_laguna.with_placement(
        weights_laguna.make(config, seed, job_like["replicated"]),
        held[1], job_like["replicated"])), steps


def reference_readings(run, job_like, precision="float32", forced=None):
    """Follow the first steps with the plain reference (or a control),
    its expert layers taking ``forced`` in place of their own choice,
    from the job's own start."""
    import jax

    make_params, steps = seeded(run, job_like)
    return reference.train_steps(
        make_params, steps, run.config, precision=precision,
        block_rows=len(run.devices),
        place=lambda x: jax.device_put(x, job_like["rows"]), forced=forced,
        keep=tuple(run.config["attention_rows_compared"]))


def first_attention(run, job_like, forced, precision="float32",
                    broken=None):
    """What the compared attention rows add to the stream in the FIRST
    step, by the reference alone (a forward pass from the job's own
    start, the expert layers taking ``forced``, the first step's choice):
    ``{layer name: (B, S, d)}``, as one step of
    ``readings["attention"]``.  ``broken``: one of ``refs/laguna.BROKEN``,
    what the control tool holds the comparison against at the cell's
    size."""
    import jax

    make_params, steps = seeded(run, job_like)
    tokens = steps[0][0]
    names = tuple(run.config["attention_rows_compared"])
    place = lambda x: jax.device_put(x, job_like["rows"])  # noqa: E731
    f = {name: place(np.asarray(c).reshape(tokens.shape + (-1,)))
         for name, c in forced.items()}
    return jax.device_get(jax.jit(lambda p, t, f: reference.attention_rows(
        p, t, run.config, names, precision, f, broken))(
            make_params(), place(tokens), f))


def row_gaps(run, got, want):
    """``{layer name: worst row gap}`` of the compared attention rows
    over the steps of ``got`` and ``want`` (``{layer name: rows}`` a
    step)."""
    return {name: worst_row_gap([step[name] for step in got],
                                [step[name] for step in want])
            for name in run.config["attention_rows_compared"]}


def compare_all(run, readings, ref):
    """The cell's whole comparison: ``train_moe_hybrid.compare``'s four
    readings of a reference that took ``readings["chosen"]`` for its
    experts, and the compared attention rows, row by row, each under its
    own name."""
    compare(run, readings, ref)
    for name, gap in row_gaps(run, readings["attention"],
                              ref["attention"]).items():
        run.check(f"attention_{name}_worst_row_gap", gap,
                  run.limits["attention_row_gap"])


def first_steps(run, job):
    """Set-up's part on the device: the first steps by the window's own
    call and feed, with the experts each chose.  A Reporter is installed
    for as long (and no longer: the window runs without telemetry, as the
    other cells' do), so that the program's trace-time ``flash/*`` (the
    census of every row SHAPE: ``flash/shape/*``) and ``moe/*`` gauges
    and the first batch's load are there to print."""
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
              if k.startswith(("moe/", "flash/")) and "/layer_" not in k}
    harness.say(f"flash and moe geometry (program gauges): {gauges}")
    # (host copies in the device arrays' place: the window keeps none)
    job.attention = jax.device_get(job.attention)
    return dict(readings, chosen=chosen, attention=job.attention)


def run(run):
    import jax
    from jax.profiler import TraceAnnotation

    config, mix, devices = run.config, run.mix, run.devices
    n_ref = int(mix["reference_steps"])
    run.stage("imports done, building the job")
    job = GswaMoeJob(config, mix, devices)
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
    n_params = weights_laguna.n_params(config)
    harness.say(
        f"train_gswa_moe: steps={n} window_s={elapsed:.4f} "
        f"step_ms={step_ms:.4f} step_ms_outside_trace={clear_step_ms:.4f} "
        f"tokens_per_s_per_chip={tokens / (step_ms / 1e3) / len(devices):.1f}"
        f" model_tflop_per_step="
        f"{flops_laguna.train_flops_per_step(config, mix) / 1e12:.3f} "
        f"n_params={n_params} held_pairs_first_batch={held_pairs(loads[:1])}"
        f" held_pairs_traced_steps={traced and held_pairs(traced)} "
        f"held_pairs_last_step={held_pairs(loads[-1:])} "
        f"held_pairs_window_mean={held_pairs(loads[n_ref:])} "
        f"tile_fill_window={tile_fill(loads[n_ref:]):.4f} "
        f"first_losses={readings['losses']} "
        f"window_loss_first={head:.4f} window_loss_last={tail:.4f}")

    job_like = like(job)
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
    compare_all(run, readings, ref)

    layer_ctx = {
        "kind": "train_gswa_moe", "config": config, "mix": mix,
        "devices": devices, "device_kind": devices[0].device_kind,
        "steps": n, "window_s": elapsed, "step_ms": step_ms,
        "clear_step_ms": clear_step_ms, "n_params": n_params,
        "trace_steps": trace_steps, "trace": None,
        # What the grouped matmuls' roofline share counts its rows from,
        # and the fill of the tiles they lie in: the traced steps' own.
        "moe_held_pairs": traced and held_pairs(traced),
        "moe_tile_fill": traced and tile_fill(traced),
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
