"""Runner of ``kind: train_bd_moe`` traffic: an ``sdar_moe`` configuration
(identical layers of a GQA attention row with QK-norm and a softmax top-k
sparse-expert FFN; an untied head) TRAINED BY BLOCK DIFFUSION through the
same path as the other training cells — ``create_communicator`` ->
``create_multi_node_optimizer`` -> ``make_train_step``, flash attention,
fused cross-entropy over the head's own matrix — the model built from the
configuration's published keys by the program's own
``block_table.table_from_config``, told which experts this chip holds.
The runner states no mask, no position and no weight of its own: the
table says the rows run under the block-diffusion mask, and the
program's ``models.block_diffusion.block_diffusion_loss`` builds ``[x0 ;
xt]``, the positions and the weighted loss from the batch
``traffic_bd.py`` noised from ``--seed``.

:class:`BdMoeJob` is ``train_swa_moe.SwaMoeJob`` (the experts placed by
load at set-up) with another table, another loss, other seeded weights
and another reference; the window is timed by the same statements as the
other runners', so that ``train_step_ms`` means here what it means in
the other cells; and ``correct`` is what the other expert cells' is
(``train_moe_hybrid``'s docstring says what and why): the timed step
hands its routers' choice back (over all ``2 L`` rows) and, beside it,
its counters — the rows whose token was masked and the sum of the
weights — no held pair may lie past the row buffer's bound on any step,
the reference follows the first steps WITH the step's own choice.  The
share of (row, choice) pairs its own routers would have settled otherwise
is printed and not held to a limit: no precision of the matrix products
and no broken statement of the objective moves it (the limits file says
what was read), so it has no second reading to set one between.

One check more, the mask's own, FROM THE TIMED STEP.  Under seeded
weights a branch's output is a hundredth of the residual stream, so what
a CLEAN row attends reaches the loss at second order only: a clean row
that sees noisy keys moves no norm of the first steps past the rounding
of a sound run (``tools/control_train_bd_moe.py``).  So the compiled step
the window drives hands back, beside its routers' choice, what its first
layer's attention row added to the stream (flax's
``capture_intermediates`` on ``layer_0/MultiHeadAttention_0``: the
projections, QK-norm, the rotation at the handed positions, the flash
kernel under the mask the table states and the output projection, for
all ``2 L`` rows), and the first steps' are compared row by row with the
reference's first attention (:func:`worst_row_gap`): a row of the first
blocks has a handful of keys, so one key too many or too few, a wrong
block length or a lost cut between the copies moves it by its whole
norm.
"""

import math
import time

import numpy as np

from chipbench import (
    flops_sdar_moe,
    harness,
    traffic_bd,
    weights_sdar_moe,
)
from chipbench.refs import sdar_moe as reference
from chipbench.runners import train
from chipbench.runners.train_cca_moe import held_experts, routing_load
from chipbench.runners.train_gdn_moe import say_owners, tile_fill
from chipbench.runners.train_moe_hybrid import (
    MoeHybridJob,
    chosen_from_masks,
    differing_pairs_share,
    held_pairs,
)


#: The module whose output the step hands back for the mask's check.
FIRST_ATTENTION = ("layer_0", "MultiHeadAttention_0")


def build_table(config):
    """The program's block table from the published keys (and the
    objective's ``block_length``): the router keeps its published width;
    the layers kept and the experts held are the deployment's."""
    from chainermn_tpu.models.block_table import table_from_config

    published = dict(config, num_experts=config["num_experts_published"])
    return table_from_config(
        published, n_layers=config["n_layer"],
        experts_held=held_experts(config))


def rows_of(batch):
    """A batch's ``[x0 ; xt]``, what the layers and their routers see."""
    return np.concatenate(batch[:2], axis=1)


class BdMoeJob(MoeHybridJob):
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
        from chainermn_tpu.models.block_diffusion import block_diffusion_loss
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.ops import make_flash_attention_fn

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
        if self.table.block_diffusion != config["block_length"]:
            raise ValueError("the table's block is not the configuration's")
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
            x0, xt, weights = batch
            seen = {}

            def hidden(tokens, positions):
                h, state = model.apply(
                    {"params": p}, tokens, position_offset=positions,
                    return_hidden=True, mutable=["intermediates"],
                    capture_intermediates=lambda module, _: (
                        module.path == FIRST_ATTENTION))
                seen.update(state["intermediates"])
                return h

            loss, counters = block_diffusion_loss(
                hidden, p["lm_head"], x0, xt, weights,
                chunk=prog["ce_chunk"])
            chosen = {name: layer["ExpertLayer_0"]["chosen"][0]
                      for name, layer in seen.items()}
            layer, row = FIRST_ATTENTION
            return loss, (chosen, counters, seen[layer][row]["__call__"][0])

        self.step_fn = opt.make_train_step(loss_fn, donate=prog["donate"],
                                           has_aux=True)
        self._norms = jax.jit(lambda tree: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree))
        self._delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))

    def make_weights(self, seed):
        """The seed's tree with every layer's experts placed on the
        layer's chips by their load under the seed's first batch
        (``weights_sdar_moe.placement``): found once a seed, kept in
        ``placement`` for the reference to start from the same."""
        params = weights_sdar_moe.make(self.config, seed, self.replicated)
        if self.placement is None or self.placement[0] != seed:
            first = traffic_bd.train_batches(self.mix, self.config, seed)(0)
            self.placement = (seed, weights_sdar_moe.placement(
                params, rows_of(first), self.config))
            harness.say(f"experts placed: {self.placement[1]}")
        return weights_sdar_moe.with_placement(
            params, self.placement[1], self.replicated)

    def reset(self, seed):
        """Seeded weights, a fresh optimizer state and the seed's feed."""
        super().reset(seed)
        self.counters, self.attention = [], []
        self.batches = traffic_bd.train_batches(self.mix, self.config, seed)

    def step(self, batch):
        """One step; ``routed`` gains ``{layer: (rows, top_k) int}``, the
        experts the step's routers chose, ``counters`` the step's masked
        rows and sum of weights, and — for the first steps, those the
        reference follows — ``attention`` what the first layer's attention
        row added to the stream."""
        (self.params, self.state, loss,
         (chosen, counters, attention)) = self.step_fn(
            self.params, self.state, batch)
        self.routed.append(chosen)
        self.counters.append(counters)
        if len(self.attention) < int(self.mix["reference_steps"]):
            self.attention.append(attention)
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
            lambda: weights_sdar_moe.make(self.config, 0)))
        state = placed(jax.eval_shape(self.opt.init, params))
        compiled = self.step_fn.lower(params, state, self.feed(0)).compile()
        harness.say(f"memory_analysis: {compiled.memory_analysis()}")
        return device_trace.scope_table(compiled)


def like(job):
    """What the reference needs of a job once the job is gone: where to
    put arrays, and the placement of the job's seed."""
    return {"replicated": job.replicated, "rows": job.rows,
            "placement": job.placement}


def control_readings(run, job_like, precision):
    """The control: the reference in ``precision``, choosing for itself,
    as ``readings``; and the float32 reference that took its choice."""
    low = reference_readings(run, job_like, precision)
    low["chosen"] = chosen_from_masks(
        low["chosen"], run.config["num_experts_per_tok"])
    return low, reference_readings(run, job_like, forced=low["chosen"])


def reference_readings(run, job_like, precision="float32", forced=None,
                       broken=None):
    """Follow the first steps with the plain reference (or a control, or
    a ``broken`` statement of the objective), its expert layers taking
    ``forced`` in place of their own choice, from the job's own start:
    the seed's tree under ``job_like``'s placement (found here where a
    caller brings none)."""
    import jax

    config, mix, seed = run.config, run.mix, run.seed
    batches = traffic_bd.train_batches(mix, config, seed)
    steps = [batches(i) for i in range(int(mix["reference_steps"]))]
    held = job_like.get("placement")
    if held is None or held[0] != seed:
        held = (seed, weights_sdar_moe.placement(
            weights_sdar_moe.make(config, seed, job_like["replicated"]),
            rows_of(steps[0]), config))
    return reference.train_steps(
        lambda: weights_sdar_moe.with_placement(
            weights_sdar_moe.make(config, seed, job_like["replicated"]),
            held[1], job_like["replicated"]),
        steps, config, precision=precision, block_rows=len(run.devices),
        place=lambda x: jax.device_put(x, job_like["rows"]), forced=forced,
        broken=broken)


def worst_row_gap(got, want):
    """``max over steps and rows of |got - want| / |want|`` of what the
    first layer's attention row added to the stream, the norms over a
    row's width."""
    worst = 0.0
    for g, w in zip(got, want, strict=True):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        gap = np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
        worst = max(worst, float(np.max(np.where(
            np.isfinite(gap), gap, np.inf))))
    return worst


def compare_all(run, readings, ref):
    """The cell's whole comparison: ``train.compare``'s three readings of
    a reference that took ``readings["chosen"]`` for its experts, the
    routers' agreement (printed), and the first attention row by row."""
    train.compare(run, readings, ref)
    harness.say(f"router_pairs_differing_share (no limit): "
                f"{differing_pairs_share(readings['chosen'], ref['chosen'])}")
    run.check("blockdiff_attention_worst_row_gap",
              worst_row_gap(readings["attention"], ref["attention"]),
              run.limits["attention_row_gap"])


def first_steps(run, job):
    """Set-up's part on the device: the first steps by the window's own
    call and feed, with the experts each chose.  A Reporter is installed
    for as long (and no longer: the window runs without telemetry, as the
    other cells' do), so that the program's trace-time ``flash/*``,
    ``blockdiff/*`` and ``moe/*`` gauges and the first batch's load are
    there to print."""
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
              if k.startswith(("moe/", "flash/", "blockdiff/"))
              and "/layer_" not in k}
    harness.say(f"flash, block-diffusion and moe geometry (program "
                f"gauges): {gauges}")
    # (host copies in the device arrays' place: the window keeps none)
    job.attention = jax.device_get(job.attention)
    return dict(readings, chosen=chosen, attention=job.attention)


def run(run):
    import jax
    from jax.profiler import TraceAnnotation

    config, mix, devices = run.config, run.mix, run.devices
    n_ref = int(mix["reference_steps"])
    run.stage("imports done, building the job")
    job = BdMoeJob(config, mix, devices)
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
    counters = jax.device_get(job.counters)
    steps_traced = slice(n_ref + trace_at, n_ref + trace_at + trace_steps
                         ) if run.trace else None
    traced = loads[steps_traced] if run.trace else None
    # The loss of a step is a draw of its noise (weights up to 1 / eps):
    # the window's trend is read on means of five, as in the other cells.
    k = min(5, max(1, n // 2))
    head, tail = np.mean(host_losses[:k]), np.mean(host_losses[-k:])
    harness.say(f"window losses: first {k} mean {head:.4f}, last {k} mean "
                f"{tail:.4f}")
    device = harness.device_report(devices)

    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    n_params = weights_sdar_moe.n_params(config)
    masked = [int(c["masked_rows"]) for c in counters]
    harness.say(
        f"train_bd_moe: steps={n} window_s={elapsed:.4f} "
        f"step_ms={step_ms:.4f} step_ms_outside_trace={clear_step_ms:.4f} "
        f"documents_tokens_per_s_per_chip="
        f"{tokens / (step_ms / 1e3) / len(devices):.1f}"
        f" model_tflop_per_step="
        f"{flops_sdar_moe.train_flops_per_step(config, mix) / 1e12:.3f} "
        f"n_params={n_params} held_pairs_first_batch={held_pairs(loads[:1])}"
        f" held_pairs_traced_steps={traced and held_pairs(traced)} "
        f"held_pairs_last_step={held_pairs(loads[-1:])} "
        f"held_pairs_window_mean={held_pairs(loads[n_ref:])} "
        f"tile_fill_window={tile_fill(loads[n_ref:]):.4f} "
        f"masked_rows_mean={np.mean(masked):.1f} "
        f"masked_share_of_head_rows={np.mean(masked) / tokens:.4f} "
        f"masked_rows_min_max={min(masked)},{max(masked)} "
        f"weight_sum_mean="
        f"{np.mean([float(c['weight_sum']) for c in counters]):.1f} "
        f"first_losses={readings['losses']}")

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
        "kind": "train_bd_moe", "config": config, "mix": mix,
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
