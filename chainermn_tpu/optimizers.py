"""Multi-node optimizer — the data-parallel hot path.

Reference: REF:chainermn/optimizers.py — ``create_multi_node_optimizer(
actual_optimizer, communicator, double_buffering=False)`` wraps any Chainer
optimizer; on ``update()`` it (first call) broadcasts model parameters from
rank 0, then runs local backward, ``communicator.allreduce_grad(model)``,
and the inner optimizer's update.  ``_DoubleBufferingOptimizer`` overlaps
this step's allreduce with the next step's compute, applying one-step-stale
averaged gradients.

TPU-native translation (SURVEY §7 "hard part 2" — the eager-API ↔
traced-step impedance): the reference's imperative per-step
``allreduce_grad`` call becomes a collective *traced into* one jitted step
function.  ``make_train_step`` builds that step: a ``shard_map`` over the
communicator's mesh computes per-device gradients on the local batch shard,
runs the communicator's characteristic allreduce, and applies an inner
`optax` transformation on the (now replicated) mean gradients.  XLA then
owns the overlap: async collectives hide the allreduce behind surrounding
compute where data dependence allows, which is what the reference's
dedicated side stream bought it.

Double buffering keeps its reference *semantics* (apply one-step-stale
means; the first call only reduces, no update) because the staleness — not
the stream machinery — is what changes training behavior; the overlap
itself widens, since with stale application the collective's result is not
needed until the *next* step and XLA may overlap it across the entire
step boundary.

The imperative parity surface (``setup``/``update``/``target``) is a thin
stateful veneer over the functional path for users arriving from the
reference API.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from chainermn_tpu.communicators.base import CommunicatorBase
from chainermn_tpu.observability import startup as _startup
from chainermn_tpu.observability.spans import named_scope


def _check_batch_divisibility(batch, n_dev, n_accum=1):
    quantum = n_dev * n_accum
    for leaf in jax.tree.leaves(batch):
        if hasattr(leaf, "shape") and leaf.shape and leaf.shape[0] % quantum:
            raise ValueError(
                f"global batch axis ({leaf.shape[0]}) must be divisible by "
                f"device count x n_accum ({n_dev} x {n_accum} = {quantum}); "
                f"pad or drop the remainder (see datasets.toy.batch_iterator "
                f"drop_last)"
            )


_AOT_METHODS = ("lower", "trace", "eval_shape")


def _forward_aot(step, jitted_for):
    """Give a plain-function ``step`` wrapper jit's AOT surface.

    ``lower``/``trace``/``eval_shape`` are methods of the jitted callable,
    not entries of its ``__dict__``, so ``functools.wraps`` does not carry
    them.  ``jitted_for(*args)`` returns the jitted callable ``step``
    dispatches those arguments to (ZeRO steps build theirs per parameter
    tree).  ``chip_smoke.py`` and the benchmark's scope reader lower the
    step for its compiled text and the collective linter reads donation
    off ``trace``: a step without the surface is an error there, not a
    fallback."""

    def forward(name):
        def method(*args, **kwargs):
            return getattr(jitted_for(*args), name)(*args, **kwargs)

        return method

    for name in _AOT_METHODS:
        setattr(step, name, forward(name))
    return step


def _carry_step_surface(wrapper, step_fn):
    """Re-expose a built step's AOT surface (and the recompile-count
    guard's ``_cache_size``, where the step has one) on ``wrapper``."""
    for name in _AOT_METHODS:
        setattr(wrapper, name, getattr(step_fn, name))
    if hasattr(step_fn, "_cache_size"):
        wrapper._cache_size = step_fn._cache_size
    return wrapper


def _instrument_step(step_fn):
    """Host-side wrapper of a built train step.  Every call runs under
    the ``chainermn:train_step`` profiler annotation (about a microsecond
    with no profiler session), so a capture can put the device's idle
    gaps down to it.  When a Reporter or StepRecorder is installed
    (``observability.telemetry_active``) the call also runs under
    ``span("train_step")`` — host-side duration into both sinks — and
    bumps the reporter's ``train_step_calls`` counter.  The first calls'
    start and end go to the start-up ledger (``observability.startup``:
    the compile stages of the first call parent to it)."""
    from chainermn_tpu.observability import spans as _spans

    @functools.wraps(step_fn)
    def instrumented(*args, **kwargs):
        call = _startup.open_call("train_step")    # None past the record
        try:
            if not _spans.telemetry_active():
                with _spans.annotate("train_step"):
                    return step_fn(*args, **kwargs)
            from chainermn_tpu.observability import reporter as _rep

            with _spans.span("train_step"):
                out = step_fn(*args, **kwargs)
            rep = _rep.get_reporter()
            if rep is not None:
                rep.count("train_step_calls")
            return out
        finally:
            _startup.close(call)

    return _carry_step_surface(instrumented, step_fn)


def _jit_step(mapped, name, donate_argnums):
    """``jax.jit`` a step body under a program name of the vocabulary
    (``observability/spans.py``): the module compiles as ``jit_<name>``,
    which is how a profiler capture lists it, whatever the body's
    function was called."""

    def program(*args):
        return mapped(*args)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, donate_argnums=donate_argnums)


def _run_first_call_lint(step_fn, comm, mode, args, kwargs):
    """One lint pass over the step being compiled for the first time.
    Lint infrastructure failures must never take down training, so
    everything short of a strict-mode violation is a warning."""
    import warnings

    try:
        from chainermn_tpu.analysis import analyze_fn

        report = analyze_fn(step_fn, *args, comm=comm, **kwargs)
    except Exception as e:  # tracing oddity, not a user bug
        warnings.warn(f"CHAINERMN_TPU_LINT: lint pass failed: {e!r}")
        return
    try:
        from chainermn_tpu.observability import reporter as _rep
        from chainermn_tpu.observability import step_log as _sl

        rep = _rep.get_reporter()
        if rep is not None:
            rep.count("lint/findings", len(report.findings))
            rep.count("lint/errors", len(report.errors))
        rec = _sl.current_recorder()
        if rec is not None:
            rec.record(
                "lint",
                rules_run=list(report.rules_run),
                findings=[f.summary() for f in report.findings],
            )
    except Exception:
        pass
    if report.errors:
        if mode == "strict":
            from chainermn_tpu.analysis import LintError

            raise LintError(report)
        warnings.warn(
            "CHAINERMN_TPU_LINT found problems in the train step:\n"
            + report.render()
        )


def _lint_hook(step_fn, comm):
    """Opt-in static lint at the step's first call (the call that pays
    for compilation anyway): ``CHAINERMN_TPU_LINT=1`` warns and reports
    through the Reporter/step log, ``=strict`` raises ``LintError``.
    Unset, the step function passes through untouched — and after the
    first call the cost is one list check."""
    mode = os.environ.get("CHAINERMN_TPU_LINT", "").strip().lower()
    if mode in ("", "0", "off", "false"):
        return step_fn
    done = []

    @functools.wraps(step_fn)
    def linted(*args, **kwargs):
        if not done:
            done.append(True)
            _run_first_call_lint(step_fn, comm, mode, args, kwargs)
        return step_fn(*args, **kwargs)

    return _carry_step_surface(linted, step_fn)


def flat_shard_state_spec(optimizer, shard_size: int, world):
    """Per-leaf PartitionSpecs for an optax state over a flat fp32 shard:
    shard-sized 1-D leaves ride the world axes, scalars (e.g. adam's count)
    replicate.  Shared by the ZeRO optimizer paths and the sharded
    MultiNodeChainList tier."""

    def leaf_spec(leaf):
        shape = getattr(leaf, "shape", ())
        return P(world) if (len(shape) == 1 and shape[0] == shard_size) else P()

    shard = jax.ShapeDtypeStruct((shard_size,), jnp.float32)
    state_shape = jax.eval_shape(optimizer.init, shard)
    return jax.tree.map(leaf_spec, state_shape)


class MultiNodeOptimizerState(NamedTuple):
    inner: Any            # the wrapped optax optimizer's state
    step: jnp.ndarray     # int32 step counter
    comm_buf: Any         # double buffering: previous step's averaged grads
                          # (None-like zeros tree when double_buffering=False)


class MultiNodeOptimizer:
    """Wrap an ``optax.GradientTransformation`` with distributed gradient
    averaging — the reference's ``_MultiNodeOptimizer`` reimagined for
    traced steps."""

    def __init__(
        self,
        actual_optimizer: optax.GradientTransformation,
        communicator: CommunicatorBase,
        double_buffering: bool = False,
        zero_stage: int = 0,
    ):
        """ZeRO staging (the TPU-native memory ladder the reference never
        had — its optimizer state, gradients, and parameters were fully
        replicated per GPU):

        - ``zero_stage=1``: optimizer state sharded 1/n per device.
          Gradients arrive by reduce-scatter, the inner optimizer updates
          only the local flat shard, updated parameters are all-gathered.
        - ``zero_stage=2``: additionally, with gradient accumulation
          (``n_accum > 1``) each microbatch's gradients are reduce-scattered
          immediately, so the accumulator is a 1/n shard instead of a full
          gradient tree.  Without accumulation it is identical to stage 1
          (inside one fused step XLA never materializes persistent full
          gradients anyway).
        - ``zero_stage=3``: master parameters themselves live sharded 1/n
          per device between steps as one flat fp32 buffer; each step
          all-gathers them, computes, reduce-scatters gradients, and
          updates only the local shard.  The train step then takes and
          returns the flat buffer — use :meth:`shard_params` /
          :meth:`materialize` to convert to/from the user pytree.
        """
        self.actual_optimizer = actual_optimizer
        self.communicator = communicator
        self.double_buffering = double_buffering
        if zero_stage not in (0, 1, 2, 3):
            raise ValueError("zero_stage must be 0, 1, 2 or 3")
        self.zero_stage = zero_stage
        # ZeRO-3 pack metadata: (treedef, [(shape, dtype, size)]) captured by
        # shard_params/init so the flat buffer can be unpacked without the
        # original tree in hand.  _z3_jit caches the shard/materialize jits
        # per metadata so repeated calls don't recompile.
        self._z3_meta = None
        self._z3_jit = {}
        # imperative-parity state (setup/update/target)
        self._params = None
        self._state = None
        self._step_fn = None
        self._setup_has_aux = False

    # ------------------------------------------------------------------
    # Functional API
    # ------------------------------------------------------------------
    def init(self, params, *, _skip_broadcast: bool = False
             ) -> MultiNodeOptimizerState:
        """Initialize optimizer state.  The analogue of the reference's
        first-``update`` ``broadcast_data``: parameters are replicated from
        process 0 so every host starts identical.  (``_skip_broadcast``:
        internal — setup() broadcasts once itself and must not pay the
        full-tree collective twice.)"""
        if not _skip_broadcast:
            params = self.broadcast_params(params)
        if self.zero_stage == 3:
            self._capture_z3_meta(params)
        if self.zero_stage > 0:
            inner = self._zero_init(params)
        else:
            inner = self.actual_optimizer.init(params)
        if not self.double_buffering:
            zeros = ()
        elif self.zero_stage > 0:
            # Stale means live as the 1/n fp32 gradient shard — double
            # buffering costs shard-sized memory under ZeRO, not a full
            # gradient tree.
            n, _, shard_size = self._zero_geometry(params)
            zeros = jnp.zeros((shard_size * n,), jnp.float32)
        else:
            zeros = jax.tree.map(jnp.zeros_like, params)
        return MultiNodeOptimizerState(
            inner=inner,
            step=jnp.zeros((), jnp.int32),
            comm_buf=zeros,
        )

    # ------------------------------------------------------------------
    # ZeRO-1 plumbing: flat padded buffer, per-device shard
    # ------------------------------------------------------------------
    def _zero_geometry(self, params):
        n = self.communicator.device_size
        total = sum(l.size for l in jax.tree.leaves(params))
        pad = (-total) % n
        return n, total, (total + pad) // n

    def _zero_pack(self, tree, padded_size):
        from chainermn_tpu.communicators.packing import pack_tree

        return pack_tree(
            jax.tree.map(
                lambda x: x if x.dtype == jnp.float32
                else x.astype(jnp.float32),
                tree,
            ),
            pad_to=padded_size,
        )

    def _zero_inner_spec(self, shard_size):
        return flat_shard_state_spec(
            self.actual_optimizer, shard_size, self.communicator.world_axes
        )

    def _zero_init(self, params):
        comm = self.communicator
        n, total, shard_size = self._zero_geometry(params)

        def body(params):
            flat, _ = self._zero_pack(params, shard_size * n)
            mine = lax.dynamic_slice_in_dim(
                flat, comm.axis_index() * shard_size, shard_size
            )
            return self.actual_optimizer.init(mine)

        return jax.jit(
            comm.shard_map(
                body, in_specs=(P(),), out_specs=self._zero_inner_spec(shard_size)
            )
        )(params)

    # ------------------------------------------------------------------
    # ZeRO-3 plumbing: params live as ONE flat fp32 buffer sharded P(world)
    # ------------------------------------------------------------------
    def _capture_z3_meta(self, params):
        leaves, treedef = jax.tree.flatten(params)
        self._z3_meta = (
            treedef,
            [(l.shape, l.dtype, l.size) for l in leaves],
        )

    def _z3_unpack(self, buf):
        """Unflatten the gathered fp32 buffer back into the user pytree at
        each leaf's original shape and dtype (the forward-compute copy)."""
        treedef, metas = self._z3_meta
        out, off = [], 0
        for shape, dtype, size in metas:
            out.append(buf[off : off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(treedef, out)

    def _world_axis(self):
        comm = self.communicator
        return comm.axes if len(comm.axes) > 1 else comm.axes[0]

    def _z3_key(self, kind):
        treedef, metas = self._z3_meta
        return (kind, treedef, tuple(metas))

    def shard_params(self, params):
        """ZeRO-3 entry: user pytree → flat fp32 master buffer, one 1/n
        shard resident per device.  The returned array is what the stage-3
        train step takes and returns in place of the pytree."""
        if self.zero_stage != 3:
            raise ValueError("shard_params is only meaningful for zero_stage=3")
        comm = self.communicator
        self._capture_z3_meta(params)
        n, _, shard_size = self._zero_geometry(params)
        world = self._world_axis()

        fn = self._z3_jit.get(self._z3_key("shard"))
        if fn is None:

            def body(tree):
                flat, _ = self._zero_pack(tree, shard_size * n)
                return lax.dynamic_slice_in_dim(
                    flat, comm.axis_index() * shard_size, shard_size
                )

            fn = jax.jit(comm.shard_map(body, in_specs=(P(),), out_specs=P(world)))
            self._z3_jit[self._z3_key("shard")] = fn
        return fn(params)

    def materialize(self, flat):
        """ZeRO-3 exit: flat sharded master buffer → replicated user pytree
        (for evaluation, checkpoint export, or leaving stage-3 training)."""
        if self._z3_meta is None:
            raise RuntimeError("call shard_params (or init) before materialize")
        comm = self.communicator
        world = self._world_axis()

        fn = self._z3_jit.get(self._z3_key("mat"))
        if fn is None:

            def body(local):
                full = lax.all_gather(local, world, axis=0, tiled=True)
                return self._z3_unpack(full)

            fn = jax.jit(comm.shard_map(body, in_specs=(P(world),), out_specs=P()))
            self._z3_jit[self._z3_key("mat")] = fn
        return fn(flat)

    def broadcast_params(self, params):
        """Host-plane replication from process 0 (reference
        ``broadcast_data``).  A no-op on one host: device-plane replication
        is the sharding's job under jit."""
        if self.communicator.size > 1:
            from jax.experimental import multihost_utils

            params = multihost_utils.broadcast_one_to_all(params)
        return params

    # ------------------------------------------------------------------
    # Microbatch gradient machinery shared by every stage
    # ------------------------------------------------------------------
    def _make_micro_grad_fn(self, loss_fn, has_aux, loss_scale):
        """Return ``one(params, microbatch, key) -> (loss, aux, grads)``.

        With ``loss_scale`` the returned gradients are SCALED — they stay
        scaled through accumulation and the (possibly reduced-precision)
        collective, preserving small-magnitude structure on the wire, and
        are unscaled by the caller just before the optimizer update.  The
        returned loss is always unscaled.
        """

        def one(params, mb, key):
            f = loss_fn if key is None else (lambda p, b: loss_fn(p, b, key))
            if loss_scale is not None:
                if has_aux:
                    g = lambda p, b: (  # noqa: E731
                        lambda o: (o[0] * loss_scale, o[1])
                    )(f(p, b))
                else:
                    g = lambda p, b: f(p, b) * loss_scale  # noqa: E731
            else:
                g = f
            out, grads = jax.value_and_grad(g, has_aux=has_aux)(params, mb)
            loss, aux = out if has_aux else (out, None)
            if loss_scale is not None:
                loss = loss / loss_scale
            return loss, aux, grads

        return one

    def _split_micro(self, batch, n_accum):
        """(B, ...) local batch → (n_accum, B/n_accum, ...) microbatches."""
        return jax.tree.map(
            lambda x: x.reshape(n_accum, x.shape[0] // n_accum, *x.shape[1:]),
            batch,
        )

    def _base_key(self, rng, step):
        if rng is None:
            return None
        return jax.random.fold_in(
            jax.random.fold_in(rng, step), self.communicator.axis_index()
        )

    def _accum_local_grads(self, one, params, batch, base_key, n_accum):
        """Scan the microbatches, accumulating FULL local gradient trees
        (stages 0 and 1).  Returns (mean_loss, stacked_aux, mean_grads)."""
        with named_scope("fwd-bwd"):
            if n_accum == 1:
                loss, aux, grads = one(
                    params, batch, base_key
                )
                return loss, aux, grads

            micro = self._split_micro(batch, n_accum)

            def mb(carry, xs):
                gacc, lacc = carry
                i, b = xs
                key = (None if base_key is None
                       else jax.random.fold_in(base_key, i))
                loss, aux, grads = one(params, b, key)
                gacc = jax.tree.map(jnp.add, gacc, grads)
                return (gacc, lacc + loss), aux

            zeros = jax.tree.map(jnp.zeros_like, params)
            (gacc, lsum), auxs = lax.scan(
                mb, (zeros, jnp.zeros((), jnp.float32)),
                (jnp.arange(n_accum), micro)
            )
            grads = jax.tree.map(lambda g: g / n_accum, gacc)
            return lsum / n_accum, auxs, grads

    def _apply_update(self, params, state, grads, loss_scale=None,
                      overlap=None, exchanged=False):
        """Allreduce local grads and apply the inner optimizer — the shared
        tail of the stage-0 step bodies.

        With ``double_buffering``: allreduce this step's grads into buffer
        B, *apply* last step's averaged buffer A (reference
        _DoubleBufferingOptimizer), skipping the inner update entirely on
        step 0.  Scaled gradients (``loss_scale``) are unscaled exactly
        once, at application time.

        ``overlap`` pins the communicator's staged bucket emission for this
        step (``None`` defers to ctor/env — see
        :meth:`CommunicatorBase.allreduce_grad`): when on, each bucket's
        pack+allreduce is emitted as its last grad leaf becomes available
        (reverse leaf-production order), generalizing the double-buffering
        idea — instead of hiding the whole allreduce behind the *next*
        step's compute at one-step staleness, buckets hide behind *this*
        step's remaining backward compute with no staleness at all.
        """
        comm = self.communicator
        opt = self.actual_optimizer

        def mean(grads):
            # ``exchanged``: CommunicatorBase.mean_grads_under has laid
            # the exchange under the backward pass already.
            if exchanged:
                return grads
            with named_scope("allreduce"):
                return comm.allreduce_grad(grads, overlap=overlap)

        if self.double_buffering:
            new_mean = mean(grads)
            stale = state.comm_buf

            def do_update(operand):
                params, inner, stale = operand
                if loss_scale is not None:
                    stale = jax.tree.map(lambda g: g / loss_scale, stale)
                updates, inner = opt.update(stale, inner, params)
                return optax.apply_updates(params, updates), inner

            with named_scope("opt-update"):
                params, inner = lax.cond(
                    state.step > 0,
                    do_update,
                    lambda operand: (operand[0], operand[1]),
                    (params, state.inner, stale),
                )
            return params, MultiNodeOptimizerState(
                inner=inner, step=state.step + 1, comm_buf=new_mean
            )
        grads = mean(grads)
        if loss_scale is not None:
            grads = jax.tree.map(lambda g: g / loss_scale, grads)
        with named_scope("opt-update"):
            updates, inner = opt.update(grads, state.inner, params)
            params = optax.apply_updates(params, updates)
        return params, MultiNodeOptimizerState(
            inner=inner, step=state.step + 1, comm_buf=()
        )

    def _apply_shard_update(self, pshard, state, gshard, loss_scale=None):
        """The ZeRO analogue of :meth:`_apply_update`: apply a gradient
        *shard* to the local parameter shard.  With ``double_buffering``
        the stale shard in ``comm_buf`` is applied (skipping step 0) and
        this step's ``gshard`` is stored for the next — identical staleness
        semantics to stage 0, at 1/n the buffer memory.  Scaled gradients
        are unscaled exactly once, at application time."""
        opt = self.actual_optimizer
        if self.double_buffering:

            def do_update(operand):
                pshard, inner, stale = operand
                if loss_scale is not None:
                    stale = stale / loss_scale
                updates, inner = opt.update(stale, inner, pshard)
                return optax.apply_updates(pshard, updates), inner

            with named_scope("opt-update"):
                pshard, inner = lax.cond(
                    state.step > 0,
                    do_update,
                    lambda operand: (operand[0], operand[1]),
                    (pshard, state.inner, state.comm_buf),
                )
            new_state = MultiNodeOptimizerState(
                inner=inner, step=state.step + 1, comm_buf=gshard
            )
            return pshard, new_state
        if loss_scale is not None:
            gshard = gshard / loss_scale
        with named_scope("opt-update"):
            updates, inner = opt.update(gshard, state.inner, pshard)
            pshard = optax.apply_updates(pshard, updates)
        return pshard, MultiNodeOptimizerState(
            inner=inner, step=state.step + 1, comm_buf=()
        )

    def _zero_param_update(
        self, params, state, gshard, shard_size, n, loss_scale=None
    ):
        """The ZeRO-1/2 parameter tail shared by the stateless and
        with-model-state steps: pack params → take the local shard → apply
        the (possibly stale) gradient shard → all-gather → unpack at the
        original dtypes."""
        comm = self.communicator
        world = self._world_axis()
        pflat, unpack = self._zero_pack(params, shard_size * n)
        pshard = lax.dynamic_slice_in_dim(
            pflat, comm.axis_index() * shard_size, shard_size
        )
        pshard, new_state = self._apply_shard_update(
            pshard, state, gshard, loss_scale
        )
        with named_scope("allreduce"):
            # the second half of the ring allreduce ZeRO-1/2 split in two
            pfull = lax.all_gather(pshard, world, axis=0, tiled=True)
        new_params = unpack(pfull[: shard_size * n])
        new_params = jax.tree.map(
            lambda x, ref: x.astype(ref.dtype), new_params, params
        )
        return new_params, new_state

    def _zero_state_spec(self, shard_size):
        """The MultiNodeOptimizerState PartitionSpec for ZeRO steps: inner
        state sharded over the world, comm_buf likewise when double
        buffering holds the stale gradient shard."""
        world = self._world_axis()
        return MultiNodeOptimizerState(
            inner=self._zero_inner_spec(shard_size),
            step=P(),
            comm_buf=P(world) if self.double_buffering else (),
        )

    def _finalize_step(self, step_fn):
        """Every built train step exits through here: the opt-in lint
        hook (innermost, so it traces the bare step) then telemetry."""
        _startup.mark("make_train_step.return")
        return _instrument_step(_lint_hook(step_fn, self.communicator))

    def make_train_step(
        self,
        loss_fn: Callable,
        batch_spec=None,
        donate: bool = True,
        has_aux: bool = False,
        rng: Any = None,
        n_accum: int = 1,
        loss_scale: float | None = None,
        overlap: bool | None = None,
    ):
        """Build the jitted SPMD training step.

        ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)`` with
        ``has_aux``) computes the *local* mean loss on one device's batch
        shard; the step averages gradients with the communicator's
        characteristic collective pattern and applies the inner optimizer.

        With ``rng`` (a base PRNGKey), ``loss_fn(params, batch, rng)`` is
        called with a key folded over (step, device rank) — per-device
        dropout/augmentation randomness that stays reproducible.

        ``n_accum > 1`` splits each device's batch shard into that many
        microbatches and accumulates gradients over a ``lax.scan`` before
        the collective — same math as the full batch (equal microbatch
        sizes), bounded activation memory.  With ``has_aux`` the aux is
        then stacked along a leading ``n_accum`` axis.

        ``loss_scale`` multiplies the loss before differentiation and
        unscales gradients after communication — parity knob for fp16-style
        mixed precision (bf16, the TPU default, does not need it).

        ``overlap`` pins the staged bucket/allreduce pipeline for this
        step: buckets are emitted in reverse leaf-production order so each
        ``all-reduce-start`` can straddle the remaining backward compute
        (XLA async collectives + the latency-hiding scheduler).  ``None``
        (default) resolves communicator ctor → ``CHAINERMN_TPU_OVERLAP``
        env (default ON); ``False`` forces the eager pack-all-then-reduce
        schedule.  Bit-exact either way.  ZeRO steps reduce-scatter one
        flat shard and have nothing to stage, so the knob is inert there.

        Returns ``step(params, state, batch) -> (params, state, loss[, aux])``.
        """
        _startup.mark("make_train_step")
        comm = self.communicator
        axes = comm.axes
        if batch_spec is None:
            batch_spec = P(axes if len(axes) > 1 else axes[0])
        opt = self.actual_optimizer
        if n_accum < 1:
            raise ValueError(f"n_accum must be >= 1, got {n_accum}")
        if self.zero_stage in (1, 2):
            return self._finalize_step(self._make_zero_train_step(
                loss_fn, batch_spec, donate, has_aux, rng, n_accum, loss_scale
            ))
        if self.zero_stage == 3:
            return self._finalize_step(self._make_zero3_train_step(
                loss_fn, batch_spec, donate, has_aux, rng, n_accum, loss_scale
            ))
        one = self._make_micro_grad_fn(loss_fn, has_aux, loss_scale)

        def body(params, state, batch):
            key = self._base_key(rng, state.step)
            if n_accum == 1:
                # One backward pass: the communicator may lay the
                # exchange of its gradients under it.
                def grads_of(p, b):
                    loss, aux, grads = self._accum_local_grads(
                        one, p, b, key, 1)
                    return (loss, aux), grads

                (loss, aux), grads, exchanged = comm.mean_grads_under(
                    grads_of, params, batch, overlap=overlap)
            else:
                loss, aux, grads = self._accum_local_grads(
                    one, params, batch, key, n_accum)
                exchanged = False
            loss = lax.pmean(loss, axes)
            params, new_state = self._apply_update(
                params, state, grads, loss_scale, overlap=overlap,
                exchanged=exchanged,
            )
            if has_aux:
                return params, new_state, loss, aux
            return params, new_state, loss

        n_out = 4 if has_aux else 3
        mapped = comm.shard_map(
            body,
            in_specs=(P(), P(), batch_spec),
            out_specs=(P(),) * n_out,
        )
        donate_argnums = (0, 1) if donate else ()
        jitted = _jit_step(mapped, "train_step", donate_argnums)
        n_dev = comm.device_size

        @functools.wraps(jitted)
        def step(params, state, batch):
            _check_batch_divisibility(batch, n_dev, n_accum)
            return jitted(params, state, batch)

        step._cache_size = jitted._cache_size
        return self._finalize_step(_forward_aot(step, lambda *a: jitted))

    def _scatter_grads(self, grads, shard_size, n, world):
        """Pack a full local gradient tree and reduce-scatter it to this
        device's fp32 flat shard (mean over the world)."""
        comm = self.communicator
        gflat, _ = self._zero_pack(grads, shard_size * n)
        if comm.allreduce_grad_dtype is not None:
            gflat = gflat.astype(comm.allreduce_grad_dtype)
        with named_scope("allreduce"):
            gshard = lax.psum_scatter(
                gflat, world, scatter_dimension=0, tiled=True
            ) / n
        return gshard.astype(jnp.float32)

    def _accum_scattered_grads(
        self, one, params, batch, base_key, n_accum, shard_size, n, world
    ):
        """Scan the microbatches, reduce-scattering each one's gradients and
        accumulating only the 1/n fp32 shard (ZeRO-2/3).  Returns
        ``(gshard, mean_loss, aux)``; with ``n_accum == 1`` there is no scan
        and aux comes back unstacked, matching the stage-0/1 contract."""
        if n_accum == 1:
            with named_scope("fwd-bwd"):
                loss, aux, grads = one(params, batch, base_key)
            return self._scatter_grads(grads, shard_size, n, world), loss, aux

        micro = self._split_micro(batch, n_accum)

        def mb(carry, xs):
            sacc, lacc = carry
            i, b = xs
            key = None if base_key is None else jax.random.fold_in(base_key, i)
            with named_scope("fwd-bwd"):
                loss, aux, grads = one(params, b, key)
            sacc = sacc + self._scatter_grads(grads, shard_size, n, world)
            return (sacc, lacc + loss), aux

        (sacc, lsum), aux = lax.scan(
            mb,
            (jnp.zeros((shard_size,), jnp.float32),
             jnp.zeros((), jnp.float32)),
            (jnp.arange(n_accum), micro),
        )
        return sacc / n_accum, lsum / n_accum, aux

    def _make_zero_train_step(
        self, loss_fn, batch_spec, donate, has_aux, rng, n_accum, loss_scale
    ):
        """ZeRO-1/2 step: reduce-scatter grads → update local flat shard →
        all-gather params.  Communication volume equals one allreduce
        (reduce-scatter + all-gather IS a ring allreduce split in half), so
        this costs nothing extra on the wire while dividing optimizer-state
        memory by the world size.

        Stage 2 (only distinct under gradient accumulation): each
        microbatch's gradients are reduce-scattered inside the scan and only
        the 1/n fp32 shard is accumulated — gradient-accumulator memory
        drops from a full tree to ``total/n`` at the price of ``n_accum``
        smaller collectives instead of one (same total bytes on the wire,
        more latency terms).
        """
        comm = self.communicator
        axes = comm.axes
        world = self._world_axis()
        one = self._make_micro_grad_fn(loss_fn, has_aux, loss_scale)
        per_micro_scatter = self.zero_stage == 2 and n_accum > 1

        def body(params, state, batch):
            n, total, shard_size = self._zero_geometry(params)
            base_key = self._base_key(rng, state.step)

            if per_micro_scatter:
                gshard, loss, aux = self._accum_scattered_grads(
                    one, params, batch, base_key, n_accum, shard_size, n, world
                )
            else:
                loss, aux, grads = self._accum_local_grads(
                    one, params, batch, base_key, n_accum
                )
                gshard = self._scatter_grads(grads, shard_size, n, world)
            loss = lax.pmean(loss, axes)
            new_params, new_state = self._zero_param_update(
                params, state, gshard, shard_size, n, loss_scale
            )
            if has_aux:
                return new_params, new_state, loss, aux
            return new_params, new_state, loss

        # Geometry depends only on parameter shapes; derive the inner-state
        # spec lazily at first call via closure over the real params.
        def make(params_example):
            n, total, shard = self._zero_geometry(params_example)
            state_spec = self._zero_state_spec(shard)
            n_out = 4 if has_aux else 3
            mapped = comm.shard_map(
                body,
                in_specs=(P(), state_spec, batch_spec),
                out_specs=(P(), state_spec) + (P(),) * (n_out - 2),
            )
            return _jit_step(
                mapped, "train_step_zero", (0, 1) if donate else ()
            )

        compiled = {}

        def jitted_for(params, *_):
            # PyTreeDefs are hashable and stable — safe cache keys (an id()
            # of a temporary would be reusable after GC).
            key = jax.tree.structure(params)
            fn = compiled.get(key)
            if fn is None:
                fn = compiled[key] = make(params)
            return fn

        def step(params, state, batch):
            _check_batch_divisibility(batch, comm.device_size, n_accum)
            return jitted_for(params)(params, state, batch)

        return _forward_aot(step, jitted_for)

    def _make_zero3_train_step(
        self, loss_fn, batch_spec, donate, has_aux, rng, n_accum, loss_scale
    ):
        """ZeRO-3 step: master parameters are ONE flat fp32 buffer sharded
        1/n per device *between* steps.  Each step all-gathers the buffer,
        unpacks it into the user pytree at compute dtype, runs fwd/bwd,
        reduce-scatters gradients, and updates only the local shard — the
        returned buffer is again 1/n resident per device.

        Per-step wire cost is one all-gather (params) + one reduce-scatter
        (grads) = the volume of one ring allreduce; the gathered compute
        copy is transient within the step (XLA frees it after backward), so
        persistent parameter + optimizer memory is ``O(total/n)``.

        The step signature is ``step(flat_params, state, batch)`` with
        ``flat_params`` from :meth:`shard_params`; recover the pytree with
        :meth:`materialize`.
        """
        comm = self.communicator
        axes = comm.axes
        world = self._world_axis()
        one = self._make_micro_grad_fn(loss_fn, has_aux, loss_scale)

        def body(pshard, state, batch):
            n = comm.device_size
            shard_size = pshard.shape[0]
            pfull = lax.all_gather(pshard, world, axis=0, tiled=True)
            params = self._z3_unpack(pfull)
            base_key = self._base_key(rng, state.step)
            gshard, loss, aux = self._accum_scattered_grads(
                one, params, batch, base_key, n_accum, shard_size, n, world
            )
            loss = lax.pmean(loss, axes)
            new_pshard, new_state = self._apply_shard_update(
                pshard, state, gshard, loss_scale
            )
            if has_aux:
                return new_pshard, new_state, loss, aux
            return new_pshard, new_state, loss

        def make(flat_example):
            shard = flat_example.shape[0] // comm.device_size
            state_spec = self._zero_state_spec(shard)
            n_out = 4 if has_aux else 3
            mapped = comm.shard_map(
                body,
                in_specs=(P(world), state_spec, batch_spec),
                out_specs=(P(world), state_spec) + (P(),) * (n_out - 2),
            )
            return _jit_step(
                mapped, "train_step_zero3", (0, 1) if donate else ()
            )

        compiled = {}

        def jitted_for(flat_params, *_):
            if self._z3_meta is None:
                raise RuntimeError(
                    "zero_stage=3: call init(params) (or shard_params) first"
                )
            # The traced body bakes in the unpack metadata, so the cache key
            # must include it — same padded size with a different tree
            # layout must re-trace, not silently reuse the wrong unpacking.
            treedef, metas = self._z3_meta
            key = (flat_params.shape, treedef, tuple(metas))
            fn = compiled.get(key)
            if fn is None:
                fn = compiled[key] = make(flat_params)
            return fn

        def step(flat_params, state, batch):
            fn = jitted_for(flat_params)
            _check_batch_divisibility(batch, comm.device_size, n_accum)
            return fn(flat_params, state, batch)

        return _forward_aot(step, jitted_for)

    def make_train_step_with_state(
        self,
        loss_fn: Callable,
        batch_spec=None,
        donate: bool = True,
        overlap: bool | None = None,
    ):
        """Like :meth:`make_train_step` for models with non-trainable mutable
        state (BatchNorm statistics etc. — flax's ``batch_stats``).

        ``overlap`` pins the staged bucket/allreduce pipeline exactly as in
        :meth:`make_train_step` (``None`` = ctor → env, default ON;
        bit-exact either way; inert for ZeRO).

        ``loss_fn(params, model_state, batch) -> (loss, new_model_state)``.
        The new model state is ``pmean``-synchronized across the world —
        cross-replica BatchNorm, a strict improvement over the reference's
        per-GPU statistics.

        Returns ``step(params, opt_state, model_state, batch) ->
        (params, opt_state, model_state, loss)``.

        ``double_buffering`` works here too: step N applies step N−1's
        averaged gradients (first step reduce-only), while model state
        (BatchNorm statistics) always updates from the CURRENT step —
        statistics are running estimates, not gradients, so staleness
        semantics do not apply to them.

        ZeRO works here too: stages 1/2 keep the pytree step signature with
        the optimizer state sharded; stage 3 takes/returns the flat sharded
        master buffer in place of the params pytree (as
        :meth:`make_train_step` does) — ``step(flat_params, opt_state,
        model_state, batch)``.
        """
        _startup.mark("make_train_step")
        comm = self.communicator
        axes = comm.axes
        if batch_spec is None:
            batch_spec = P(axes if len(axes) > 1 else axes[0])

        def grads_and_state(params, model_state, batch):
            with named_scope("fwd-bwd"):
                (loss, new_model_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, model_state, batch)
            loss = lax.pmean(loss, axes)
            new_model_state = jax.tree.map(
                lambda x: lax.pmean(x, axes)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                new_model_state,
            )
            return loss, new_model_state, grads

        if self.zero_stage > 0:
            return self._finalize_step(self._make_zero_with_state_step(
                grads_and_state, batch_spec, donate
            ))

        def body(params, state, model_state, batch):
            loss, new_model_state, grads = grads_and_state(
                params, model_state, batch
            )
            params, new_state = self._apply_update(
                params, state, grads, overlap=overlap
            )
            return params, new_state, new_model_state, loss

        mapped = comm.shard_map(
            body,
            in_specs=(P(), P(), P(), batch_spec),
            out_specs=(P(),) * 4,
        )
        donate_argnums = (0, 1, 2) if donate else ()
        return self._finalize_step(
            _jit_step(mapped, "train_step_with_state", donate_argnums)
        )

    def _make_zero_with_state_step(self, grads_and_state, batch_spec, donate):
        """ZeRO tails for the with-model-state step.  Stages 1/2 are
        identical here (stage 2's distinct behavior only exists under
        gradient accumulation, which the with-state surface does not
        expose); stage 3 trades the pytree for the flat sharded buffer."""
        comm = self.communicator
        world = self._world_axis()

        if self.zero_stage in (1, 2):

            def body(params, state, model_state, batch):
                n, total, shard_size = self._zero_geometry(params)
                loss, new_model_state, grads = grads_and_state(
                    params, model_state, batch
                )
                gshard = self._scatter_grads(grads, shard_size, n, world)
                new_params, new_state = self._zero_param_update(
                    params, state, gshard, shard_size, n
                )
                return new_params, new_state, new_model_state, loss

            def make(params_example):
                n, total, shard = self._zero_geometry(params_example)
                state_spec = self._zero_state_spec(shard)
                mapped = comm.shard_map(
                    body,
                    in_specs=(P(), state_spec, P(), batch_spec),
                    out_specs=(P(), state_spec, P(), P()),
                )
                return _jit_step(
                    mapped, "train_step_zero_with_state",
                    (0, 1, 2) if donate else (),
                )

            compiled = {}

            def jitted_for(params, *_):
                key = jax.tree.structure(params)
                fn = compiled.get(key)
                if fn is None:
                    fn = compiled[key] = make(params)
                return fn

            def step(params, state, model_state, batch):
                _check_batch_divisibility(batch, comm.device_size)
                return jitted_for(params)(params, state, model_state, batch)

            return _forward_aot(step, jitted_for)

        # zero_stage == 3: flat sharded master buffer in place of params.
        def body3(pshard, state, model_state, batch):
            n = comm.device_size
            shard_size = pshard.shape[0]
            pfull = lax.all_gather(pshard, world, axis=0, tiled=True)
            params = self._z3_unpack(pfull)
            loss, new_model_state, grads = grads_and_state(
                params, model_state, batch
            )
            gshard = self._scatter_grads(grads, shard_size, n, world)
            new_pshard, new_state = self._apply_shard_update(
                pshard, state, gshard
            )
            return new_pshard, new_state, new_model_state, loss

        def make3(flat_example):
            shard = flat_example.shape[0] // comm.device_size
            state_spec = self._zero_state_spec(shard)
            mapped = comm.shard_map(
                body3,
                in_specs=(P(world), state_spec, P(), batch_spec),
                out_specs=(P(world), state_spec, P(), P()),
            )
            return _jit_step(
                mapped, "train_step_zero3_with_state",
                (0, 1, 2) if donate else (),
            )

        compiled3 = {}

        def jitted_for3(flat_params, *_):
            if self._z3_meta is None:
                raise RuntimeError(
                    "zero_stage=3: call init(params) (or shard_params) first"
                )
            treedef, metas = self._z3_meta
            key = (flat_params.shape, treedef, tuple(metas))
            fn = compiled3.get(key)
            if fn is None:
                fn = compiled3[key] = make3(flat_params)
            return fn

        def step3(flat_params, state, model_state, batch):
            fn = jitted_for3(flat_params)
            _check_batch_divisibility(batch, comm.device_size)
            return fn(flat_params, state, model_state, batch)

        return _forward_aot(step3, jitted_for3)

    # ------------------------------------------------------------------
    # Imperative parity API (reference: optimizer.setup(model) + update())
    # ------------------------------------------------------------------
    def setup(self, params, loss_fn: Callable, batch_spec=None, *,
              rng: Any = None, n_accum: int = 1, has_aux: bool = False,
              loss_scale: float | None = None):
        """Imperative surface with the FULL feature matrix of
        :meth:`make_train_step` — ``rng`` (per-(step, device) dropout
        keys), ``n_accum`` (gradient accumulation), ``has_aux`` (update()
        returns ``(loss, aux)``), ``loss_scale``, and every
        ``zero_stage`` incl. 3 (parameters live as the flat sharded
        master buffer internally; :attr:`target` materializes them)."""
        # Exactly ONE full-tree broadcast: init() is told to skip its
        # own (the reference's first-update broadcast_data contract is
        # still honored — by this call).
        params = self.broadcast_params(params)
        self._state = self.init(params, _skip_broadcast=True)
        self._params = (
            self.shard_params(params) if self.zero_stage == 3 else params
        )
        self._step_fn = self.make_train_step(
            loss_fn, batch_spec=batch_spec, donate=False,
            rng=rng, n_accum=n_accum, has_aux=has_aux,
            loss_scale=loss_scale,
        )
        self._setup_has_aux = has_aux
        return self

    def update(self, batch):
        """Imperative one-step update, mirroring the reference's
        ``optimizer.update(loss_func, *args)`` call shape.  Returns the
        loss, or ``(loss, aux)`` when setup() was given ``has_aux``."""
        if self._step_fn is None:
            raise RuntimeError("call setup(params, loss_fn) before update()")
        out = self._step_fn(self._params, self._state, batch)
        if self._setup_has_aux:
            self._params, self._state, loss, aux = out
            return loss, aux
        self._params, self._state, loss = out
        return loss

    @property
    def target(self):
        """Current parameters (reference: ``optimizer.target`` is the
        model).  Under ``zero_stage=3`` the sharded master buffer is
        materialized back to the parameter tree."""
        if self.zero_stage == 3 and self._params is not None:
            return self.materialize(self._params)
        return self._params

    @property
    def t(self):
        return int(self._state.step) if self._state is not None else 0


def create_multi_node_optimizer(
    actual_optimizer: optax.GradientTransformation,
    communicator: CommunicatorBase,
    double_buffering: bool = False,
    zero_stage: int = 0,
) -> MultiNodeOptimizer:
    """Reference-parity factory (REF:chainermn/optimizers.py), extended
    with ZeRO sharding: ``zero_stage=1`` (optimizer state), ``2`` (+ sharded
    gradient accumulation), ``3`` (+ sharded master parameters)."""
    _startup.mark("create_multi_node_optimizer")
    return MultiNodeOptimizer(
        actual_optimizer,
        communicator,
        double_buffering=double_buffering,
        zero_stage=zero_stage,
    )
