"""Tensor-parallel sharding rules — the GSPMD face of the framework.

The reference's closest artifact is the parallel_convolution example
(channel-sharded conv + differentiable allgather,
REF:examples/parallel_convolution/); generalized here the TPU way: name a
``model`` mesh axis, annotate parameter PartitionSpecs (heads and MLP
hidden are the shardable dimensions of a transformer), and let XLA insert
the collectives — the "pick a mesh, annotate shardings, let XLA do the
rest" recipe of the scaling playbook.

Two styles coexist in this package by design, mirroring the reference's
two-plane split:

* **explicit collectives** (shard_map + communicator methods) where the
  reference had explicit communicator calls — the DP optimizer, pipelines,
  ring attention;
* **GSPMD annotation** (this module) where the parallelism is a property
  of the *weights*, which is how TP is idiomatically done on TPU.

Which weights get which spec now lives in the declarative plan registry
(:mod:`chainermn_tpu.sharding`): :func:`make_gspmd_train_step` accepts a
:class:`~chainermn_tpu.sharding.ShardingPlan` (or registry name) and
resolves params AND optimizer moments from its one rule table;
:func:`transformer_param_spec` remains as a shim over what is now plan
``"tp"``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def transformer_param_spec(params, model_axis: str = "model"):
    """PartitionSpec pytree for the transformer/ViT families in
    ``chainermn_tpu.models``: attention heads and MLP hidden sharded over
    ``model_axis``, everything else replicated.

    The rules are NAME-PATTERN matches (``query``/``key``/``value``/
    ``out``/``wi``/``wo`` path substrings — the naming of this package's
    models).  A model with different parameter naming would silently
    replicate everything, so a spec that shards NOTHING raises — pass a
    hand-written spec tree to :func:`make_gspmd_train_step` for custom
    naming instead.

    .. note:: **Changed contract.**  Direct use is deprecated: the same
       rules now live in the declarative plan registry as plan ``"tp"``
       (``chainermn_tpu.sharding.get_plan("tp")``), which additionally
       resolves grads, optimizer moments, and the serving KV cache from
       one table, and is lintable (rule R006).  This shim is kept for
       existing callers and resolves leaf-for-leaf identically to the
       ``tp`` plan (pinned by ``tests/test_shardplan.py``); new code
       should pass a :class:`~chainermn_tpu.sharding.ShardingPlan` to
       :func:`make_gspmd_train_step` instead.  See docs/sharding.md."""

    def spec_for(path, leaf) -> P:
        names = [
            getattr(p, "key", getattr(p, "name", str(p))) for p in path
        ]
        joined = "/".join(str(n) for n in names)
        shape = getattr(leaf, "shape", ())
        if "query" in joined or "key" in joined or "value" in joined:
            if len(shape) == 3:  # (d_model, n_heads, d_head)
                return P(None, model_axis, None)
        if joined.endswith("out/kernel") or "/out/" in joined:
            if len(shape) == 3:  # (n_heads, d_head, d_model)
                return P(model_axis, None, None)
        if "wi/kernel" in joined:
            return P(None, model_axis)
        if "wo/kernel" in joined:
            return P(model_axis, None)
        return P()

    spec = jax.tree_util.tree_map_with_path(spec_for, params)
    if not any(
        any(ax is not None for ax in s) for s in jax.tree.leaves(
            spec, is_leaf=lambda x: isinstance(x, P)
        )
    ):
        raise ValueError(
            "transformer_param_spec matched NO shardable parameters — "
            "tensor parallelism would silently do nothing.  The rules "
            "key on this package's layer names (query/key/value/out, "
            "wi/wo); for a model with different naming, write the "
            "PartitionSpec tree by hand and pass it to "
            "make_gspmd_train_step directly."
        )
    return spec


def make_gspmd_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    param_spec,
    data_axis: str = "data",
):
    """Build a jitted dp×tp training step via sharding annotation.

    ``loss_fn(params, batch) -> loss``; the batch's leading axis is sharded
    over ``data_axis``, parameters per ``param_spec``.  The gradient
    all-reduce over the data axis and the activation collectives over the
    model axis are inserted by XLA from the shardings — the GSPMD
    counterpart of the communicator's explicit psum.

    ``param_spec`` is either a PartitionSpec pytree matching ``params``
    (the original contract), OR a :class:`~chainermn_tpu.sharding.
    ShardingPlan` / registry plan name (``"tp"``, ``"dp_tp"``, …).  With
    a plan, params AND optimizer moments resolve from the one rule
    table — no spec tree to hand-maintain — and the jit is built at the
    first ``shard_fn`` call (the plan needs real tree paths to resolve).

    Returns ``(step, shard_fn)``: ``shard_fn(params, opt_state)`` places
    initial state, ``step(params, opt_state, batch) -> (params, opt_state,
    loss)``.
    """
    from chainermn_tpu.sharding.plan import ShardingPlan

    if isinstance(param_spec, str):
        from chainermn_tpu.sharding.registry import get_plan

        param_spec = get_plan(param_spec)
    plan = param_spec if isinstance(param_spec, ShardingPlan) else None

    def to_sharding(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    batch_sharding = NamedSharding(mesh, P(data_axis))

    if plan is not None:
        missing = set(plan.axes) - set(mesh.axis_names)
        if missing:
            raise ValueError(
                f"sharding plan {plan.name!r} shards over axes "
                f"{sorted(missing)} the mesh lacks (mesh axes: "
                f"{tuple(mesh.axis_names)})"
            )
        state = {}

        def plan_shard_fn(params, opt_state):
            param_shardings = to_sharding(plan.resolve(params))
            moment_shardings = to_sharding(plan.resolve_moments(opt_state))
            # out_shardings pins the step to a placement fixed point:
            # without it GSPMD may emit outputs in a different layout
            # than in_shardings, and feeding the donated outputs back
            # into the next step fails the pjit sharding check.
            state["jit"] = jax.jit(
                step,
                in_shardings=(param_shardings, moment_shardings,
                              batch_sharding),
                out_shardings=(param_shardings, moment_shardings, None),
                donate_argnums=(0, 1),
            )
            return (
                jax.device_put(params, param_shardings),
                jax.device_put(opt_state, moment_shardings),
            )

        def plan_step(params, opt_state, batch):
            if "jit" not in state:
                raise RuntimeError(
                    "plan-driven gspmd step called before shard_fn: call "
                    "shard_fn(params, opt_state) once to resolve the "
                    "plan and place the initial state"
                )
            return state["jit"](params, opt_state, batch)

        return plan_step, plan_shard_fn

    param_shardings = to_sharding(param_spec)

    # Optimizer moments (adam's mu/nu etc.) are param-shaped; shard them
    # like their parameter so TP actually divides optimizer memory.  The
    # association mechanism is the TREE PATH: optax state leaves carry
    # their parameter's path as a suffix (e.g. ('0', 'mu', *param_path)),
    # so the longest path suffix that names a same-shaped parameter wins.
    # Path is the ONLY mechanism: scalar state (adam's count) replicates,
    # and any other leaf whose path embeds no parameter path is a hard
    # error — the old shape-first-match fallback could silently pick a
    # wrong layout when two same-shape params shard differently, and
    # plans now guarantee coverage, so a miss means the spec tree is
    # wrong, not that the leaf deserves an arbitrary placement.

    def _path_key(path):
        keys = []
        for entry in path:
            if hasattr(entry, "key"):
                keys.append(str(entry.key))
            elif hasattr(entry, "name"):
                keys.append(str(entry.name))
            elif hasattr(entry, "idx"):
                keys.append(str(entry.idx))
            else:
                keys.append(str(entry))
        return tuple(keys)

    spec_state = {}

    def shard_fn(params, opt_state):
        path_to_sharding = {}
        param_leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        sharding_leaves = jax.tree.leaves(
            param_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )
        for (p_path, p_leaf), s_leaf in zip(param_leaves, sharding_leaves):
            path_to_sharding[_path_key(p_path)] = (p_leaf.shape, s_leaf)
        params = jax.device_put(params, param_shardings)
        replicated = NamedSharding(mesh, P())

        def opt_shard(path, x):
            shape = getattr(x, "shape", None)
            key = _path_key(path)
            # Longest matching suffix first: the full param path beats
            # any accidental tail collision.
            for i in range(len(key)):
                hit = path_to_sharding.get(key[i:])
                if hit is not None and hit[0] == shape:
                    return jax.device_put(x, hit[1])
            if not shape:  # scalar state (adam's count): replicate
                return jax.device_put(x, replicated)
            raise ValueError(
                f"optimizer state leaf '{'/'.join(key)}' (shape "
                f"{tuple(shape)}) embeds no parameter tree path from "
                "the spec tree — cannot infer its sharding.  Resolve "
                "optimizer state through a ShardingPlan "
                "(plan.resolve_moments) or extend the param_spec tree "
                "to cover the parameter this leaf belongs to."
            )

        opt_state = jax.tree_util.tree_map_with_path(opt_shard, opt_state)
        # Rebuild the jit with the now-known optimizer-state shardings
        # pinned on BOTH sides: out_shardings makes the step a placement
        # fixed point, so its donated outputs feed straight back in.
        # Without the pin GSPMD may emit an output in a different layout
        # and the next call fails the pjit sharding check.
        opt_shardings = jax.tree.map(lambda leaf: leaf.sharding, opt_state)
        spec_state["jit"] = jax.jit(
            step,
            in_shardings=(param_shardings, opt_shardings, batch_sharding),
            out_shardings=(param_shardings, opt_shardings, None),
            donate_argnums=(0, 1),
        )
        return params, opt_state

    eager = jax.jit(
        step,
        in_shardings=(param_shardings, None, batch_sharding),
        donate_argnums=(0, 1),
    )

    def spec_step(params, opt_state, batch):
        return spec_state.get("jit", eager)(params, opt_state, batch)

    return spec_step, shard_fn


# ---------------------------------------------------------------------------
# Vocab-parallel embedding + cross-entropy (Megatron-style TP for the LM
# head).  The embedding table's VOCAB axis is sharded over the model axis;
# the logits never exist unsharded — each device holds (chunk, V/n) tiles
# and the softmax statistics merge with one pmax + psum per chunk, the
# reference's allreduce contract applied to the softmax instead of the
# gradients (REF:chainermn/functions/collective_communication.py is the
# differentiable-collective precedent).
#
# Both ops are explicit custom_vjps: differentiating lax.psum inside these
# shard_map regions (replication tracking off) would transpose psum to
# psum and inflate gradients by the axis size, so the backward collectives
# are written by hand — dh = psum over shards of dlogits_s @ E_s; dE_s is
# purely local.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def vocab_parallel_embed(tokens, embedding_shard, axis_name,
                         grad_reduce=False):
    """Token lookup against a VOCAB-SHARDED embedding table, inside
    ``shard_map`` over ``axis_name``.

    ``embedding_shard``: ``(V/n, D)`` — this device's contiguous vocab
    rows (shard ``i`` owns ids ``[i*V/n, (i+1)*V/n)``).  Each device
    resolves the ids it owns (others contribute zeros) and one ``psum``
    assembles the replicated ``(..., D)`` activations — O(tokens x D)
    wire, table stays sharded (the per-device memory win TP exists for).

    ``grad_reduce`` (static): the backward collective for the table.
    False (default) is the pure-TP contract — downstream cotangents are
    REPLICATED over ``axis_name``, so each device's local scatter is the
    complete gradient for its shard.  True is the SP-composed contract —
    downstream consumes only a per-device slice of the output (sequence
    parallelism over the SAME axis), so cotangents arrive as
    device-varying zero-masked slices; the backward ``psum``s the
    COTANGENT first (reassembling the full replicated ``dL/d out``) and
    then scatters locally, so each shard collects every sequence
    position's contribution to its own rows.  (Scattering first and
    psum-ing the scattered shards would be wrong twice over: a device
    drops cotangents for ids outside its own vocab range, and the psum
    would mix different shards' row spaces.)
    """
    out, _ = _vp_embed_fwd_impl(tokens, embedding_shard, axis_name)
    return out


def _vp_embed_fwd_impl(tokens, embedding_shard, axis_name):
    i = lax.axis_index(axis_name)
    v_loc = embedding_shard.shape[0]
    local = tokens - i * v_loc
    in_range = jnp.logical_and(local >= 0, local < v_loc)
    idx = jnp.clip(local, 0, v_loc - 1)
    emb = jnp.take(embedding_shard, idx, axis=0)
    emb = jnp.where(in_range[..., None], emb, 0.0)
    return lax.psum(emb, axis_name), (idx, in_range)


def _vp_embed_vjp_fwd(tokens, embedding_shard, axis_name, grad_reduce):
    out, (idx, in_range) = _vp_embed_fwd_impl(
        tokens, embedding_shard, axis_name
    )
    return out, (idx, in_range, embedding_shard.shape)


def _vp_embed_vjp_bwd(axis_name, grad_reduce, res, g):
    idx, in_range, shape = res
    if grad_reduce:
        # Device-varying (zero-masked slice) cotangents: reassemble the
        # full replicated dL/d out BEFORE the ownership-masked scatter.
        g = lax.psum(g, axis_name)
    g_masked = jnp.where(in_range[..., None], g, 0.0)
    d_emb = jnp.zeros(shape, g.dtype).at[idx.reshape(-1)].add(
        g_masked.reshape(-1, shape[-1])
    )
    return None, d_emb


vocab_parallel_embed.defvjp(_vp_embed_vjp_fwd, _vp_embed_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_seq_for_replicated_head(x, axis_name, axis=1):
    """All-gather a sequence-sharded activation for a head whose gradient
    is REPLICATED over ``axis_name`` (the vocab-parallel CE) — Megatron's
    g/ḡ conjugate-collective pair.

    Every device seeds the identical replicated cotangent on the gathered
    tensor, so a plain ``lax.all_gather``'s transpose (reduce-scatter)
    would sum the ``n`` identical copies and inflate every upstream
    gradient by the axis size.  This version's backward SLICES the
    replicated cotangent back to the caller's shard — the correct 1x
    adjoint when (and only when) the downstream consumer produces a
    replicated gradient, as the explicit-collective CE here does.
    """
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def _gather_head_vjp_fwd(x, axis_name, axis):
    return lax.all_gather(x, axis_name, axis=axis, tiled=True), x.shape[axis]


def _gather_head_vjp_bwd(axis_name, axis, s_local, g):
    my = lax.axis_index(axis_name)
    return (lax.dynamic_slice_in_dim(g, my * s_local, s_local, axis),)


gather_seq_for_replicated_head.defvjp(
    _gather_head_vjp_fwd, _gather_head_vjp_bwd
)


class _VocabShardStrategy:
    """:class:`chainermn_tpu.ops.fused_ce.LocalVocabStrategy`'s
    cross-shard sibling: row max/sum-exp/picked-logit merge over the
    model axis (pmax + psum), labels resolved by contiguous-shard
    ownership, and the backward's ``dh`` summed across shards (``dh =
    Σ_s dlogits_s @ E_s``).  The chunked scan itself lives once, in
    ``ops.fused_ce``."""

    def __init__(self, axis_name, v_loc):
        self.axis_name = axis_name
        self.v_loc = v_loc
        self.offset = lax.axis_index(axis_name) * v_loc

    def merge_max(self, m):
        return lax.pmax(m, self.axis_name)

    def merge_sum(self, s):
        return lax.psum(s, self.axis_name)

    def merge_pick(self, p):
        return lax.psum(p, self.axis_name)

    def reduce_dh(self, dh):
        return lax.psum(dh, self.axis_name)

    def label_local(self, labels):
        local = labels - self.offset
        owner = jnp.logical_and(local >= 0, local < self.v_loc)
        return jnp.clip(local, 0, self.v_loc - 1), owner


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _vp_ce_sum(hidden, embedding_shard, labels, axis_name, chunk):
    """Replicated (loss_sum, n_valid, lse) over vocab-sharded logits."""
    from chainermn_tpu.ops.fused_ce import ce_scan_fwd

    return ce_scan_fwd(
        hidden, embedding_shard, labels, chunk,
        _VocabShardStrategy(axis_name, embedding_shard.shape[0]),
    )


def _vp_ce_vjp_fwd(hidden, embedding_shard, labels, axis_name, chunk):
    from chainermn_tpu.ops.fused_ce import ce_scan_fwd

    out = ce_scan_fwd(
        hidden, embedding_shard, labels, chunk,
        _VocabShardStrategy(axis_name, embedding_shard.shape[0]),
    )
    return out, (hidden, embedding_shard, labels, out[2])


def _vp_ce_vjp_bwd(axis_name, chunk, res, cots):
    from chainermn_tpu.ops.fused_ce import ce_scan_bwd

    hidden, embedding_shard, labels, lse = res
    g_loss, _g_nvalid, g_lse = cots
    dh, d_emb = ce_scan_bwd(
        hidden, embedding_shard, labels, lse, g_loss, g_lse, chunk,
        _VocabShardStrategy(axis_name, embedding_shard.shape[0]),
    )
    return dh, d_emb, None


_vp_ce_sum.defvjp(_vp_ce_vjp_fwd, _vp_ce_vjp_bwd)


def vocab_parallel_cross_entropy(hidden, embedding_shard, labels,
                                 axis_name: str, *, chunk: int = 512):
    """Mean softmax cross-entropy against a VOCAB-SHARDED tied embedding,
    inside ``shard_map`` over ``axis_name`` — the tensor-parallel LM head.

    Semantics of :func:`chainermn_tpu.ops.fused_cross_entropy` (negative
    labels ignored; bf16 MXU matmuls, fp32 reductions; chunked — no
    ``(N, V)`` OR ``(N, V/n)`` materialization beyond one
    ``(chunk, V/n)`` tile per device), with the softmax statistics merged
    across shards: one ``pmax`` (row max) + two ``psum``s (sum-exp,
    owner-picked logit) per chunk, and one ``psum`` per chunk in the
    backward for ``dh``.  Returns the replicated scalar mean; gradients:
    ``d hidden`` replicated, ``d embedding_shard`` local to each shard.

    Differentiate INSIDE the sharded region (``jax.grad`` of a loss
    calling this, within the same ``shard_map`` body) — the custom
    backward issues its own collectives against per-device cotangent
    seeds.  Differentiating from outside *through* ``shard_map`` layers
    that transform's own transpose scaling on top and is not supported —
    the contract every explicit-collective device-plane op in this
    package shares.
    """
    from chainermn_tpu.ops.fused_ce import _validate_and_flatten

    h2, l2 = _validate_and_flatten(hidden, embedding_shard, labels, chunk)
    loss_sum, n_valid, _lse = _vp_ce_sum(
        h2, embedding_shard, l2, axis_name, int(chunk)
    )
    return loss_sum / jnp.maximum(n_valid, 1.0)
