"""Built-in sharding plans.

Every plan here ends in a terminal catch-all rule, so any model in
:mod:`chainermn_tpu.models` resolves with zero unmatched leaves (lint
rule R006 enforces exactly that).  The ``tp`` table is the declarative
rendering of the old hand-wired ``transformer_param_spec`` — same
specs, leaf for leaf — plus a KV-page rule so the SAME table drives the
tensor-parallel :class:`~chainermn_tpu.serving.engine.InferenceEngine`
cache.

Plans compose with the mesh at the call site: a plan only says *which
named axes* shard *which leaves*; ``plans_for_mesh`` filters the
registry down to plans whose axes the mesh actually has.
"""

from __future__ import annotations

from typing import Dict, List

from jax.sharding import PartitionSpec as P

from chainermn_tpu.sharding.plan import PlanRule, ShardingPlan, validate

_REGISTRY: Dict[str, ShardingPlan] = {}


def register_plan(plan: ShardingPlan, *, overwrite: bool = False
                  ) -> ShardingPlan:
    """Add ``plan`` to the registry (used by the built-ins below and by
    user code defining project-local layouts)."""
    if plan.name in _REGISTRY and not overwrite:
        raise ValueError(
            f"sharding plan {plan.name!r} is already registered "
            "(pass overwrite=True to replace it)"
        )
    _REGISTRY[plan.name] = plan
    return plan


def get_plan(name: str) -> ShardingPlan:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown sharding plan {name!r}; registered plans: "
            f"{sorted(_REGISTRY)}"
        ) from None


def list_plans() -> List[ShardingPlan]:
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def plans_for_mesh(mesh, params=None) -> List[ShardingPlan]:
    """Registry plans whose every axis exists on ``mesh`` — and, when a
    parameter tree is given, that :func:`validate` clean against it
    (including mesh divisibility)."""
    out = []
    for plan in list_plans():
        if not set(plan.axes) <= set(mesh.axis_names):
            continue
        if params is not None and not validate(plan, params, mesh).ok:
            continue
        out.append(plan)
    return out


# ---------------------------------------------------------------------
# Rule blocks (shared between plans)
# ---------------------------------------------------------------------

_REPLICATE = PlanRule("replicate", r".*", P())

# The transformer TP block: identical specs to the retired hand-wired
# transformer_param_spec, rule for rule.  ndim gates stand in for its
# shape conditions (a query *bias* is 2-D and falls through to
# replication, exactly as before).
_TP_RULES = (
    # fused or per-head attention projections: (d_model, heads, d_head)
    PlanRule("attention_qkv", r"(query|key|value)",
             P(None, "model", None), ndim=3),
    # output projection: (heads, d_head, d_model)
    PlanRule("attention_out", r"(out/kernel$|/out/)",
             P("model", None, None), ndim=3),
    # FFN up/down projections (megatron column/row split)
    PlanRule("ffn_in", r"wi/kernel", P(None, "model")),
    PlanRule("ffn_out", r"wo/kernel", P("model", None)),
    # paged KV cache: (page_count, page_size, n_kv, d_head) — shard the
    # KV-head axis so TP decode keeps heads local (serving engine only;
    # params never match, these leaves are rank 4 and named *_pages)
    PlanRule("kv_pages", r"(k|v)_pages$",
             P(None, None, "model", None), ndim=4),
    _REPLICATE,
)

# FSDP block: shard the trailing (output-features) dim of every kernel
# over the data axis, and the vocab dim of embedding tables; everything
# else (biases, norm scales, BN stats) replicates.
_FSDP_RULES = (
    PlanRule("embedding", r"embedding$", P("data", None), ndim=2),
    PlanRule("kernel_2d", r"kernel$", P(None, "data"), ndim=2),
    PlanRule("kernel_3d", r"kernel$", P(None, None, "data"), ndim=3),
    PlanRule("kernel_4d", r"kernel$", P(None, None, None, "data"),
             ndim=4),
    _REPLICATE,
)


# ---------------------------------------------------------------------
# Built-in plans
# ---------------------------------------------------------------------

register_plan(ShardingPlan(
    name="dp",
    rules=(_REPLICATE,),
    axes=("data",),
    description="Pure data parallelism: params, moments, and cache "
                "replicated; only the batch shards.",
))

register_plan(ShardingPlan(
    name="tp",
    rules=_TP_RULES,
    axes=("model",),
    description="Megatron tensor parallelism for attention/FFN "
                "families (transformer, ViT): heads and FFN hidden "
                "shard over 'model'; KV pages shard for TP decode.",
))

register_plan(ShardingPlan(
    name="dp_tp",
    rules=_TP_RULES,
    axes=("data", "model"),
    description="Composed DP×TP on a 2-D ('data', 'model') mesh: the "
                "tp rule table for params/moments, batch over 'data'.",
))

register_plan(ShardingPlan(
    name="sp",
    rules=(_REPLICATE,),
    axes=("sp",),
    description="Sequence-parallel prefill (serving engine): params and "
                "KV pages replicated over 'sp'; only the chunk "
                "program's token axis shards (shard_map inside the "
                "engine's sp prefill step), so one slice's activations "
                "split across devices while decode stays single-chip.",
))

register_plan(ShardingPlan(
    name="fsdp",
    rules=_FSDP_RULES,
    axes=("data",),
    description="Fully-sharded data parallelism: every kernel and "
                "embedding shards one dim over 'data'; GSPMD "
                "gathers/scatters around use.",
))

register_plan(ShardingPlan(
    name="zero",
    rules=(_REPLICATE,),
    moment_rules=_FSDP_RULES,
    axes=("data",),
    description="ZeRO-1 in GSPMD form: params replicated, optimizer "
                "moments sharded over 'data' via the FSDP rule block.",
))
