"""Backward-overlapped bucket schedule — hide gradient comms in the bwd pass.

PyTorch DDP's headline optimization (Li et al., VLDB 2020) launches each
gradient bucket's allreduce as soon as its last member gradient is
produced, so communication for the early buckets rides under the
remaining backward compute.  The reference stack approximated this with
its ``double_buffering`` optimizer (overlap by one full step of
staleness); here the overlap is *exact* — same-step gradients, zero
staleness — because under XLA the mechanism is dependence structure, not
threads:

1. **Schedule** (:func:`build_overlap_schedule`): emit each bucket's
   pack + allreduce in *reverse leaf-production order*.  Reverse-mode
   autodiff materializes gradients roughly in reverse forward order, so
   the leaves at the END of the flatten order get their grads first —
   emitting the last bucket's collective first hands the compiler a
   collective whose operands are ready while earlier layers' backward
   compute is still pending.  Each bucket's collective depends only on
   its own member leaves (per-bucket pack, not pack-everything-first),
   keeping the dependence frontier minimal.
2. **What libtpu 0.0.34 does with it.**  It places the collectives
   early — between the backward fusions, each followed by its leaves'
   update — and leaves every one a synchronous ``all-reduce`` in the
   core's own instruction stream: 17 of them, 35 ms of a 436 ms
   four-chip LM step with nothing hidden (``PERF.md`` §5).  An
   asynchronous all-reduce is made and folded back under every flag set
   tried (seven, handed per program: ``ROADMAP.md`` S6); ``psum_scatter``
   + ``all_gather`` is merged back into ``all-reduce``.  A
   ``collective-permute`` stays a ``-start`` / ``-done`` pair: a DMA the
   core is free under.  This package writes no compiler flag (an unknown
   flag in ``XLA_FLAGS`` aborts the process at backend init, jaxlib
   0.9.0).
3. **The ring** (:mod:`.ring`): a full-precision float bucket of at
   least :data:`RING_MIN_BYTES` reduced as a two-way ring of
   ``lax.ppermute`` hops with ordinary adds between them — the same
   float mean, the additions in another order, every element reduced on
   one device and copied from there (bit-identical replicas).
4. **Pinning the hops** (:func:`walk_with_exchange`): the scheduler,
   left to itself, runs the hops AFTER the backward pass, where a ring
   is slower than the ``psum`` it replaces (1.2-1.9x alone, PR 45's
   probe).  So a bucket rides the ring ONLY where its hops are pinned:
   where the step has one backward pass (``make_train_step`` without
   gradient accumulation), the communicator has a ring (``xla_ici``) and
   that pass holds a matrix product to tie a hop to after the first ring
   starts (:func:`pin_sites`).  The pass is then run equation by
   equation, a bucket's ring starts where its last gradient is made, and
   each hop is tied to a matrix product further down the backward pass.
   ``allreduce_grad`` itself — gradient accumulation, the step with
   model state, a backward pass that is one ``scan`` over the layers or
   holds no matrix product, integer and small buckets, a world of one,
   the quantised wire, the eager emission — keeps ``lax.psum``.

Everything but the ring is platform-neutral and bit-exact (the
per-bucket math is identical to the eager path — only trace order
changes, and fp addition inside each bucket is untouched).

Escape hatch: ``CHAINERMN_TPU_OVERLAP=0`` restores the eager
pack-all-then-reduce-all emission.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Tuple

#: Environment escape hatch: ``0``/``false``/``off`` disables the
#: overlapped emission schedule on every communicator (eager path).
#: Unset or anything truthy keeps it ON — the default.
ENV_OVERLAP = "CHAINERMN_TPU_OVERLAP"

#: Environment override for the schedule granularity (buckets emitted
#: per stage); unset resolves ctor -> 1 (finest overlap).
ENV_OVERLAP_GRANULARITY = "CHAINERMN_TPU_OVERLAP_GRANULARITY"

DEFAULT_GRANULARITY = 1

#: The smallest float bucket (padded bytes) that rides the ring
#: (:mod:`.ring`) instead of ``lax.psum`` where its hops are pinned under
#: the backward pass.  16 MiB is the smallest bucket a step-level chip run
#: has shown to pay (the four-chip LM cell's attention projections, PR
#: 45).  A ring alone costs the core more than the ``psum`` it replaces at
#: every size probed, and near 4 MiB the two hold it about as long (0.13-
#: 0.26 ms against 0.08-0.14, ``benchmarks/grad_exchange_probe.py``):
#: between 4 and 16 MiB nothing was measured in a step.
RING_MIN_BYTES = 16 * 1024 * 1024


def overlap_enabled(default: bool = True) -> bool:
    """The :data:`ENV_OVERLAP` gate: unset -> ``default`` (ON);
    ``0``/``false``/``off``/``no`` -> False; anything else -> True."""
    raw = os.environ.get(ENV_OVERLAP, "").strip().lower()
    if not raw:
        return default
    return raw not in ("0", "false", "off", "no")


def resolve_granularity(default: int = DEFAULT_GRANULARITY) -> int:
    """The :data:`ENV_OVERLAP_GRANULARITY` override, clamped to >= 1."""
    raw = os.environ.get(ENV_OVERLAP_GRANULARITY, "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    return max(1, int(default))


@dataclasses.dataclass(frozen=True)
class OverlapSchedule:
    """Emission plan over a :class:`~.packing.GradPacker`'s buckets.

    ``stages`` lists bucket indices in emission order, grouped into
    stages of ``granularity`` buckets each: within a stage every
    bucket's pack is emitted before any of the stage's collectives
    (coarser stages give the compiler bigger fusion windows; stage size
    1 launches each collective at its earliest ready point).  The stage
    grouping never changes *which* collectives run or their per-bucket
    operands — it is pure trace order, hence bit-exact vs eager.
    """

    stages: Tuple[Tuple[int, ...], ...]
    granularity: int

    @property
    def order(self) -> Tuple[int, ...]:
        """Flat bucket emission order."""
        return tuple(i for stage in self.stages for i in stage)

    @property
    def n_buckets(self) -> int:
        return sum(len(s) for s in self.stages)

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    def describe(self) -> dict:
        return {
            "granularity": self.granularity,
            "n_stages": self.n_stages,
            "n_buckets": self.n_buckets,
            "order": list(self.order),
        }


def build_overlap_schedule(
    packer, granularity: int = DEFAULT_GRANULARITY
) -> OverlapSchedule:
    """Reverse leaf-production emission order for ``packer``'s buckets.

    Buckets are ordered by their *last* member leaf (descending): a
    bucket is ready when its final leaf's gradient exists, and
    reverse-mode AD produces later-flatten-order leaves' grads first.
    Per-dtype grouping can interleave buckets' leaf ranges, so the sort
    key is the readiness leaf, not the bucket's plan position.  Ties
    (identical last-leaf — impossible for a well-formed plan, but cheap
    to pin) break by descending bucket index for determinism.
    """
    g = max(1, int(granularity))
    order: List[int] = sorted(
        range(len(packer.buckets)),
        key=lambda i: (max(packer.buckets[i].leaf_indices), i),
        reverse=True,
    )
    stages = tuple(
        tuple(order[i : i + g]) for i in range(0, len(order), g)
    )
    return OverlapSchedule(stages=stages, granularity=g)


#: :func:`walk_with_exchange` ties the operands of a matrix product or a
#: call that hold at least ``PIN_MIN_ELEMS`` elements and were made at
#: most ``PIN_AGE`` equations before it: in a backward pass the cotangent
#: that has just arrived, which two products read (it lies in memory
#: whatever is fused round it, so that tying it moves no byte) — and not
#: a residual of the forward pass: tied, an activation the compiler
#: would have recomputed inside its reader is kept from the forward pass
#: on, 0.4 GB a layer at the dp4 cell's shapes (the one step both were
#: set on; ``tests/test_ring_exchange.py`` holds each to its side).
PIN_MIN_ELEMS = 1 << 16
PIN_AGE = 32


def _fresh_operands(eqn, i, born):
    """The operands of equation ``i`` a hop can be tied to (none where
    the equation is no matrix product or call): see :data:`PIN_AGE`.
    ``born`` maps a variable to the index of the equation that made it."""
    from jax import core as jcore
    from jax.extend.core import Literal

    if not (eqn.primitive.name == "dot_general"
            or any(jcore.jaxprs_in_params(eqn.params))):
        return []
    return list(dict.fromkeys(
        v for v in eqn.invars if not isinstance(v, Literal)
        and v.aval.size >= PIN_MIN_ELEMS
        and i - born.get(v, -PIN_AGE) < PIN_AGE))


def _born(jaxpr):
    return {v: i for i, eqn in enumerate(jaxpr.eqns) for v in eqn.outvars}


def pin_sites(jaxpr) -> List[int]:
    """The equations of ``jaxpr`` :func:`walk_with_exchange` can tie a hop
    to, by index.  A backward pass that is one ``scan`` over the layers,
    or one of convolutions, has none after its gradients are made: its
    exchange keeps ``lax.psum``."""
    born = _born(jaxpr)
    return [i for i, eqn in enumerate(jaxpr.eqns)
            if _fresh_operands(eqn, i, born)]


def made_at(jaxpr, watch_from) -> List[int]:
    """For each output of ``jaxpr`` from ``watch_from`` on, the index of
    the equation that makes it (-1: an input or a constant)."""
    born = _born(jaxpr)
    return [born.get(v, -1) for v in jaxpr.outvars[watch_from:]]


def walk_with_exchange(closed_jaxpr, args, watch_from, on_value, flying,
                       land):
    """Run ``closed_jaxpr`` on ``args`` equation by equation with an
    exchange in flight beside it; returns its outputs and how many times
    the exchange was tied to the computation.

    The outputs from ``watch_from`` on are watched (the gradient
    leaves): ``on_value(k, value)`` is called the moment the ``k``-th of
    them is made and says whether a bucket's ring started there.
    ``flying()`` lists the arrays now in flight and ``land(arrays)`` takes
    them back, landed, which sends the next hops.

    A dependence is the one thing that places a hop: left to itself the
    scheduler of libtpu 0.0.34 starts five collective-permutes early,
    stretches them over the whole backward pass and runs every other hop
    after it (five are in flight at most).  So at the first matrix
    product or call after a ring has started, its large operands and the
    arrays in flight pass through one ``optimization_barrier``, and every
    later reader takes the tied operands (two readers of one buffer under
    two names would cost a copy).  What was in flight has landed before
    that equation and everything downstream of it; the next hops are sent
    from there; and the next barrier waits for the newest ring's first
    pieces, so for the product that made its gradients: that product and
    the hops lie between the same two barriers.

    The loop is ``jax.core.eval_jaxpr``'s own (the equation's source
    information and context kept, so that the lowered program's scopes
    are the traced function's), with the ties between the equations.
    """
    import jax
    from jax.extend import source_info_util
    from jax.extend.core import Literal

    jaxpr = closed_jaxpr.jaxpr
    env = dict(zip(jaxpr.constvars, closed_jaxpr.consts))
    env.update(zip(jaxpr.invars, args))
    born, watched, ties = {}, {}, 0
    for k, v in enumerate(jaxpr.outvars[watch_from:]):
        if not isinstance(v, Literal):
            watched.setdefault(v, []).append(k)

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    def made(v):
        return any([on_value(key, env[v]) for key in watched.get(v, ())])

    started = any([made(v) for v in jaxpr.invars])
    for i, eqn in enumerate(jaxpr.eqns):
        fresh = _fresh_operands(eqn, i, born) if started else []
        in_flight = flying() if fresh else []
        if in_flight:
            tied, landed = jax.lax.optimization_barrier(
                ([env[v] for v in fresh], in_flight))
            env.update(zip(fresh, tied))
            land(landed)
            started = False
            ties += 1
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        name_stack = (source_info_util.current_name_stack()
                      + eqn.source_info.name_stack)
        with source_info_util.user_context(
                eqn.source_info.traceback, name_stack=name_stack), \
                eqn.ctx.manager:
            ans = eqn.primitive.bind(
                *subfuns, *map(read, eqn.invars), **bind_params)
        if not eqn.primitive.multiple_results:
            ans = [ans]
        env.update(zip(eqn.outvars, ans))
        born.update((v, i) for v in eqn.outvars)
        started |= any([made(v) for v in eqn.outvars])
    return [read(v) for v in jaxpr.outvars], ties
