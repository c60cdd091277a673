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
2. **Async lowering** is the compiler's: whether a collective becomes
   an ``all-reduce-start``/``all-reduce-done`` pair with backward
   compute between the two is libtpu's scheduling.  This package writes
   no compiler flag — an unknown flag in ``XLA_FLAGS`` aborts the
   process at backend init (jaxlib 0.9.0), and no chip A/B exists for
   any.  (At libtpu 0.0.34's defaults the four-chip LM step compiled to
   17 plain all-reduces and no start/done pair — ``chip_smoke.py``, PR
   21; which flags, handed to libtpu through ``LIBTPU_INIT_ARGS``,
   change that is ROADMAP S6's A/B.)  The schedule itself is
   platform-neutral and bit-exact everywhere (the per-bucket math is
   identical to the eager path — only trace order changes, and fp
   addition inside each bucket is untouched).

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
