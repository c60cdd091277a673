"""Communicator factory.

Reference: ``create_communicator`` in
REF:chainermn/communicators/__init__.py — a string → class dispatch that is
the single user entry point for distributed setup, defaulting ``mpi_comm``
to ``MPI.COMM_WORLD``.  Here the "world" default is the full device mesh
built from ``jax.devices()`` (``mesh_utils.build_mesh``).

Name map (reference → this package):

=================  ==========================================================
``naive``          per-parameter psum, CPU-friendly correctness oracle
``flat``           single fused psum over one packed buffer (alias)
``pure_nccl``      alias of ``xla_ici`` — the fastest flat backend
``xla_ici``        the TPU-native headline backend (BASELINE.json)
``hierarchical``   psum over ``intra`` (ICI) then ``inter`` (DCN)
``two_dimensional``  reduce-scatter/allreduce/all-gather over ICI×DCN
``single_host``    ICI-only; asserts one host (ref: ``single_node``)
``non_cuda_aware``  alias of ``hierarchical`` — the reference's host-staged
                   fallback has no TPU meaning (XLA owns staging), but the
                   name resolves for API parity
=================  ==========================================================
"""

from __future__ import annotations

from typing import Any

from jax.sharding import Mesh

from .base import CommunicatorBase
from .hierarchical import HierarchicalCommunicator
from .naive import NaiveCommunicator
from .single_host import SingleHostCommunicator, SingleNodeCommunicator
from .two_dimensional import TwoDimensionalCommunicator
from .xla_ici import FlatCommunicator, XlaIciCommunicator
from . import mesh_utils, overlap, packing, quant
from .mesh_utils import build_mesh
from .overlap import OverlapSchedule, build_overlap_schedule
from .packing import DEFAULT_BUCKET_BYTES, GradPacker, pack_tree

_COMMUNICATORS: dict[str, type[CommunicatorBase]] = {
    "naive": NaiveCommunicator,
    "flat": FlatCommunicator,
    "xla_ici": XlaIciCommunicator,
    "pure_nccl": XlaIciCommunicator,
    "hierarchical": HierarchicalCommunicator,
    "non_cuda_aware": HierarchicalCommunicator,
    "two_dimensional": TwoDimensionalCommunicator,
    "single_host": SingleHostCommunicator,
    "single_node": SingleNodeCommunicator,
}


def create_communicator(
    communicator_name: str = "xla_ici",
    mesh: Mesh | None = None,
    allreduce_grad_dtype: Any | None = None,
    inter_size: int | None = None,
    intra_size: int | None = None,
    bucket_bytes: int | None = None,
    scatter_inter: bool = False,
    overlap: bool | None = None,
    overlap_granularity: int | None = None,
    comm_dtype: Any | None = None,
) -> CommunicatorBase:
    """Create a communicator by name (reference signature:
    ``create_communicator(communicator_name='hierarchical', mpi_comm=None,
    allreduce_grad_dtype=None)``).

    ``mesh`` defaults to the full-slice ``(inter, intra)`` mesh;
    ``inter_size``/``intra_size`` force a factorization (testing analogue of
    running ``mpiexec -n 2`` on one box, SURVEY §4).

    ``bucket_bytes`` caps the fused gradient-allreduce buckets (see
    :mod:`chainermn_tpu.communicators.packing` and docs/performance.md):
    ``None`` resolves env override → 4 MiB default, ``0``
    disables bucketing (legacy per-leaf lowering), ``>0`` is an explicit
    cap.  ``scatter_inter`` (hierarchical only) decomposes its intra leg
    into reduce-scatter/all-gather so the inter (DCN) hop moves
    ``1/intra_size`` of the bytes.

    ``overlap`` controls the backward-overlapped bucket emission
    (:mod:`chainermn_tpu.communicators.overlap`): ``None`` resolves the
    ``CHAINERMN_TPU_OVERLAP`` env gate (default ON), ``False`` pins the
    eager pack-all-then-reduce-all schedule (bit-exact against the staged
    one; no A/B of the two on the chip is on the ledger).
    ``overlap_granularity`` sets buckets emitted per
    schedule stage (``None`` = env → 1).

    ``comm_dtype`` puts gradient buckets on a low-precision wire
    (:mod:`chainermn_tpu.communicators.quant`): ``"int8"`` or ``"fp8"``
    (e4m3 where the backend supports it, int8 fallback otherwise) scale
    each packed bucket by its global amax, run the sum collective on
    the narrow dtype, and dequantize in f32.  ``None`` resolves the
    ``CHAINERMN_TPU_COMM_DTYPE`` env → off; ``"none"``
    pins it off.  Error vs the fp32 allreduce is bounded per dtype
    (docs/performance.md).
    """
    from chainermn_tpu.observability import startup

    startup.mark("create_communicator")
    try:
        cls = _COMMUNICATORS[communicator_name]
    except KeyError:
        raise ValueError(
            f"unknown communicator {communicator_name!r}; "
            f"choose from {sorted(_COMMUNICATORS)}"
        ) from None
    if mesh is None:
        mesh = build_mesh(inter_size=inter_size, intra_size=intra_size)
    kwargs: dict = dict(
        allreduce_grad_dtype=allreduce_grad_dtype, bucket_bytes=bucket_bytes,
        overlap=overlap, overlap_granularity=overlap_granularity,
        comm_dtype=comm_dtype,
    )
    if scatter_inter:
        if not issubclass(cls, HierarchicalCommunicator):
            raise ValueError(
                "scatter_inter is only meaningful for the hierarchical "
                f"communicator, not {communicator_name!r}"
            )
        kwargs["scatter_inter"] = True
    return cls(mesh, **kwargs)


__all__ = [
    "CommunicatorBase",
    "NaiveCommunicator",
    "FlatCommunicator",
    "XlaIciCommunicator",
    "HierarchicalCommunicator",
    "TwoDimensionalCommunicator",
    "SingleHostCommunicator",
    "SingleNodeCommunicator",
    "create_communicator",
    "build_mesh",
    "mesh_utils",
    "overlap",
    "packing",
    "quant",
    "GradPacker",
    "OverlapSchedule",
    "build_overlap_schedule",
    "pack_tree",
    "DEFAULT_BUCKET_BYTES",
]
