"""Mesh/topology bookkeeping — the TPU-native analogue of the reference's
communication utilities.

The reference (REF:chainermn/communicators/_communication_utility.py)
discovers topology with an MPI allgather of hostnames (``init_ranks``) and
builds intra-/inter-node sub-communicators with ``MPI_Comm_split``.  On TPU
the equivalent facts come from JAX itself: ``jax.devices()`` enumerates every
chip in the slice, ``jax.process_index()/process_count()`` give the host
topology, and a :class:`jax.sharding.Mesh` with an ``(inter, intra)`` axis
split plays the role of the reference's inter-/intra-node MPI communicators.
ICI collectives ride the ``intra`` axis; DCN-spanning collectives ride
``inter``.

There is no analogue of REF:chainermn/communicators/_memory_utility.py's
pinned-host/GPU pack buffers: XLA owns device memory and fuses the
pack/allreduce/unpack pipeline itself.  The packing *strategy* of the
``flat``/``pure_nccl`` communicators survives as an explicit flatten-concat
in :mod:`chainermn_tpu.communicators.xla_ici`.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_INTER = "inter"  # DCN / host-spanning axis (reference: inter-node MPI comm)
AXIS_INTRA = "intra"  # ICI / within-host axis (reference: intra-node NCCL comm)


def build_mesh(
    inter_size: int | None = None,
    intra_size: int | None = None,
    devices: Sequence[jax.Device] | None = None,
    axis_names: tuple[str, str] = (AXIS_INTER, AXIS_INTRA),
) -> Mesh:
    """Build the 2-D ``(inter, intra)`` device mesh every communicator runs on.

    Mirrors ``init_ranks`` + ``init_intra_mpi_comm`` + ``init_inter_mpi_comm``
    in REF:chainermn/communicators/_communication_utility.py: the ``inter``
    axis corresponds to the node dimension (one entry per host, DCN between
    them) and ``intra`` to the chips within a host (ICI between them).

    On a real multi-host slice the default is ``inter = process_count`` and
    ``intra = local chips per host``.  For single-process testing (the
    analogue of the reference's ``mpiexec -n 2`` on one box, SURVEY §4) any
    factorization of the device count may be forced, e.g.
    ``build_mesh(inter_size=2, intra_size=4)`` on 8 virtual CPU devices to
    exercise both collective legs of the hierarchical/2-D algorithms.
    """
    from chainermn_tpu.observability import startup

    startup.mark("build_mesh")
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)

    if inter_size is None and intra_size is None:
        inter_size = jax.process_count()
    if inter_size is None:
        assert intra_size is not None
        inter_size = n // intra_size
    if intra_size is None:
        intra_size = n // inter_size
    if inter_size * intra_size != n:
        raise ValueError(
            f"mesh shape ({inter_size}, {intra_size}) does not cover "
            f"{n} devices"
        )

    # Order devices so that each `inter` row holds one host's chips — this is
    # what keeps `intra`-axis collectives on ICI.  jax.devices() is already
    # process-major, matching the reference's hostname-sorted rank layout.
    grid = np.array(devices).reshape(inter_size, intra_size)
    return Mesh(grid, axis_names)


def axis_size_traced(name: str) -> int:
    """Static size of a mesh axis from inside ``shard_map``."""
    return jax.lax.axis_size(name)


def flat_rank(axes: Sequence[str]):
    """Traced flattened rank over ``axes`` — usable inside ``shard_map``.

    The analogue of the reference's ``comm.rank`` in its SPMD per-process
    view (REF:chainermn/communicators/communicator_base.py).  Row-major over
    the given axes, so with ``axes=('inter','intra')`` rank order matches
    the reference's hostname-major global rank order.
    """
    idx = jax.lax.axis_index(axes[0])
    for name in axes[1:]:
        idx = idx * axis_size_traced(name) + jax.lax.axis_index(name)
    return idx


def axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return math.prod(mesh.shape[a] for a in axes)
