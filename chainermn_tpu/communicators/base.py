"""Communicator base class — TPU-native contract matching the reference's
``CommunicatorBase`` (REF:chainermn/communicators/communicator_base.py).

Design stance (SURVEY §7): the reference is N identical MPI processes each
holding one GPU, with an eager communicator object whose methods *are* the
network operations.  The TPU-native rebuild keeps the same API surface but
runs on one global JAX view: a :class:`jax.sharding.Mesh` whose
``(inter, intra)`` axes encode the reference's inter-/intra-node split, with
XLA collectives (``psum``/``all_gather``/``all_to_all``/``ppermute``) as the
data plane.

Two planes, mirroring the reference's MPI-control/NCCL-data split (SURVEY
§2.6):

* **device plane** — collectives *traced into* a jitted program.  Methods in
  this plane (``allreduce_grad``, ``broadcast_data``, ``bcast``,
  ``allgather``, ``alltoall``, ``reduce_scatter``, ``send``/``recv``, …) must
  be called inside a ``shard_map`` over this communicator's mesh axes, where
  every device runs the same SPMD program — exactly the per-rank viewpoint a
  ChainerMN process had.  Eager convenience wrappers (``eager_*``) wrap the
  same implementations in ``jit(shard_map(...))`` for use on "rank-stacked"
  global arrays (leading axis = ``device_size``).
* **host/object plane** — pickled-object transport between *processes*
  (``bcast_obj``, ``gather_obj``, ``allreduce_obj``), the analogue of the
  reference's pickle-over-MPI ``*_obj`` methods
  (REF:chainermn/communicators/mpi_communicator_base.py).  Implemented over
  ``jax.experimental.multihost_utils`` when ``process_count > 1`` and as
  local no-ops on a single host.

Rank semantics: the reference has one process per GPU, so ``rank`` is both a
host and a device concept.  Under JAX one process drives many chips, so the
two split: ``rank``/``size`` here are *host*-plane (process) values — the
ones used for logging gates, dataset scattering, and object transport —
while ``device_size``/``intra_size``/``inter_size`` describe the chip mesh
and ``axis_index()`` is the traced per-chip rank inside ``shard_map``.
``intra_rank`` keeps its reference role of "which local accelerator should I
use" in the degenerate sense: JAX processes own all their local devices, so
it is always 0 and ``local_devices`` is the real answer.
"""

from __future__ import annotations

import os
import pickle
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import kvtransport, mesh_utils, overlap as overlap_mod, packing, quant
from . import ring as ring_mod


def shard_map_compat(fn, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


_shard_map = shard_map_compat


_PPERMUTE_FALLBACK_WARNED = False


def _warn_ppermute_fallback(world: int) -> None:
    """One-time warning when ``ppermute`` hits its general fallback.

    The fallback is ``all_gather`` + slice: correct for arbitrary
    permutations, but it moves ``world × message`` bytes instead of the
    O(message) the factored paths move.  No in-tree caller reaches it, so
    user code arriving here is almost always an unintended routing pattern
    worth restructuring (e.g. into per-axis maps or a uniform ring shift).
    """
    global _PPERMUTE_FALLBACK_WARNED
    if _PPERMUTE_FALLBACK_WARNED:
        return
    _PPERMUTE_FALLBACK_WARNED = True
    warnings.warn(
        "ppermute: permutation does not factor per-axis and is not a "
        f"uniform flat shift; falling back to all_gather over all "
        f"{world} devices + slice.  This moves world-volume "
        f"({world}x message) bytes per call.  Restructure the "
        "permutation (per-axis injective maps, or a constant "
        "(dst-src) % world shift) to get the O(message) paths.  "
        "This warning is emitted once per process.",
        RuntimeWarning,
        stacklevel=3,
    )


def _tree_cast(tree, dtype):
    if dtype is None:
        return tree
    # Skip leaves already at the target dtype: a no-op astype still emits
    # a convert_element_type into the jaxpr, inflating the hlo_audit
    # census (and the compiler's work) for nothing.
    return jax.tree.map(
        lambda x: x if x.dtype == dtype else x.astype(dtype), tree
    )


class CommunicatorBase:
    """Abstract communicator. Subclasses specialise ``allreduce_grad``.

    Reference contract: REF:chainermn/communicators/communicator_base.py
    (properties ``rank/size/intra_rank/intra_size/inter_rank/inter_size``;
    collectives ``send/recv/bcast/gather/allgather/alltoall``; model-level
    ``broadcast_data``/``allreduce_grad``; object-level ``bcast_obj``/
    ``gather_obj``/``allreduce_obj``; ``split``).
    """

    name = "base"
    _plane_count = 0  # class-level: SPMD construction order, see __init__

    def __init__(
        self,
        mesh: Mesh | None = None,
        axes: Sequence[str] | None = None,
        allreduce_grad_dtype: Any | None = None,
        host_members: Sequence[int] | None = None,
        bucket_bytes: int | None = None,
        overlap: bool | None = None,
        overlap_granularity: int | None = None,
        comm_dtype: Any | None = None,
    ):
        # Subgroup membership (``split(color, key)``): the ordered GLOBAL
        # process indices participating in this communicator's host plane.
        # None = the full world.  The calling process must be a member.
        self._hp_members = (
            list(host_members) if host_members is not None else None
        )
        if (
            self._hp_members is not None
            and jax.process_index() not in self._hp_members
        ):
            raise ValueError(
                f"process {jax.process_index()} is not in host_members "
                f"{self._hp_members}"
            )
        if mesh is None:
            mesh = mesh_utils.build_mesh()
        self.mesh = mesh
        self.axes = tuple(axes if axes is not None else mesh.axis_names)
        for a in self.axes:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh axes {mesh.axis_names}")
        # The analogue of pure_nccl's fp16 allreduce option
        # (REF:chainermn/communicators/pure_nccl_communicator.py,
        # `allreduce_grad_dtype`): cast grads before the collective, cast
        # back after.  bfloat16 is the TPU-native choice.
        self.allreduce_grad_dtype = (
            jnp.dtype(allreduce_grad_dtype) if allreduce_grad_dtype else None
        )
        # Gradient bucketing cap (chainermn_tpu.communicators.packing):
        # None = resolve at call time (env override -> default),
        # 0 = bucketing off (the legacy per-leaf/one-buffer lowering),
        # >0 = explicit per-bucket payload cap in bytes.
        if bucket_bytes is not None:
            bucket_bytes = int(bucket_bytes)
            if bucket_bytes < 0:
                raise ValueError(
                    f"bucket_bytes must be >= 0, got {bucket_bytes}"
                )
        self.bucket_bytes = bucket_bytes
        # Backward-overlapped bucket emission
        # (chainermn_tpu.communicators.overlap): None = resolve at call
        # time (CHAINERMN_TPU_OVERLAP env, default ON), True/False pins
        # the schedule regardless of environment.
        self.overlap = None if overlap is None else bool(overlap)
        if overlap_granularity is not None:
            overlap_granularity = int(overlap_granularity)
            if overlap_granularity < 1:
                raise ValueError(
                    "overlap_granularity must be >= 1, got "
                    f"{overlap_granularity}"
                )
        self.overlap_granularity = overlap_granularity
        # Low-precision gradient exchange (chainermn_tpu.communicators.
        # quant): None = resolve at call time (CHAINERMN_TPU_COMM_DTYPE
        # env -> off), "none" pins it off, "int8"/"fp8" scale
        # packed buckets onto that wire dtype around the sum collective.
        self.comm_dtype = quant.canonical_comm_dtype(comm_dtype)
        # Host-plane transport context.  Communicator construction is SPMD
        # (every process builds the same communicators in the same order —
        # the same contract MPI_Comm_create relies on), so a class-level
        # creation counter yields matching key namespaces on all processes,
        # playing the role of an MPI communicator context id.  The contract
        # is VERIFIED, not trusted: each plane publishes its construction
        # site (the first user frame below) at creation and checks it
        # against rank 0's at first use, so a rank-conditional
        # create_communicator fails fast with a diagnostic instead of
        # silently delivering another stream's payloads or hanging.
        import traceback

        site = "<unknown>"
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for frame in reversed(traceback.extract_stack()[:-1]):
            if not frame.filename.startswith(pkg):
                site = f"{frame.filename}:{frame.lineno}"
                break
        CommunicatorBase._plane_count += 1
        self._obj_plane = kvtransport.ObjectPlane(
            f"comm{CommunicatorBase._plane_count}",
            jax.process_index(), self.size,
            site=site, members=self._hp_members,
        )

    # ------------------------------------------------------------------
    # Host-plane topology (process granularity — reference ``rank``/``size``)
    # ------------------------------------------------------------------
    @property
    def rank(self) -> int:
        if self._hp_members is not None:
            return self._hp_members.index(jax.process_index())
        return jax.process_index()

    @property
    def size(self) -> int:
        if self._hp_members is not None:
            return len(self._hp_members)
        return jax.process_count()

    @property
    def intra_rank(self) -> int:
        # Reference: GPU index within the node, used as `device = comm.intra_rank`.
        # A JAX process owns all its local devices; see module docstring.
        return 0

    @property
    def local_devices(self):
        # Compare against the GLOBAL process index: on a split() subgroup
        # self.rank is subgroup-relative while d.process_index is global.
        me = jax.process_index()
        return [d for d in self.mesh.devices.flat if d.process_index == me]

    # ------------------------------------------------------------------
    # Device-plane topology (chip granularity)
    # ------------------------------------------------------------------
    @property
    def device_size(self) -> int:
        """Total chips in this communicator's world (reference ``size``)."""
        return mesh_utils.axes_size(self.mesh, self.axes)

    @property
    def inter_size(self) -> int:
        return self.mesh.shape.get(mesh_utils.AXIS_INTER, 1) if mesh_utils.AXIS_INTER in self.axes else 1

    @property
    def intra_size(self) -> int:
        return self.mesh.shape.get(mesh_utils.AXIS_INTRA, 1) if mesh_utils.AXIS_INTRA in self.axes else 1

    @property
    def inter_rank(self) -> int:
        return self.rank  # one mesh row per host; host rank == inter row.

    # ------------------------------------------------------------------
    # Traced device-plane collectives (call inside shard_map over self.axes)
    # ------------------------------------------------------------------
    def axis_index(self):
        """Traced flattened device rank (0..device_size-1)."""
        return mesh_utils.flat_rank(self.axes)

    def allreduce(self, x, op: str = "sum"):
        """Generic traced allreduce (reference ``allreduce``/``multi_node_mean``)."""
        if op == "sum":
            return lax.psum(x, self.axes)
        if op == "mean":
            return lax.pmean(x, self.axes)
        if op == "max":
            return lax.pmax(x, self.axes)
        if op == "min":
            return lax.pmin(x, self.axes)
        raise ValueError(f"unknown op {op!r}")

    def bcast(self, x, root: int = 0):
        """Traced broadcast from flattened device rank ``root``.

        Reference: ``MpiCommunicatorBase.bcast``.  SPMD formulation: zero out
        every shard but the root's and psum — on TPU this lowers to a single
        all-reduce (or is pattern-matched to a collective-broadcast), riding
        ICI for the ``intra`` leg.
        """
        mask = (self.axis_index() == root).astype(x.dtype)
        return lax.psum(x * mask, self.axes)

    def allgather(self, x, axis: int = 0, tiled: bool = False):
        """Traced allgather (reference ``allgather``). Leading world axis."""
        return lax.all_gather(x, self.axes, axis=axis, tiled=tiled)

    def gather(self, x, root: int = 0, axis: int = 0):
        """Traced point-to-root gather (reference ``MPI_Gather``): ``root``
        receives every device's ``x`` stacked along ``axis``; other devices
        return zeros (the reference returns ``None`` off-root).

        Binomial-tree lowering, ``ceil(log2 n)`` collective rounds: in
        round ``k`` every device at relative rank ``2^k (mod 2^{k+1})``
        ships its accumulated block of ``2^k`` messages one tree level
        rootward, all in ONE ppermute.  Latency is log-depth (the previous
        one-ppermute-per-source schedule was world-linear — n−1 rounds and
        O(world²) HLO growth); aggregate wire stays O(world·message) (each
        message crosses each tree level once): leaves send one round-k
        block of 2^k rows (exactly O(message) for power-of-two worlds,
        where every block row is live; on non-power-of-two worlds trailing
        senders' blocks carry padding rows), internal nodes forward their
        subtree.
        For gather-then-use-everywhere patterns prefer :meth:`allgather`,
        which is a single collective.  For an output that exists ONLY on
        the root device (no O(world·message) zeros elsewhere), use
        :meth:`eager_gather`.
        """
        n = self.device_size
        if n == 1:
            return jnp.expand_dims(x, axis)
        idx = self.axis_index()
        buf = x[None]  # block of messages for relative ranks [me, me+width)
        for k in range((n - 1).bit_length()):
            width = 1 << k
            pairs = [
                ((s + root) % n, (s - width + root) % n)
                for s in range(width, n, 2 * width)
            ]
            # Senders' current buf holds rel ranks [s, s+width); after the
            # concat, receivers hold [r, r+2*width).  Non-participants
            # accumulate junk rows that the final root mask discards.
            buf = jnp.concatenate([buf, self.ppermute(buf, pairs)], axis=0)
        buf = buf[:n]  # non-power-of-two worlds: trailing rows are padding
        # buf rows are in RELATIVE order (row j = flat rank (root+j) % n);
        # roll restores flat-rank order, then mask to root.
        buf = jnp.roll(buf, root, axis=0)
        buf = jnp.where(idx == root, buf, jnp.zeros_like(buf))
        return jnp.moveaxis(buf, 0, axis) if axis else buf

    def alltoall(self, x, split_axis: int = 0, concat_axis: int = 0):
        """Traced all-to-all (reference ``alltoall``), the primitive under
        Ulysses-style sequence parallelism (SURVEY §5.7)."""
        return lax.all_to_all(
            x, self.axes, split_axis=split_axis, concat_axis=concat_axis,
            tiled=True,
        )

    def reduce_scatter(self, x, scatter_dimension: int = 0):
        """Traced reduce-scatter — the first leg of the two-dimensional
        algorithm (REF:chainermn/communicators/two_dimensional_communicator.py)."""
        return lax.psum_scatter(
            x, self.axes, scatter_dimension=scatter_dimension, tiled=True
        )

    def scatter(self, x, root: int = 0):
        """Traced point-to-root scatter (reference ``MPI_Scatter``): device
        ``d`` receives chunk ``d`` of ``root``'s ``x`` along axis 0.

        Binomial-tree lowering, ``ceil(log2 n)`` collective rounds (the
        mirror of :meth:`gather`): the root's buffer halves each round,
        with the upper half of every current holder's range shipped one
        tree level leafward in ONE ppermute.  Each receiver's ingress is
        its power-of-two-padded subtree (= exactly its subtree on
        power-of-two worlds) and the aggregate wire is O(world·chunk)
        (each chunk crosses each tree level once) — no broadcast of the
        whole buffer, and log-depth latency versus the previous
        one-ppermute-per-destination schedule's world-linear rounds.
        """
        n = self.device_size
        if x.shape[0] % n:
            raise ValueError(
                f"scatter axis 0 ({x.shape[0]}) must be divisible by the "
                f"device count ({n}); pad the input first"
            )
        chunk = x.shape[0] // n
        if n == 1:
            return x
        idx = self.axis_index()
        rel = (idx - root) % n
        K = (n - 1).bit_length()
        # Message-major layout in RELATIVE rank order (row j = the chunk
        # for flat rank (root+j) % n), padded to the next power of two so
        # every round's send block has a static shape.
        buf = jnp.roll(x.reshape(n, chunk, *x.shape[1:]), -root, axis=0)
        if (1 << K) != n:
            pad = jnp.zeros(((1 << K) - n,) + buf.shape[1:], buf.dtype)
            buf = jnp.concatenate([buf, pad], axis=0)
        for t in range(K):
            width = 1 << (K - t - 1)
            pairs = [
                ((r + root) % n, (r + width + root) % n)
                for r in range(0, n, 2 * width)
                if r + width < n
            ]
            got = self.ppermute(buf[width : 2 * width], pairs)
            # Receivers this round (rel ≡ width mod 2·width) adopt the
            # shipped block; holders keep their lower half; devices not yet
            # reached carry junk that a later round overwrites.
            buf = jnp.where(rel % (2 * width) == width, got, buf[:width])
        return buf.reshape((chunk,) + x.shape[1:])

    def ppermute(self, x, perm):
        """``lax.ppermute`` semantics over this communicator's (flattened)
        world: destinations named in ``perm`` (a list of (src, dst) flat
        ranks) receive their source's value, everyone else receives zeros.
        The building block of differentiable send/recv
        (chainermn_tpu.functions.point_to_point, mirroring
        REF:chainermn/functions/point_to_point_communication.py).

        Multi-axis lowering moves O(message) bytes, not O(world):

        1. *Per-axis product* — when the perm factors into one well-defined
           injective map per mesh axis (single-pair p2p, neighbor exchange,
           grid translations without flat wrap-around), it lowers to one
           ppermute hop per non-identity axis.
        2. *Uniform flat shift* — a constant ``(dst - src) % world`` shift
           (the ring case: ``ring_exchange``, pipelines over 2-axis meshes)
           wraps between rows, so the row hop is issued at both ``q`` and
           ``q+1`` and wrapped columns select the latter: 3 hops total.
        3. General perms that factor neither way fall back to
           ``all_gather`` + slice — correct for arbitrary routing, at
           world-volume cost (no in-tree caller hits this; the fallback
           exists for API completeness).

        All paths are natively differentiable (ppermute transposes to the
        reversed perm; the wrap select is elementwise).
        """
        if len(self.axes) == 1:
            return lax.ppermute(x, self.axes[0], perm)
        sizes = [self.mesh.shape[a] for a in self.axes]
        n = self.device_size

        def coords(r):
            c = []
            for s in reversed(sizes):
                c.append(r % s)
                r //= s
            return tuple(reversed(c))  # row-major; axes[0] slowest

        # (1) per-axis product decomposition.
        axis_maps: list[dict[int, int]] = [{} for _ in sizes]
        factors = True
        for s, d in perm:
            cs, cd = coords(s), coords(d)
            for k in range(len(sizes)):
                if axis_maps[k].setdefault(cs[k], cd[k]) != cd[k]:
                    factors = False
                    break
            if not factors:
                break
        if factors:
            factors = all(
                len(set(m.values())) == len(m) for m in axis_maps
            )
        if factors:
            out = x
            for k, axis in enumerate(self.axes):
                pairs = sorted(axis_maps[k].items())
                if all(a == b for a, b in pairs):
                    continue  # identity along this axis: no hop needed
                out = lax.ppermute(out, axis, pairs)
            return self._mask_non_dsts(out, perm)

        # (2) uniform flat shift over a 2-axis world.
        shifts = {(d - s) % n for s, d in perm}
        if len(shifts) == 1 and len(sizes) == 2:
            shift = shifts.pop()
            n_inter, n_intra = sizes
            q, r = divmod(shift, n_intra)
            if r:
                xj = lax.ppermute(
                    x, self.axes[1],
                    [(j, (j + r) % n_intra) for j in range(n_intra)],
                )
            else:
                xj = x  # row-multiple shift: no intra hop, no wrap
            row = lambda k: lax.ppermute(  # noqa: E731
                xj, self.axes[0],
                [(i, (i + k) % n_inter) for i in range(n_inter)],
            )
            xq = row(q) if q % n_inter else xj
            if r:
                # Columns j < r received a value that wrapped past the end
                # of its row and must advance one extra inter row.
                xq = jnp.where(
                    lax.axis_index(self.axes[1]) < r, row(q + 1), xq
                )
            return self._mask_non_dsts(xq, perm)

        # (3) general fallback: collapse via all_gather + slice.
        _warn_ppermute_fallback(n)
        src_for_dst = {d: s for s, d in perm}
        gathered = lax.all_gather(x, self.axes, axis=0)
        idx = self.axis_index()
        table = jnp.array(
            [src_for_dst.get(d, -1) for d in range(self.device_size)]
        )
        my_src = table[idx]
        picked = jnp.where(
            my_src >= 0,
            jnp.take(gathered, jnp.maximum(my_src, 0), axis=0),
            jnp.zeros_like(x),
        )
        return picked

    def _mask_non_dsts(self, out, perm):
        """Zero devices that are not a destination in ``perm`` — hop
        decompositions deliver junk to bystander devices that a true
        flattened ppermute would zero-fill."""
        dsts = {d for _, d in perm}
        if len(dsts) == self.device_size:
            return out
        table = jnp.asarray([d in dsts for d in range(self.device_size)])
        return jnp.where(table[self.axis_index()], out, jnp.zeros_like(out))

    # ------------------------------------------------------------------
    # Model plane (traced): the two methods every training step uses
    # ------------------------------------------------------------------
    def broadcast_data(self, tree, root: int = 0):
        """Replicate a parameter pytree from ``root`` to all devices.

        Reference: ``CommunicatorBase.broadcast_data(model)`` — the bcast of
        every parameter the multi-node optimizer issues on its first
        ``update`` (REF:chainermn/optimizers.py).
        """
        return jax.tree.map(lambda x: self.bcast(x, root), tree)

    def allreduce_grad(self, tree, overlap: bool | None = None):
        """Average a gradient pytree across the communicator's world.

        Reference: ``CommunicatorBase.allreduce_grad(model)`` — divides by
        ``size`` (mean), which every subclass here preserves.  Subclasses
        implement `_allreduce_impl` with their characteristic collective
        pattern; this wrapper handles the optional low-precision cast
        (``allreduce_grad_dtype``) and, for multi-leaf trees, the bucketed
        flat-buffer packing (:mod:`chainermn_tpu.communicators.packing`)
        that turns O(n_leaves) collectives into O(n_buckets) — the
        reference ``pure_nccl`` fusion generalized to every variant.
        Single-leaf trees take the direct path unchanged, and
        ``bucket_bytes=0`` (or ``CHAINERMN_TPU_BUCKET_BYTES=0``) restores
        the legacy unbucketed lowering.

        ``overlap`` pins the emission schedule for THIS call (the staged
        train-step pipeline threads it); ``None`` resolves ctor ->
        ``CHAINERMN_TPU_OVERLAP`` -> ON.  Overlapped emission is
        bit-exact vs eager: same per-bucket collectives, same operands —
        only the trace order changes so the buckets whose gradients the
        backward pass produces FIRST reduce while the rest still compute
        (see :mod:`chainermn_tpu.communicators.overlap`).

        When a ``comm_dtype`` resolves (ctor ->
        ``CHAINERMN_TPU_COMM_DTYPE``), each float bucket is amax-scaled
        onto the narrow wire dtype around its sum collective and
        dequantized in f32
        (:mod:`chainermn_tpu.communicators.quant`) — bounded-error, not
        bit-exact; the bound per dtype is documented in
        docs/performance.md.  Quantization applies to the BUCKETED path
        only: single-leaf trees and ``bucket_bytes=0`` keep the exact
        full-precision lowering (no bucket boundary means no amax scope).
        """
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return tree
        dtypes = jax.tree.map(lambda x: x.dtype, tree)
        tree = _tree_cast(tree, self.allreduce_grad_dtype)
        bb = self._bucket_bytes_for(leaves)
        if bb > 0:
            out = self._allreduce_bucketed(tree, bb, overlap=overlap)
        else:
            out = self._allreduce_impl(tree)
        return jax.tree.map(
            lambda x, d: x if x.dtype == d else x.astype(d), out, dtypes
        )

    def _allreduce_impl(self, tree):
        raise NotImplementedError

    def _allreduce_sum_impl(self, buf):
        """Pure SUM over the world for one bucket buffer — the collective
        leg the quantized path runs on the narrow wire dtype.  Separate
        from ``_allreduce_impl`` because every variant's mean divides by
        ``device_size`` inline, and integer division on an int8 buffer
        would truncate toward zero and bias every gradient; the quantized
        path applies the mean in f32 at dequant time instead.  Subclasses
        with a multi-leg pattern (hierarchical, two_dimensional) override
        with their characteristic sum chain.
        """
        return lax.psum(buf, self.axes)

    def _allreduce_quantized(self, buf, wire_dt):
        """One bucket through the blessed scale->cast->sum->cast->unscale
        pattern (see :mod:`chainermn_tpu.communicators.quant`): global
        amax via ``pmax``, world-headroom scale, narrow-dtype sum via
        :meth:`_allreduce_sum_impl`, f32 dequant carrying the mean."""
        world = self.device_size
        q, scale = quant.quantize_for_allreduce(buf, wire_dt, self.axes, world)
        qsum = self._allreduce_sum_impl(q)
        return quant.dequantize_mean(qsum, scale, world, buf.dtype)

    def resolve_comm_dtype(self) -> str | None:
        """Effective gradient wire dtype for one ``allreduce_grad`` call.

        Resolution order mirrors :meth:`resolve_bucket_bytes`: the
        constructor's ``comm_dtype`` if set ("none" pins off); else the
        ``CHAINERMN_TPU_COMM_DTYPE`` environment override; else off.
        Returns a canonical name from :data:`quant.COMM_DTYPE_CHOICES`,
        or ``None`` for off.
        """
        cd = self.comm_dtype
        if cd is None:
            env = os.environ.get(quant.ENV_COMM_DTYPE, "").strip()
            if env:
                try:
                    cd = quant.canonical_comm_dtype(env)
                except ValueError:
                    cd = None
        return None if cd in (None, "none") else cd

    def resolve_bucket_bytes(self) -> int:
        """Effective bucket cap for one ``allreduce_grad`` call.

        Resolution order: the constructor's ``bucket_bytes`` if set; else
        the ``CHAINERMN_TPU_BUCKET_BYTES`` environment override; else
        :data:`packing.DEFAULT_BUCKET_BYTES`.  Returns 0 when bucketing
        is disabled.
        """
        bb = self.bucket_bytes
        if bb is None:
            env = os.environ.get(packing.ENV_BUCKET_BYTES, "").strip()
            if env:
                try:
                    bb = int(env)
                except ValueError:
                    bb = None
        if bb is None:
            bb = packing.DEFAULT_BUCKET_BYTES
        return max(int(bb), 0)

    def resolve_overlap(self, overlap: bool | None = None) -> bool:
        """Effective overlap switch for one ``allreduce_grad`` call:
        the call-site pin if given, else the constructor's ``overlap``,
        else the ``CHAINERMN_TPU_OVERLAP`` environment gate (default
        ON — ``0`` is the escape hatch)."""
        if overlap is not None:
            return bool(overlap)
        if self.overlap is not None:
            return self.overlap
        return overlap_mod.overlap_enabled()

    def resolve_overlap_granularity(self) -> int:
        """Effective schedule granularity (buckets emitted per stage).

        Resolution order mirrors :meth:`resolve_bucket_bytes`: ctor ->
        ``CHAINERMN_TPU_OVERLAP_GRANULARITY`` env -> 1 (finest overlap:
        one collective per stage).
        """
        if self.overlap_granularity is not None:
            return self.overlap_granularity
        raw = os.environ.get(overlap_mod.ENV_OVERLAP_GRANULARITY, "").strip()
        if raw:
            try:
                return max(1, int(raw))
            except ValueError:
                pass
        return overlap_mod.DEFAULT_GRANULARITY

    def _allreduce_bucketed(self, tree, bucket_bytes: int,
                            overlap: bool | None = None):
        """One characteristic ``_allreduce_impl`` per contiguous per-dtype
        bucket.  Pack/unpack are pure layout moves (ravel/concat/slice),
        so they commute exactly with the elementwise-linear collectives
        every subclass lowers to — bucketed and unbucketed results are
        identical up to the collective's own dtype arithmetic.

        Two emission schedules, numerically identical:

        * **overlapped** (default): per-bucket pack + collective in
          reverse leaf-production order (`overlap.build_overlap_schedule`)
          so each collective's operands are exactly its member leaves and
          the first-ready buckets reduce under the rest of the backward
          pass (async start/done pairs straddle compute in the HLO once
          the latency-hiding scheduler runs).
        * **eager** (``CHAINERMN_TPU_OVERLAP=0``): pack every bucket,
          then reduce every bucket — the pre-overlap lowering, kept as
          the escape hatch and the parity oracle.
        """
        packer, reduce_bucket, schedule = self._exchange_plan(
            tree, bucket_bytes, overlap)
        self._report_plan(packer, ())
        from chainermn_tpu.observability.spans import named_scope

        if schedule is None:
            with named_scope("grad-pack"):
                bufs = packer.pack(tree)
            outs = [reduce_bucket(b) for b in bufs]
            with named_scope("grad-unpack"):
                return packer.unpack(outs)
        return self._emit_staged(
            packer, packer._check_tree(tree), reduce_bucket, schedule,
            [None] * packer.n_buckets)

    def _emit_staged(self, packer, leaves, reduce_bucket, schedule, outs):
        """The staged emission: stage by stage, pack and reduce every
        bucket ``outs`` does not hold yet, then unpack."""
        from chainermn_tpu.observability.spans import named_scope

        for s, stage in enumerate(schedule.stages):
            with named_scope(f"grad-stage{s}"):
                todo = [i for i in stage if outs[i] is None]
                bufs = [packer.pack_bucket(leaves, i) for i in todo]
                for i, buf in zip(todo, bufs):
                    outs[i] = reduce_bucket(buf)
        with named_scope("grad-unpack"):
            return packer.unpack(outs)

    def _bucket_bytes_for(self, leaves) -> int:
        """The cap :meth:`allreduce_grad` packs ``leaves`` under; 0 (no
        packing: one collective over the tree as it is) for a single leaf
        and where bucketing is off."""
        return self.resolve_bucket_bytes() if len(leaves) > 1 else 0

    def _exchange_plan(self, tree, bucket_bytes: int, overlap: bool | None):
        """How ``tree``'s buckets are exchanged: ``(packer, reduce_bucket,
        schedule)`` — ``reduce_bucket(buf)`` the characteristic collective
        (quantised where a wire dtype resolves), ``schedule`` the staged
        emission's (``None``: the eager one).  ``tree``'s leaves need a
        shape and a dtype only."""
        packer = packing.GradPacker.for_tree(tree, bucket_bytes=bucket_bytes)
        # Low-precision wire: quantize each float bucket around its sum
        # collective (quant.py's blessed pattern).  Integer buckets pass
        # through at full precision, and the schedule is untouched —
        # scaled buckets still stage in reverse leaf-production order.
        wire_dt = quant.wire_dtype(self.resolve_comm_dtype())

        def reduce_bucket(buf):
            if wire_dt is not None and quant.quantizable(buf.dtype):
                return self._allreduce_quantized(buf, wire_dt)
            return self._allreduce_impl(buf)

        if not self.resolve_overlap(overlap):
            return packer, reduce_bucket, None
        return packer, reduce_bucket, overlap_mod.build_overlap_schedule(
            packer, self.resolve_overlap_granularity())

    def _report_plan(self, packer, ring) -> None:
        """Publish one exchange's plan to the Reporter (trace-time)."""
        self._report_packing(packer)
        self._report_quant(
            packer, quant.wire_dtype(self.resolve_comm_dtype()))
        self._report_exchange(packer, ring)

    def mean_grads_under(self, grad_fn, params, *rest,
                         overlap: bool | None = None):
        """``out, grads = grad_fn(params, *rest)`` with the large float
        buckets exchanged UNDER the computation, as rings: a bucket's
        ring (:mod:`.ring`) starts where its last gradient is made and
        each hop is pinned to a later matrix product of the backward pass
        (:func:`.overlap.walk_with_exchange`).  Returns ``(out, grads,
        exchanged)``; ``exchanged`` says that ``grads`` (of ``params``'
        structure) is already the world's mean, what
        :meth:`allreduce_grad` would have made of it: the same buckets,
        every one the ring does not take through the same collective.

        A ring that nothing pins runs after the backward pass and is
        slower there than the ``psum`` it replaces, so this is the ONE
        place a bucket rides it, and only if the pass holds a product to
        pin a hop to after the first ring starts
        (:func:`.overlap.pin_sites`).  Anywhere else — no ring on this
        communicator, the eager emission, a quantised or cast wire, no
        bucket of :data:`.overlap.RING_MIN_BYTES`, a backward pass that
        is one ``scan`` — the result is ``grad_fn``'s own, untouched, and
        ``False``: the caller's :meth:`allreduce_grad` does the exchange.
        """
        bb = self._bucket_bytes_for(jax.tree.leaves(params))
        ring = ()
        if (bb and self.allreduce_grad_dtype is None
                and quant.wire_dtype(self.resolve_comm_dtype()) is None):
            # a gradient has its parameter's shape and dtype
            packer, reduce_bucket, schedule = self._exchange_plan(
                params, bb, overlap)
            if schedule is not None:
                ring = frozenset(i for i, b in enumerate(packer.buckets)
                                 if self._rides_ring(b))
        if not ring:
            return (*grad_fn(params, *rest), False)
        from chainermn_tpu.observability.spans import named_scope

        closed, shapes = jax.make_jaxpr(grad_fn, return_shape=True)(
            params, *rest)
        args = jax.tree.leaves((params, *rest))
        n_grads = packer.n_leaves
        n_out = len(closed.jaxpr.outvars) - n_grads
        born = overlap_mod.made_at(closed.jaxpr, n_out)
        first_start = min(max(born[k] for k in packer.buckets[i].leaf_indices)
                          for i in ring)
        if not any(site > first_start
                   for site in overlap_mod.pin_sites(closed.jaxpr)):
            # nothing to pin a hop to: the traced pass as it is
            flat = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)
            return (*jax.tree.unflatten(jax.tree.structure(shapes), flat),
                    False)
        self._report_plan(packer, ring)
        stage_of = {i: s for s, st in enumerate(schedule.stages) for i in st}
        bucket_of = {k: i for i in ring
                     for k in packer.buckets[i].leaf_indices}
        missing = {i: len(packer.buckets[i].leaf_indices) for i in ring}
        made: list = [None] * n_grads
        outs: list = [None] * packer.n_buckets
        rings: dict = {}  # bucket -> [its ring_steps, the pieces in flight]

        def go_on(i, steps, landed):
            with named_scope("allreduce"), \
                    named_scope(f"grad-stage{stage_of[i]}"):
                try:
                    rings[i] = [steps, steps.send(landed)]
                except StopIteration as done:
                    rings.pop(i, None)
                    outs[i] = done.value

        def on_value(k, value):
            made[k] = value
            i = bucket_of.get(k)
            if i is None:
                return False
            missing[i] -= 1
            if missing[i]:
                return False
            with named_scope("allreduce"), \
                    named_scope(f"grad-stage{stage_of[i]}"):
                buf = self._ring_input(packer, made, i)
            go_on(i, self._ring_steps(buf), None)
            return True

        def flying():
            return [piece for _, pieces in rings.values()
                    for piece in pieces]

        def land(landed):
            landed = iter(landed)
            for i, (steps, pieces) in list(rings.items()):
                go_on(i, steps, [next(landed) for _ in pieces])

        flat, ties = overlap_mod.walk_with_exchange(
            closed, args, n_out, on_value, flying, land)
        self._count("grad_exchange/ties", ties)
        # The last rings' remaining hops have only the update of the other
        # leaves to fly under.
        while rings:
            land(flying())
        out = jax.tree.unflatten(jax.tree.structure(shapes[0]), flat[:n_out])
        # What rode no ring — and a ring bucket whose gradients were never
        # made (constants of the program) — is reduced here, after the
        # pass, as allreduce_grad would have.
        with named_scope("allreduce"):
            return out, self._emit_staged(
                packer, flat[n_out:], reduce_bucket, schedule, outs), True

    def _ring_input(self, packer, leaves, i):
        """Bucket ``i`` as the ring takes it: a bucket that is one whole
        2-D leaf in the leaf's own shape (if it has rows enough to cut
        into pieces), any other packed."""
        k = packer.whole_leaf(i)
        # (a 3-D leaf cut along its leading axis is relaid out before
        # every write-back: six copies a leaf in the compiled step)
        if k is not None and leaves[k].ndim == 2 and ring_mod.piece_rows(
                leaves[k].shape, self.device_size):
            return leaves[k]
        return packer.pack_bucket(leaves, i)

    def _rides_ring(self, bucket) -> bool:
        """Whether :meth:`mean_grads_under` may reduce ``bucket`` (a
        :class:`packing.Bucket`) through :meth:`_ring_steps` instead of
        ``_allreduce_impl``.  No ring here: ``xla_ici`` has one."""
        return False

    def _ring_steps(self, buf):
        """One bucket's ring as the generator of :func:`ring.ring_steps`."""
        raise NotImplementedError

    def _count(self, name: str, value: int) -> None:
        """One trace-time counter to the Reporter, where telemetry is on."""
        from chainermn_tpu.observability import reporter as _reporter
        from chainermn_tpu.observability import spans as _spans

        rep = _reporter.get_reporter() if _spans.telemetry_active() else None
        if rep is not None:
            rep.count(name, value)

    def _report_exchange(self, packer, ring) -> None:
        """Publish how the buckets are exchanged (trace-time, beside
        :meth:`_report_packing`): how many ride the ring and how many a
        ``psum``, the ring's bytes and its collective-permutes.
        (:meth:`mean_grads_under` adds ``grad_exchange/ties``: how many
        times the rings were tied to the backward pass.)"""
        self._count("grad_exchange/ring_buckets", len(ring))
        self._count("grad_exchange/psum_buckets",
                    packer.n_buckets - len(ring))
        self._count("grad_exchange/ring_bytes", sum(
            packer.buckets[i].padded_bytes for i in ring))
        self._count("grad_exchange/hops",
                    len(ring) * ring_mod.ring_hops(self.device_size))

    def _report_packing(self, packer) -> None:
        """Publish the packing plan to the Reporter — at TRACE time (the
        plan is static; a jitted step re-publishes only when retraced)."""
        from chainermn_tpu.observability import reporter as _reporter
        from chainermn_tpu.observability import spans as _spans

        if not _spans.telemetry_active():
            return
        rep = _reporter.get_reporter()
        if rep is None:  # pragma: no cover - raced deactivation
            return
        rep.count("grad_pack/traces")
        rep.count("grad_pack/leaves", packer.n_leaves)
        rep.count("grad_pack/buckets", packer.n_buckets)
        rep.count("grad_pack/payload_bytes", packer.payload_bytes)
        rep.count(
            "grad_pack/pad_bytes", packer.padded_bytes - packer.payload_bytes
        )
        rep.histogram_observe("grad_pack/bucket_bytes", packer.bucket_bytes)

    def _report_quant(self, packer, wire_dt) -> None:
        """Publish the quantization plan (trace-time, like
        :meth:`_report_packing`): how many buckets ride the narrow wire
        and the bytes they move vs their full-precision payload."""
        if wire_dt is None:
            return
        from chainermn_tpu.observability import reporter as _reporter
        from chainermn_tpu.observability import spans as _spans

        if not _spans.telemetry_active():
            return
        rep = _reporter.get_reporter()
        if rep is None:  # pragma: no cover - raced deactivation
            return
        wire_size = jnp.dtype(wire_dt).itemsize
        n_q = sum(
            1 for b in packer.buckets if quant.quantizable(b.dtype)
        )
        rep.count("grad_pack/quant_buckets", n_q)
        rep.count("grad_pack/quant_wire_bytes", sum(
            b.padded_elems * wire_size
            for b in packer.buckets if quant.quantizable(b.dtype)
        ))

    def multi_node_mean(self, tree):
        """Alias matching later reference spellings of allreduce_grad."""
        return self.allreduce_grad(tree)

    # ------------------------------------------------------------------
    # Eager wrappers: jit(shard_map(traced impl)) over rank-stacked arrays
    # ------------------------------------------------------------------
    def _eager(self, fn: Callable, in_specs, out_specs):
        return jax.jit(
            _shard_map(
                fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
        )

    @property
    def world_axes(self):
        """This communicator's mesh axes in the form collectives take: the
        tuple for multi-axis worlds, the bare name for single-axis ones."""
        return self.axes if len(self.axes) > 1 else self.axes[0]

    @property
    def _world_spec(self):
        """PartitionSpec sharding a leading "rank" axis over the world."""
        return P(self.world_axes)

    def _eager_cached(self, key, stacked_tree, make_body):
        """Build-or-reuse a jitted shard_map for an eager collective.

        Keyed by (op, treedef, leaf shapes/dtypes) so repeated calls — the
        reference's per-step eager ``comm.allreduce_grad(model)`` pattern —
        hit the compile cache instead of re-tracing a fresh closure.
        """
        leaves, treedef = jax.tree.flatten(stacked_tree)
        cache_key = (key, treedef, tuple((l.shape, jnp.asarray(l).dtype) for l in leaves))
        cache = getattr(self, "_eager_cache", None)
        if cache is None:
            cache = self._eager_cache = {}
        fn = cache.get(cache_key)
        if fn is None:
            spec = self._world_spec
            body = make_body()
            specs = jax.tree.map(lambda _: spec, stacked_tree)
            fn = cache[cache_key] = self._eager(body, (specs,), specs)
        return fn(stacked_tree)

    def eager_allreduce_grad(self, stacked_tree):
        """Eager allreduce over a pytree whose leaves have a leading
        ``device_size`` axis ("each rank's grads", the reference's eager
        ``comm.allreduce_grad(model)`` call shape). Returns the same shape
        with every slice equal to the mean."""

        def make_body():
            def body(tree):
                tree = jax.tree.map(lambda x: jnp.squeeze(x, 0), tree)
                out = self.allreduce_grad(tree)
                return jax.tree.map(lambda x: x[None], out)

            return body

        # The resolved wire dtype joins the cache key: toggling
        # comm_dtype (attribute or env) between calls must retrace, not
        # reuse the other precision's compiled collective.
        return self._eager_cached(
            ("allreduce_grad", self.resolve_comm_dtype()),
            stacked_tree, make_body,
        )

    def device_for_rank(self, r: int):
        """The device at flattened rank ``r`` (row-major over ``self.axes``,
        matching :meth:`axis_index`)."""
        sizes = [self.mesh.shape[a] for a in self.axes]
        coords = dict.fromkeys(self.mesh.axis_names, 0)
        for a, s in zip(reversed(self.axes), reversed(sizes)):
            coords[a] = r % s
            r //= s
        pos = tuple(coords[a] for a in self.mesh.axis_names)
        return np.asarray(self.mesh.devices)[pos]

    def eager_gather(self, stacked_x, root: int = 0):
        """Gather a rank-stacked array to the ROOT DEVICE ONLY — the
        off-root-cheap output form of :meth:`gather`.

        ``stacked_x``: global array with leading ``device_size`` axis (each
        device's message at its rank slot).  Returns the same array resident
        solely on ``root``'s device (``SingleDeviceSharding``) — off-root
        devices hold nothing, versus the traced :meth:`gather`'s uniform
        SPMD output shape (zeros off-root, unavoidable inside shard_map).
        This is the TPU-native spelling of MPI_Gather's "only root gets the
        buffer": a resharding, which XLA lowers to its own point-to-root
        tree over ICI.  Single-host form (the root device must be
        addressable from this process; cross-process object gathers go
        through :meth:`gather_obj`)."""
        dev = self.device_for_rank(root)
        if dev.process_index != jax.process_index():
            raise ValueError(
                f"eager_gather root {root} lives on process "
                f"{dev.process_index}; only its owner can address it — use "
                "gather_obj for cross-process host-plane gathers"
            )
        return jax.device_put(
            stacked_x, jax.sharding.SingleDeviceSharding(dev)
        )

    def eager_broadcast_data(self, stacked_tree, root: int = 0):
        def make_body():
            def body(tree):
                tree = jax.tree.map(lambda x: jnp.squeeze(x, 0), tree)
                out = self.broadcast_data(tree, root)
                return jax.tree.map(lambda x: x[None], out)

            return body

        return self._eager_cached(
            ("broadcast_data", root), stacked_tree, make_body
        )

    def shard_map(self, fn, in_specs, out_specs, check_vma: bool = False):
        """Run ``fn`` in the per-device SPMD view over this communicator's
        mesh — the TPU spelling of "the body of a ChainerMN process"."""
        return _shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma,
        )

    def global_batch(self, batch):
        """Assemble the global batch from per-host batches.

        Under JAX one jitted step spans every process, so train steps take
        the *global* batch — there is no per-rank-batch analogue of the
        reference's model.  Each host passes the slice its
        ``scatter_dataset`` shard produced; leaves come back as global
        ``jax.Array``s sharded along axis 0 over the world
        (``shape[0] = per_host_batch * process_count``).  Per-host leading
        axes must be divisible by the host's local device count.
        Single-process: the host's batch IS the global batch, and each
        leaf is placed with the same world sharding — left where
        ``jnp.asarray`` puts it (the first device), a resident batch
        would be re-sharded off that one chip at every step.
        """
        from chainermn_tpu.observability import startup
        from chainermn_tpu.observability.spans import annotate

        call = startup.open_call("global_batch")    # None past the record
        try:
            with annotate("global_batch"):
                if self.size == 1:
                    sharding = jax.sharding.NamedSharding(
                        self.mesh, self._world_spec
                    )
                    return jax.device_put(batch, sharding)
                from jax.experimental import multihost_utils

                spec = self._world_spec
                specs = jax.tree.map(lambda _: spec, batch)
                return multihost_utils.host_local_array_to_global_array(
                    batch, self.mesh, specs
                )
        finally:
            startup.close(call)

    # ------------------------------------------------------------------
    # Host/object plane (reference pickle-over-MPI *_obj methods)
    # ------------------------------------------------------------------
    def send_obj(self, obj, dest: int, tag: int = 0) -> None:
        """True host-plane point-to-point send to process ``dest`` — the
        reference's ``MpiCommunicatorBase.send``.  No collective is
        involved: only the two endpoints participate.  ndarrays travel
        TYPED (raw buffer + dtype/shape header, no pickle — the
        reference's first-class ndarray path); other objects are pickled.
        The payload rides a direct TCP connection between the two
        processes (measured ~1 GB/s for 64 MiB arrays on localhost),
        rendezvoused — and, where sockets are unavailable
        (``CHAINERMN_TPU_SOCKET_P2P=0``), carried chunked — through the
        coordination service's KV store (see
        :mod:`chainermn_tpu.communicators.kvtransport`).  Matched
        ``send_obj``/``recv_obj`` pairs on the same (edge, tag) must occur
        in the same order on both sides, exactly MPI's matching rule."""
        if not (0 <= dest < self.size) or dest == self.rank:
            raise ValueError(
                f"send_obj dest must be another process in [0, {self.size}), "
                f"got {dest} (self.rank={self.rank})"
            )
        self._require_kv("send_obj")
        self._obj_plane.send(obj, dest, tag)

    def recv_obj(self, source: int, tag: int = 0,
                 timeout_ms: int | None = None):
        """Blocking host-plane receive from process ``source`` (the
        reference's ``MpiCommunicatorBase.recv``).  Waits indefinitely by
        default (MPI semantics); a finite ``timeout_ms`` raises instead,
        and the sequence stream stays intact so the receive may be
        retried."""
        if not (0 <= source < self.size) or source == self.rank:
            raise ValueError(
                f"recv_obj source must be another process in [0, {self.size}), "
                f"got {source} (self.rank={self.rank})"
            )
        self._require_kv("recv_obj")
        return self._obj_plane.recv(source, tag, timeout_ms=timeout_ms)

    def _require_kv(self, op: str) -> None:
        if not kvtransport.available():
            raise RuntimeError(
                f"{op} needs the jax.distributed coordination service "
                "(call jax.distributed.initialize); single-process runs "
                "have no peer to talk to"
            )

    def bcast_obj(self, obj, root: int = 0):
        if self.size == 1:
            return obj
        if kvtransport.available():
            # Chunked KV-store broadcast: exact payload bytes on the wire,
            # the reference's ``chunked_bcast_obj``
            # (REF:.../_communication_utility.py).
            return self._obj_plane.bcast(obj, root)
        self._require_subgroup_kv("bcast_obj")
        return self._bcast_obj_devices(obj, root)

    def _require_subgroup_kv(self, op: str) -> None:
        """The multihost_utils fallbacks below are WORLD collectives: on a
        split() subgroup they would mix colors' payloads (or deadlock), so
        subgroups insist on the coordination-service object plane."""
        if self._hp_members is not None:
            raise RuntimeError(
                f"{op} on a split() subgroup requires the jax.distributed "
                "coordination service (the world-collective fallback "
                "cannot scope to a subgroup)"
            )

    def _bcast_obj_devices(self, obj, root: int):
        """Fallback broadcast over device collectives for multi-process
        setups without a coordination-service client."""
        from jax.experimental import multihost_utils

        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        n = multihost_utils.broadcast_one_to_all(
            np.int64(payload.size), is_source=self.rank == root
        )
        buf = np.zeros(int(n), np.uint8)
        if self.rank == root:
            buf[:] = payload
        out = multihost_utils.broadcast_one_to_all(buf, is_source=self.rank == root)
        return pickle.loads(np.asarray(out).tobytes())

    def gather_obj(self, obj, root: int | None = None,
                   timeout_ms: int | None = None):
        """Gather every process's object.

        ``root=None`` (default): allgather semantics — the full list on
        every rank, which keeps SPMD callers branch-free (every in-tree
        symmetric caller wants this).

        ``root=r``: the reference's ``MPI_Gather`` wire profile
        (REF:chainermn/communicators/mpi_communicator_base.py ``gather``)
        — every non-root sends ONLY to root (O(n * payload) total wire,
        non-root processes fetch nothing) and the list is returned at
        root, ``None`` elsewhere.  ``timeout_ms`` bounds root's wait on
        EACH member's payload (the same contract ``recv_obj`` has), so a
        member that died before sending raises ``TimeoutError`` at root
        instead of blocking forever.

        Payloads travel at their exact size — no pad-to-max."""
        if self.size == 1:
            return [obj]
        if root is not None:
            if not (0 <= root < self.size):
                raise ValueError(f"gather_obj root {root} out of range")
            self._require_kv("gather_obj(root=...)")
            return self._obj_plane.gather(obj, root, timeout_ms=timeout_ms)
        if kvtransport.available():
            return self._obj_plane.allgather(obj, timeout_ms=timeout_ms)
        if timeout_ms is not None:
            raise ValueError(
                "gather_obj: timeout_ms with root=None needs the KV "
                "object plane; the process_allgather fallback has no "
                "bounded-wait implementation and would silently ignore it"
            )
        self._require_subgroup_kv("gather_obj")
        from jax.experimental import multihost_utils

        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        sizes = multihost_utils.process_allgather(np.int64(payload.size))
        buf = np.zeros(int(sizes.max()), np.uint8)
        buf[: payload.size] = payload
        all_bufs = multihost_utils.process_allgather(buf)
        return [
            pickle.loads(np.asarray(all_bufs[i][: int(sizes[i])]).tobytes())
            for i in range(self.size)
        ]

    def allgather_obj(self, obj):
        return self.gather_obj(obj)

    def allreduce_obj(self, obj, op=None):
        """Sum (or ``op``-reduce) pickled objects across processes — the
        reference's ``allreduce_obj`` used by the multi-node evaluator."""
        objs = self.gather_obj(obj)
        red = objs[0]
        for o in objs[1:]:
            red = op(red, o) if op is not None else red + o
        return red

    def scatter_obj(self, objs, root: int = 0):
        if self.size == 1:
            return objs[0] if self.rank == root else None
        if kvtransport.available():
            # Point-to-point: each rank receives only its own element
            # (reference ``scatter_obj`` wire profile), not the whole list.
            return self._obj_plane.scatter(objs, root)
        objs = self.bcast_obj(objs, root)
        return objs[self.rank]

    _barrier_seq = 0  # class-level: every process advances it identically

    def barrier(self, timeout_s: float | None = None):
        """``timeout_s`` (or env ``CHAINERMN_TPU_BARRIER_TIMEOUT_S``,
        which the elastic supervisor sets for every rank it spawns)
        bounds the wait: a peer that died mid-job raises
        ``TimeoutError`` here instead of stalling the survivor forever
        — the except hook then turns that into a loud, fast exit the
        supervisor can act on.  The env knob must be set identically on
        every rank (it routes the barrier over the object plane, and
        mixed routes would deadlock)."""
        if self.size <= 1:
            return
        if timeout_s is None:
            t = os.environ.get("CHAINERMN_TPU_BARRIER_TIMEOUT_S")
            timeout_s = float(t) if t else None
        if self._hp_members is not None:
            # Subgroup barrier: must involve ONLY the members (a world
            # barrier would deadlock against other colors).  An obj-plane
            # allgather of a token has exactly MPI_Barrier's completion
            # semantics: no member returns before every member arrived.
            self.gather_obj(
                None,
                timeout_ms=None if timeout_s is None
                else int(timeout_s * 1000),
            )
            return
        if timeout_s is not None and kvtransport.available():
            self._obj_plane.allgather(
                None, timeout_ms=int(timeout_s * 1000)
            )
            return
        from jax.experimental import multihost_utils

        # sync_global_devices asserts the name matches across processes;
        # SPMD processes hit barriers in the same order, so a class-level
        # sequence number is stable where id(self) would not be.
        CommunicatorBase._barrier_seq += 1
        multihost_utils.sync_global_devices(
            f"chainermn_tpu_barrier_{CommunicatorBase._barrier_seq}"
        )

    # ------------------------------------------------------------------
    def split(self, color_or_axes, key: int = 0):
        """Sub-communicator: ``MPI_Comm_split`` in both of its shapes.

        ``split(color, key=...)`` — the reference's arbitrary-subgroup
        semantics (REF:chainermn/communicators/mpi_communicator_base.py
        ``split(color, key)``): every member process calls with ITS color
        and key; processes sharing a color form a new communicator whose
        ranks are ordered by ``(key, old_rank)``.  ``color=None`` is
        MPI_UNDEFINED — the process participates in the split but gets
        ``None`` back.  The sub-communicator's mesh holds only the member
        processes' devices (inter = members, intra = local devices), and
        its object plane is namespaced to the subgroup.

        ``split(('intra',))`` — axis shape: a sub-communicator over a
        subset of THIS mesh's axes (a DP+PP run splitting per-axis
        sub-communicators, as the reference's seq2seq+DP examples split
        MPI_COMM_WORLD).  Variants whose collective pattern needs both
        ``inter`` and ``intra`` (hierarchical, two_dimensional) degrade to
        the flat single-collective communicator when split to one axis —
        as the reference's sub-communicators lose the node hierarchy too.
        """
        if color_or_axes is None or isinstance(
            color_or_axes, (int, np.integer)
        ):
            return self._split_color(color_or_axes, key)
        return self._split_axes(tuple(color_or_axes))

    def _split_axes(self, axes: tuple) -> "CommunicatorBase":
        # A failed variant construction may already have advanced the
        # SPMD plane ordinal; restore it so the degrade retry lands on
        # the SAME ordinal on every process.
        count = CommunicatorBase._plane_count
        try:
            return type(self)(
                self.mesh, axes=axes,
                allreduce_grad_dtype=self.allreduce_grad_dtype,
                host_members=self._hp_members,
                bucket_bytes=self.bucket_bytes,
                overlap=self.overlap,
                overlap_granularity=self.overlap_granularity,
                comm_dtype=self.comm_dtype,
            )
        except ValueError:
            CommunicatorBase._plane_count = count
            from .xla_ici import XlaIciCommunicator

            return XlaIciCommunicator(
                self.mesh, axes=axes,
                allreduce_grad_dtype=self.allreduce_grad_dtype,
                host_members=self._hp_members,
                bucket_bytes=self.bucket_bytes,
                overlap=self.overlap,
                overlap_granularity=self.overlap_granularity,
                comm_dtype=self.comm_dtype,
            )

    def split_devices(self, colors, keys=None) -> dict:
        """Device-plane ``MPI_Comm_split``: partition THIS communicator's
        DEVICES into sub-communicators by color.

        Single-controller form of the reference's arbitrary-subgroup
        split: one process speaks for all its devices, so instead of "each
        rank passes its color" the caller passes ``colors`` — a sequence
        of length ``device_size`` indexed by flat device rank (row-major
        over ``self.axes``, i.e. :meth:`device_for_rank` order) — and
        receives ``{color: communicator}`` covering every color at once.
        ``keys`` (same length) orders each subgroup (ties by old rank);
        ``None`` colors are MPI_UNDEFINED (device in no subgroup).  This
        expresses what the axis split cannot: "every 4th device", or a
        data-parallel subgroup inside one pipeline stage.

        Each sub-communicator's mesh is 1-D over its devices (axis
        ``intra`` — one collective leg, ICI-resident when the devices
        share a host).  A color whose devices span processes gets those
        processes as its host plane; a color with no devices on THIS
        process maps to ``None`` (MPI_COMM_NULL).
        """
        n = self.device_size
        colors = list(colors)
        if len(colors) != n:
            raise ValueError(
                f"colors must have length device_size={n}, got {len(colors)}"
            )
        keys = list(keys) if keys is not None else [0] * n
        if len(keys) != n:
            raise ValueError(
                f"keys must have length device_size={n}, got {len(keys)}"
            )
        groups: dict = {}
        for r in range(n):
            if colors[r] is None:
                continue
            groups.setdefault(colors[r], []).append(
                (keys[r], r, self.device_for_rank(r))
            )
        from .xla_ici import XlaIciCommunicator

        out: dict = {}
        # Deterministic construction order (SPMD).  Colors are unrestricted
        # by the API — mixed types must not raise sorted()'s unordered-types
        # TypeError, and the key must be identical on EVERY process (a
        # repr()-based key would embed id() for default-repr objects and
        # desynchronize plane ordinals across ranks).  Each group's lowest
        # member flat-rank is total, collision-free, and process-invariant.
        for c in sorted(
            groups, key=lambda c: min(r for _k, r, _d in groups[c])
        ):
            lst = sorted(groups[c], key=lambda t: (t[0], t[1]))
            devs = [d for _k, _r, d in lst]
            procs = sorted({d.process_index for d in devs})
            if jax.process_index() not in procs:
                # MPI_COMM_NULL for this process — but keep the plane
                # ordinal advancing in lockstep with constructing ranks.
                CommunicatorBase._plane_count += 1
                out[c] = None
                continue
            submesh = Mesh(
                np.array(devs, dtype=object), (mesh_utils.AXIS_INTRA,)
            )
            out[c] = XlaIciCommunicator(
                submesh,
                allreduce_grad_dtype=self.allreduce_grad_dtype,
                host_members=procs,
                bucket_bytes=self.bucket_bytes,
                overlap=self.overlap,
                overlap_granularity=self.overlap_granularity,
                comm_dtype=self.comm_dtype,
            )
        return out

    def _split_color(self, color, key: int):
        """Process-plane MPI_Comm_split.  A collective over THIS
        communicator: every member must call it (SPMD), colors partition
        the members, keys order the subgroup (ties by old rank)."""
        trips = self.allgather_obj(
            (None if color is None else int(color), int(key), self.rank)
        )
        if color is None:
            # MPI_UNDEFINED: no communicator — but the plane ordinal must
            # still advance in lockstep with the processes that DO
            # construct one, or every later communicator's namespace
            # diverges across processes.
            CommunicatorBase._plane_count += 1
            return None
        mine = sorted(
            (k, r) for c, k, r in trips if c == int(color)
        )
        sub_ranks = [r for _k, r in mine]  # ranks WITHIN this comm
        # Translate to global process indices (wire identities).
        to_global = (
            (lambda r: self._hp_members[r])
            if self._hp_members is not None
            else (lambda r: r)
        )
        members = [to_global(r) for r in sub_ranks]
        # Sub-mesh: the member processes' devices from THIS mesh, one
        # inter row per member (ordered by subgroup rank), intra = each
        # process's local devices in mesh order.
        if len(members) == self.size and members == [
            to_global(r) for r in range(self.size)
        ]:
            submesh = self.mesh  # whole group, original order
        else:
            rows = []
            mesh_devs = list(self.mesh.devices.flat)
            for g in members:
                row = [d for d in mesh_devs if d.process_index == g]
                rows.append(row)
            n_local = len(rows[0])
            if any(len(r) != n_local for r in rows):
                raise ValueError(
                    "split(color) needs equal local device counts across "
                    f"members; got {[len(r) for r in rows]}"
                )
            submesh = Mesh(
                np.array(rows, dtype=object),
                (mesh_utils.AXIS_INTER, mesh_utils.AXIS_INTRA),
            )
        from .xla_ici import XlaIciCommunicator

        cls = type(self)
        # Snapshot the plane ordinal: a variant whose constraints the
        # subgroup shape cannot satisfy may raise AFTER incrementing it,
        # which would desynchronize this process's ordinals from the
        # color=None processes that advanced exactly once.
        count = CommunicatorBase._plane_count
        try:
            return cls(
                submesh,
                allreduce_grad_dtype=self.allreduce_grad_dtype,
                host_members=members,
                bucket_bytes=self.bucket_bytes,
                overlap=self.overlap,
                overlap_granularity=self.overlap_granularity,
                comm_dtype=self.comm_dtype,
            )
        except ValueError:
            CommunicatorBase._plane_count = count
            # Variant constraints (e.g. SingleHostCommunicator) that the
            # subgroup shape cannot satisfy degrade to the flat backend.
            return XlaIciCommunicator(
                submesh,
                allreduce_grad_dtype=self.allreduce_grad_dtype,
                host_members=members,
                bucket_bytes=self.bucket_bytes,
                overlap=self.overlap,
                overlap_granularity=self.overlap_granularity,
                comm_dtype=self.comm_dtype,
            )

    def __repr__(self):
        return (
            f"<{type(self).__name__} axes={self.axes} "
            f"devices={self.device_size} hosts={self.size}>"
        )
