"""Gradient packing — the flat-buffer fusion that made ``pure_nccl`` fast.

Reference lineage: REF:chainermn/communicators/_memory_utility.py
(``pack_params``/``unpack_params``) packed every gradient into one
contiguous GPU buffer so the backend issued ONE ``ncclAllReduce`` instead
of one per parameter.  PyTorch DDP generalized the same idea into capped
*buckets* (Li et al., VLDB 2020: "PyTorch Distributed") so the first
buckets can start reducing while later gradients are still materializing.

Two utilities live here:

* :func:`pack_tree` — the single-buffer flatten/concat the ``flat``/
  ``xla_ici`` communicator and the ZeRO flat-master paths in
  :mod:`chainermn_tpu.optimizers` share (one source of truth for the
  flatten order and the unpack arithmetic).
* :class:`GradPacker` — the bucketed form every communicator's
  ``allreduce_grad`` uses by default: the gradient pytree is split into
  contiguous per-dtype buckets capped at ``bucket_bytes``, each padded to
  a power-of-two element count (collective-friendly sizes), and the
  communicator's characteristic allreduce runs once per bucket —
  O(n_buckets) collectives instead of O(n_leaves), with a lossless
  unpack (pure slicing, bit-exact).

Padding note: a bucket whose next power of two would overshoot the
``bucket_bytes`` cap (a single oversize leaf, or a near-full bucket) is
padded to a multiple of 128 elements instead — pow2-padding there could
waste up to 2x wire for no latency win.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Default bucket cap: large enough that a bucket is one fat collective,
#: small enough that the first bucket launches under the tail of the
#: backward pass.  The benchmark's dp4 cell pins and measures it
#: (``tests/test_packing.py::test_default_communicator_is_the_measured_one``);
#: no other size has been measured on the chip.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024

#: Environment escape hatch: overrides an unset ``bucket_bytes`` on every
#: communicator.  ``0`` disables bucketing (the legacy per-leaf path).
ENV_BUCKET_BYTES = "CHAINERMN_TPU_BUCKET_BYTES"

#: Non-pow2 buckets align to the TPU lane width instead.
LANE_ELEMS = 128


def _np_dtype(d) -> np.dtype:
    """``np.dtype`` that also resolves names numpy itself does not know
    (``"bfloat16"`` needs the ml_dtypes scalar type jax registers)."""
    try:
        return np.dtype(d)
    except TypeError:
        return np.dtype(getattr(jnp, str(d)))


def pack_tree(tree, pad_to: int | None = None):
    """Flatten a pytree into (one 1-D buffer, unpack closure).

    The analogue of ``pack_params`` in
    REF:chainermn/communicators/_memory_utility.py — except XLA owns the
    copies, so this is a trace-time concatenation the compiler fuses with
    the collective rather than a runtime memcpy loop.  ``pad_to`` appends
    zeros up to that element count (the ZeRO paths' divisible-by-world
    padding); ``unpack`` slices leaves from the prefix, so padding never
    round-trips into the tree.
    """
    leaves, treedef = jax.tree.flatten(tree)
    flat = (
        jnp.concatenate([jnp.ravel(l) for l in leaves])
        if leaves else jnp.zeros((0,))
    )
    shapes = [l.shape for l in leaves]
    sizes = [l.size for l in leaves]
    if pad_to is not None:
        if pad_to < flat.size:
            raise ValueError(
                f"pad_to={pad_to} smaller than packed size {flat.size}"
            )
        if pad_to > flat.size:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad_to - flat.size,), flat.dtype)]
            )

    def unpack(buf):
        out, off = [], 0
        for shape, size in zip(shapes, sizes):
            out.append(jnp.reshape(buf[off : off + size], shape))
            off += size
        return jax.tree.unflatten(treedef, out)

    return flat, unpack


def _padded_elems(elems: int, cap_elems: int) -> int:
    """Bucket padding rule: next power of two when that stays within the
    cap, else the next multiple of :data:`LANE_ELEMS`."""
    if elems == 0:
        return 0
    p = 1 << (elems - 1).bit_length()
    if p <= cap_elems:
        return p
    return elems + (-elems) % LANE_ELEMS


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One contiguous single-dtype slab of the packed gradient."""

    dtype: Any                       # np.dtype
    leaf_indices: Tuple[int, ...]    # into the tree's flatten order
    elems: int                       # payload elements (sum of leaf sizes)
    padded_elems: int                # buffer length actually reduced

    @property
    def payload_bytes(self) -> int:
        return self.elems * self.dtype.itemsize

    @property
    def padded_bytes(self) -> int:
        return self.padded_elems * self.dtype.itemsize

    @property
    def quantizable(self) -> bool:
        """Whether a ``comm_dtype`` wire applies to this bucket: float
        buckets (including the ml_dtypes extension floats, bf16/fp8)
        quantize; integer buckets ride at full precision."""
        return bool(jnp.issubdtype(self.dtype, jnp.floating))

    def wire_bytes(self, wire_itemsize: int | None = None) -> int:
        """Bytes this bucket actually moves per collective: the padded
        buffer at the wire dtype's width when quantized (plus the f32
        amax scale, one word per bucket), the padded storage bytes
        otherwise."""
        if wire_itemsize is None or not self.quantizable:
            return self.padded_bytes
        return self.padded_elems * wire_itemsize + 4


class GradPacker:
    """Bucketed pack/unpack plan for one gradient pytree structure.

    The plan is computed from leaf metadata only (treedef + shapes +
    dtypes) and is deterministic: leaves are grouped by dtype (groups in
    first-appearance order, leaves within a group in flatten order) and
    greedily filled into buckets capped at ``bucket_bytes`` of payload.
    A bucket always takes at least one leaf, so a single leaf larger than
    the cap becomes its own oversize bucket rather than an error.

    ``pack`` concatenates each bucket's raveled leaves (plus zero
    padding) into one 1-D buffer per bucket; ``unpack`` slices them back
    out — pure layout moves, so ``unpack(pack(tree)) == tree`` bit-for-
    bit, and any elementwise-linear collective applied between the two
    (psum, psum-scatter/all-gather) commutes with the packing exactly.
    """

    def __init__(
        self,
        treedef,
        shapes: Sequence[tuple],
        dtypes: Sequence[Any],
        bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    ):
        if bucket_bytes <= 0:
            raise ValueError(
                f"bucket_bytes must be positive, got {bucket_bytes} "
                "(use the unbucketed path to disable bucketing)"
            )
        self.treedef = treedef
        self.shapes = [tuple(s) for s in shapes]
        self.dtypes = [_np_dtype(d) for d in dtypes]
        self.sizes = [int(np.prod(s, dtype=np.int64)) for s in self.shapes]
        self.bucket_bytes = int(bucket_bytes)

        groups: dict[np.dtype, list[int]] = {}
        for i, dt in enumerate(self.dtypes):
            groups.setdefault(dt, []).append(i)

        buckets: List[Bucket] = []
        for dt, idxs in groups.items():
            cap_elems = max(1, self.bucket_bytes // dt.itemsize)
            cur: list[int] = []
            cur_elems = 0
            for i in idxs:
                if cur and cur_elems + self.sizes[i] > cap_elems:
                    buckets.append(Bucket(
                        dt, tuple(cur), cur_elems,
                        _padded_elems(cur_elems, cap_elems),
                    ))
                    cur, cur_elems = [], 0
                cur.append(i)
                cur_elems += self.sizes[i]
            if cur:
                buckets.append(Bucket(
                    dt, tuple(cur), cur_elems,
                    _padded_elems(cur_elems, cap_elems),
                ))
        self.buckets: Tuple[Bucket, ...] = tuple(buckets)

    @classmethod
    def for_tree(cls, tree, bucket_bytes: int = DEFAULT_BUCKET_BYTES):
        leaves, treedef = jax.tree.flatten(tree)
        return cls(
            treedef,
            [l.shape for l in leaves],
            [l.dtype for l in leaves],
            bucket_bytes,
        )

    # -- plan introspection -------------------------------------------
    @property
    def n_leaves(self) -> int:
        return len(self.shapes)

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def payload_bytes(self) -> int:
        return sum(b.payload_bytes for b in self.buckets)

    @property
    def padded_bytes(self) -> int:
        return sum(b.padded_bytes for b in self.buckets)

    def wire_bytes(self, comm_dtype=None) -> int:
        """Total bytes one allreduce moves per rank: padded storage
        bytes at full precision, or each quantizable bucket at the
        resolved wire dtype's width (``comm_dtype``: a canonical name
        from :mod:`chainermn_tpu.communicators.quant`) — the number
        bench's A/B column reports against the bf16 baseline."""
        wire_itemsize = None
        if comm_dtype is not None:
            from . import quant

            wire_dt = quant.wire_dtype(comm_dtype)
            if wire_dt is not None:
                wire_itemsize = jnp.dtype(wire_dt).itemsize
        return sum(b.wire_bytes(wire_itemsize) for b in self.buckets)

    def describe(self, comm_dtype=None) -> dict:
        """JSON-friendly plan summary (what benches and the Reporter
        counters publish).  ``comm_dtype`` (canonical quant name) adds
        the low-precision wire accounting per bucket."""
        wire_itemsize = None
        if comm_dtype is not None:
            from . import quant

            wire_dt = quant.wire_dtype(comm_dtype)
            if wire_dt is not None:
                wire_itemsize = jnp.dtype(wire_dt).itemsize
        out = {
            "bucket_bytes": self.bucket_bytes,
            "n_leaves": self.n_leaves,
            "n_buckets": self.n_buckets,
            "payload_bytes": self.payload_bytes,
            "padded_bytes": self.padded_bytes,
            "buckets": [
                {
                    "dtype": b.dtype.name,
                    "n_leaves": len(b.leaf_indices),
                    "elems": b.elems,
                    "padded_elems": b.padded_elems,
                    "padded_bytes": b.padded_bytes,
                }
                for b in self.buckets
            ],
        }
        if wire_itemsize is not None:
            out["comm_dtype"] = comm_dtype
            out["wire_bytes"] = self.wire_bytes(comm_dtype)
            for spec, b in zip(out["buckets"], self.buckets):
                spec["quantized"] = b.quantizable
                spec["wire_bytes"] = b.wire_bytes(wire_itemsize)
        return out

    # -- pack / unpack ------------------------------------------------
    def _check_tree(self, tree):
        leaves, treedef = jax.tree.flatten(tree)
        if treedef != self.treedef:
            raise ValueError(
                f"tree structure {treedef} does not match the packing "
                f"plan's {self.treedef}"
            )
        for i, l in enumerate(leaves):
            if tuple(l.shape) != self.shapes[i] or _np_dtype(l.dtype) != self.dtypes[i]:
                raise ValueError(
                    f"leaf {i} is {l.shape}/{l.dtype}, plan expects "
                    f"{self.shapes[i]}/{self.dtypes[i]}"
                )
        return leaves

    def pack(self, tree) -> List[jax.Array]:
        """Pytree → one 1-D buffer per bucket (padded with zeros)."""
        leaves = self._check_tree(tree)
        return [self.pack_bucket(leaves, i) for i in range(self.n_buckets)]

    def pack_bucket(self, leaves: Sequence[jax.Array], index: int) -> jax.Array:
        """One bucket's buffer from the tree's flattened leaves.

        The per-bucket form of :meth:`pack` the overlapped emission
        schedule (:mod:`chainermn_tpu.communicators.overlap`) uses:
        packing bucket-by-bucket keeps each collective's dependence
        frontier at exactly its member leaves, so the compiler may start
        it while other leaves' gradients are still being produced.
        ``leaves`` must already be in the plan's flatten order (use
        :meth:`_check_tree` / ``jax.tree.flatten`` on the full tree).
        """
        b = self.buckets[index]
        parts = [jnp.ravel(leaves[i]) for i in b.leaf_indices]
        pad = b.padded_elems - b.elems
        if pad:
            parts.append(jnp.zeros((pad,), dtype=b.dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def whole_leaf(self, index: int):
        """The leaf that IS bucket ``index`` (one leaf, no padding), or
        ``None``: such a bucket can be exchanged in the leaf's own shape
        (:meth:`unpack` takes it back so) — raveling a tiled 2-D array is
        a copy on the chip."""
        b = self.buckets[index]
        if len(b.leaf_indices) == 1 and b.padded_elems == b.elems:
            return b.leaf_indices[0]
        return None

    def unpack(self, bufs: Sequence[jax.Array]):
        """Bucket buffers → pytree (inverse of :meth:`pack`; padding is
        discarded)."""
        if len(bufs) != self.n_buckets:
            raise ValueError(
                f"got {len(bufs)} buffers for {self.n_buckets} buckets"
            )
        out = [None] * self.n_leaves
        for b, buf in zip(self.buckets, bufs):
            if buf.size != b.padded_elems:
                raise ValueError(
                    f"buffer has {buf.size} elems, bucket expects "
                    f"{b.padded_elems}"
                )
            if buf.ndim != 1:  # a whole leaf, exchanged in its shape
                out[b.leaf_indices[0]] = buf
                continue
            off = 0
            for i in b.leaf_indices:
                out[i] = jnp.reshape(
                    buf[off : off + self.sizes[i]], self.shapes[i]
                )
                off += self.sizes[i]
        return jax.tree.unflatten(self.treedef, out)


def synthetic_grad_tree(
    n_leaves: int,
    total_bytes: int,
    dtypes: Sequence[Any] = ("float32", "bfloat16"),
) -> dict:
    """Deterministic mixed-shape / mixed-dtype gradient pytree.

    The shared shape-maker behind the ``allreduce_tree`` bench and the
    census golden test — one definition so their
    "64-leaf mixed-shape tree" is the same tree.  Leaf 0 is a scalar,
    every 5th leaf is 2-D, dtypes round-robin, and sizes follow a cycling
    weight so buckets straddle leaf boundaries.  Values are exact in
    bfloat16 (multiples of 1/32 below 8) so low-precision round trips
    stay bit-stable.
    """
    dts = [_np_dtype(d) for d in dtypes]
    weights = [(i % 7) + 1 for i in range(n_leaves)]
    wsum = sum(weights) or 1
    tree = {}
    for i in range(n_leaves):
        dt = dts[i % len(dts)]
        if i == 0:
            shape: tuple = ()
        else:
            elems = max(1, int(total_bytes * weights[i] / wsum) // dt.itemsize)
            if i % 5 == 0 and elems % 2 == 0:
                shape = (elems // 2, 2)
            else:
                shape = (elems,)
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        vals = (np.arange(size, dtype=np.float32) % 97) / 32.0 + (i % 13) / 8.0
        tree[f"leaf_{i:03d}"] = vals.reshape(shape).astype(dt)
    return tree
