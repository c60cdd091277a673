"""Flat single-collective communicator — the ``pure_nccl``/``flat`` analogue.

Reference lineage:

* REF:chainermn/communicators/flat_communicator.py — pack every gradient
  into ONE contiguous GPU buffer, one ``MPI_Allreduce`` over it, unpack.
* REF:chainermn/communicators/pure_nccl_communicator.py — same flat buffer
  but a single ``ncclAllReduce`` across all ranks on a dedicated stream,
  with an optional fp16 cast-pack (``allreduce_grad_dtype``).

TPU-native translation: flatten + concatenate the gradient pytree into one
1-D buffer and issue a single ``lax.psum`` over the whole mesh.  XLA lowers
this to one fused all-reduce riding ICI (and DCN for the ``inter`` axis hops
on multi-host meshes) — the same "one big collective amortizes latency"
strategy that made ``pure_nccl`` the reference's fastest backend, which is
why BASELINE.json maps it to the ``xla_ici`` name.  The optional
low-precision leg uses bfloat16 (TPU's native low-precision format) instead
of the reference's fp16.

There is no stream to manage, and on libtpu 0.0.34 nothing overlaps that
``psum`` either: it compiles to a synchronous ``all-reduce`` in the core's
instruction stream (an asynchronous one is folded back under every flag
set tried).  So where a train step has one backward pass
(``make_train_step``, ``overlap=True``, the default) its large float
buckets are reduced as two-way rings of ``lax.ppermute`` hops pinned
under that pass instead (``CommunicatorBase.mean_grads_under``,
:mod:`.ring`: collective-permutes stay asynchronous DMAs), in ring order
by the chips' coordinates.  ``allreduce_grad`` itself, small and integer
buckets, a world of one, the quantised wire and the eager emission keep
the ``psum`` below: a ring nothing pins is slower than it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import overlap, ring
from .base import CommunicatorBase

# The flatten/concat core now lives in packing.py (shared with the
# bucketed allreduce_grad path and the ZeRO flat-master buffers in
# chainermn_tpu.optimizers); this name stays as the import surface.
from .packing import pack_tree as pack


class XlaIciCommunicator(CommunicatorBase):
    name = "xla_ici"

    def _allreduce_impl(self, tree):
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return tree
        # Pack in a common dtype (cast already applied by allreduce_grad
        # when allreduce_grad_dtype is set; otherwise promote to the widest
        # leaf dtype so the single fused collective is well-typed).
        common = jnp.result_type(*[l.dtype for l in leaves])
        casted = jax.tree.map(
            lambda x: x if x.dtype == common else x.astype(common), tree
        )
        flat, unpack = pack(casted)
        flat = lax.psum(flat, self.axes) / self.device_size
        out = unpack(flat)
        return jax.tree.map(
            lambda x, ref: x if x.dtype == ref.dtype else x.astype(ref.dtype),
            out, tree,
        )


    def _rides_ring(self, bucket):
        return (
            self.device_size > 1
            and bucket.quantizable  # a float bucket
            and bucket.padded_bytes >= overlap.RING_MIN_BYTES
        )

    @functools.cached_property
    def _ring_order(self):
        return ring.ring_order(self.mesh, self.axes)

    def _ring_steps(self, buf):
        return ring.ring_steps(buf, self.axes, self._ring_order)


# ``flat`` is the CUDA-aware-MPI spelling of the same algorithm in the
# reference; expose it as an alias class so create_communicator('flat')
# resolves (SURVEY §2.1).
class FlatCommunicator(XlaIciCommunicator):
    name = "flat"
