"""A bucket's mean as a two-way ring of ``lax.ppermute`` hops.

libtpu 0.0.34 compiles ``lax.psum`` to a synchronous ``all-reduce`` in the
core's own instruction stream (see :mod:`.overlap`); a
``collective-permute`` is a DMA and stays a ``-start`` / ``-done`` pair
the scheduler lays other work between.  So the mean of one packed bucket
over ``n`` devices is written here as a ring: reduce-scatter in ``n - 1``
hops (send a partial sum on, add the one that arrives to the next local
chunk), the ``1 / n`` folded into the last add, then all-gather in
``n - 1`` hops.  Half of every chunk travels each way round, as the native
algorithm's two colours do.

Every element is reduced to its final value on exactly ONE device and
copied from there, so all devices end bit-identical.  The partial sums
are carried from hop to hop as arrays of their own (a chunk's sum is what
the next hop sends), so the only copies round the hops are the first
chunk read out of the bucket and the gathered pieces written back over
it.  (Laying the pieces out by a ``lax.switch`` over the ring position,
each branch a concatenation at constant offsets, writes faster than
``dynamic_update_slice`` at traced offsets does — 415 against 419 ms a
step in the four-chip cell — and costs 54 MB of generated code, 12 s of
compilation and 1.7 s at every warm start of the program: not taken.)
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import mesh_utils


#: A piece (half a chunk: what one hop carries) is a whole number of the
#: tiles a 1-D array is laid out in on the chip, so that cutting it out
#: of the bucket and writing it back are plain copies.  A packed bucket
#: stays 1-D throughout: an ``(n, 2, m)`` view is tiled over its last two
#: dimensions and every piece then costs a relayout loop.
PIECE_ALIGN_1D = 1024


def ring_order(mesh, axes: Sequence[str]) -> Tuple[int, ...]:
    """Flat ranks (row-major over ``axes``) in ring order.

    Neighbours in the ring are neighbours on the chip's interconnect
    where the devices say where they are (``device.coords`` on TPU): on a
    2x2 the ring 0 -> 1 -> 3 -> 2 uses four physical links, mesh order
    crosses a diagonal twice.  Devices without coordinates (CPU), more
    than one device a coordinate, or a grid no closed walk covers keep
    mesh order.
    """
    names = list(mesh.axis_names)
    grid = np.moveaxis(
        mesh.devices, [names.index(a) for a in axes], range(len(axes)))
    devices = list(grid.reshape(
        (mesh_utils.axes_size(mesh, axes), -1))[:, 0])
    n = len(devices)
    coords = [getattr(d, "coords", None) for d in devices]
    if n < 4 or None in coords or len(set(map(tuple, coords))) < n:
        return tuple(range(n))
    near = [[j for j in range(n) if sum(
        abs(a - b) for a, b in zip(coords[i], coords[j])) == 1]
        for i in range(n)]

    def walk(path):
        if len(path) == n:
            return path if path[0] in near[path[-1]] else None
        for j in near[path[-1]]:
            if j not in path:
                found = walk(path + [j])
                if found:
                    return found
        return None

    # The search is exponential in the worst case: one host's chips only.
    found = walk([0]) if n <= 16 else None
    return tuple(found) if found else tuple(range(n))


def ring_hops(n: int) -> int:
    """Collective-permutes one bucket's ring issues: reduce-scatter and
    all-gather, both ways round."""
    return 4 * (n - 1)


def piece_rows(shape, n: int) -> int:
    """Rows of axis 0 one hop carries for a bucket of ``shape`` over ``n``
    devices (0: too few for a ring): a piece is a whole number of the
    tiles the array is laid out in (1024 elements of a 1-D array, 8 rows
    of a 2-D one; the leading axis of a longer shape is not tiled), so
    that cutting it out and writing it back move one contiguous block."""
    align = {1: PIECE_ALIGN_1D, 2: 8}.get(len(shape), 1)
    return shape[0] // (2 * n * align) * align


def ring_steps(buf, axes: Sequence[str], order: Sequence[int]):
    """The ring over ``buf`` as a generator (call inside ``shard_map``;
    ring positions -> flat ranks in ``order``): it yields the two pieces a
    hop has just put in flight, one each way round, and goes on with what
    is sent back in their place — the same arrays, or those arrays tied
    to the point of the program they must have landed by
    (:func:`.overlap.walk_with_exchange`).  It returns the mean.

    ``buf`` is a packed 1-D bucket or a gradient leaf in its own shape
    (no ravel: a 2-D leaf raveled changes its tiling, a copy); either way
    it is cut along axis 0.
    """
    n = len(order)
    axes = tuple(axes)
    size = buf.shape[0]
    m = piece_rows(buf.shape, n)
    tail = size - 2 * n * m
    if m == 0:
        return _mean(lax.psum(buf, axes), n)
    clockwise = [(order[i], order[(i + 1) % n]) for i in range(n)]
    perms = (clockwise, [(d, s) for s, d in clockwise])
    # One way round a device stands at ring position ``pos``; the other
    # way round the same hops read as a ring in which it stands at
    # ``-pos``.  Hop s sends the sum of chunk p - s so far and adds what
    # arrives to chunk p - s - 1; after n - 1 hops chunk p + 1 is whole
    # here.  Where each (way, hop)'s piece starts is looked up by the
    # device's position in one table.
    place = np.empty(n, np.int32)
    place[list(order)] = np.arange(n, dtype=np.int32)
    starts = _starts(mesh_utils.flat_rank(axes), place=tuple(place), m=m)

    def hop(pieces):
        return [lax.ppermute(piece, axes, perm)
                for piece, perm in zip(pieces, perms)]

    accs = _cut(buf, starts, None, m=m, s=0)
    for s in range(1, n):
        accs = _cut(buf, starts, (yield hop(accs)), m=m, s=s,
                    scale=n if s == n - 1 else 0)
    # Chunk p + 1 is whole here; hop k of the all-gather brings chunk
    # p + 1 - k, and each piece is written over its place in the bucket.
    out = _write(buf, starts, accs, s=n - 1)
    for k in range(1, n):
        accs = yield hop(accs)
        out = _write(out, starts, accs, s=k - 1)
    if tail:
        # What is left of a bucket that is no whole number of pieces is
        # too small for hops of its own.
        out = lax.dynamic_update_slice_in_dim(out, _mean(lax.psum(
            lax.slice_in_dim(buf, size - tail, size), axes), n),
            size - tail, axis=0)
    return out


# The ring's local work between two hops, each a jitted function of its
# own: a step holds some fifty rings of a handful of shapes, traced anew
# at every start of the program, and a jitted piece is traced and lowered
# once a shape (the compiler inlines the calls).


@functools.partial(jax.jit, static_argnames=("place", "m"))
def _starts(rank, *, place, m):
    """Where each (way, hop)'s piece starts in the bucket, ``(2, n)``,
    on the device of flat rank ``rank``."""
    n = len(place)
    at = np.arange(n)
    stands = np.stack([at, (n - at) % n], 1)
    table = (2 * ((stands[:, :, None] - at) % n)
             + np.arange(2)[None, :, None]) * m
    return jnp.asarray(table, jnp.int32)[jnp.asarray(place)[rank]]


@functools.partial(jax.jit, static_argnames=("m", "s", "scale"))
def _cut(buf, starts, landed, *, m, s, scale=0):
    """Hop ``s``'s two pieces of ``buf`` (one each way), plus what has
    just landed, over ``scale`` at the last hop."""
    pieces = [lax.dynamic_slice_in_dim(buf, starts[half, s], m, axis=0)
              for half in (0, 1)]
    if landed is not None:
        pieces = [lax.add(a, b) for a, b in zip(pieces, landed)]
    return [_mean(a, scale) for a in pieces] if scale else pieces


@functools.partial(jax.jit, static_argnames=("s",))
def _write(out, starts, pieces, *, s):
    """``pieces`` written over hop ``s``'s places in ``out``."""
    for half, piece in enumerate(pieces):
        out = lax.dynamic_update_slice_in_dim(
            out, piece, starts[half, s], axis=0)
    return out


def _mean(total, n: int):
    return lax.div(total, np.asarray(n, total.dtype))


def ring_mean(buf, axes: Sequence[str], order: Sequence[int]):
    """Mean of ``buf`` over the devices of ``axes`` with every hop left to
    the scheduler: the ring alone, as the probe and the tests time and
    check it (a step pins the hops: ``mean_grads_under``)."""
    steps, flying = ring_steps(buf, axes, order), None
    try:
        while True:
            flying = steps.send(flying)
    except StopIteration as done:
        return done.value
