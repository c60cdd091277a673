"""Flash attention as a Pallas TPU kernel.

Blockwise attention with online softmax: Q blocks stream over KV blocks
held in VMEM, accumulating unnormalized outputs with running max/denominator
— O(S) memory instead of O(S²), fp32 accumulation, MXU matmuls via
``jnp.dot(..., preferred_element_type=float32)``.  The same math as
``parallel.ring_attention`` — there the blocks live on *different chips*
and rotate over ICI; here they live in *HBM* and stream through VMEM.  A
sequence-parallel model composes both: ring outside, this kernel inside
each block pair.

Causal skipping: grid programs whose whole K block is in the future of the
whole Q block write nothing and skip the matmuls (``pl.when``), and their
index maps stop at the band, so such a step copies nothing either.  A
step has a fixed cost, so the blocks are as large as the scoped VMEM
takes (:func:`auto_block_size`): at S=2048 a head row is four steps, not
256.  Under a sliding window the grid itself is the band: the inner axis
is as long as the widest band is in tiles and the index maps start each
resident block's walk at its band's first tile, so no step outside the
band exists to be skipped.  Every live tile runs the one masked body;
two things are cut from it by what the device's own times say (PERF.md
§6, PR 50).  A square tile ON the causal diagonal holds a triangle, and
in the BACKWARD kernels, where a half keeps an edge of 512, it runs BY
HALVES (:func:`_by_halves`, :func:`_run_tiles`): three runs of the same
body on row and column slices of the same refs, two of them the triangle
again at half the edge and one unmasked, and the quarter that holds no
live pair is not computed — same grid, same blocks, same copies, three
quarters of the products and of the time (the forward's halves lose to
its rows' statistics and it keeps whole tiles).  And under a window a
row that has met no key is guarded by one select a row
(:func:`_reached`), not one an element.  All three backward kernels
enter their bodies the one way, so the one-pass backward and the two
kernels past its footprint rule run the same parts in the same order.
What a call was built with — blocks, tiles live / visited / copied a
head row and of the live ones those an edge of the mask cuts and those
halved (:func:`tile_census`) — goes to the telemetry sinks, the first
five in the kernels' scope path too.

Differentiable: a ``custom_vjp`` with an explicit FlashAttention-2-style
backward — the forward saves one fp32 log-sum-exp per row, and the
backward recomputes probabilities blockwise from it, so neither pass ever
materializes the S×S matrix: O(S) memory in both passes.  The backward is
ONE pass over the forward's grid (:func:`_flash_bwd_fused`, the kernel
and scope ``flash-bwd-dkv``): each live tile makes ``s``, the mask,
``p``, ``dp`` and ``ds`` once and from them all three gradients — five
products and one fetch of each operand — ``dq`` summed over a Q block's
K tiles in a ``(block_q, D)`` scratch, ``dk`` and ``dv`` summed into
float32 buffers that hold the KV row WHOLE in VMEM over the row's group
of query heads (:func:`bwd_resident_bytes`: 2 MiB at S = 2,048 and
D = 128, 16 at S = 16,384, 24 at scores 192 / values 128) and cast into
whole-row outputs once.  The rule is the shape's
(:func:`bwd_fused_vmem_bytes`): one pass where that footprint — the
streamed tile, the resident rows, the whole-row outputs twice — is within
:data:`VMEM_LIMIT_MAX`, else the two kernels it replaced
(:func:`_flash_bwd_pair`: ``flash-bwd-dq`` and ``flash-bwd-dkv``, each
recomputing ``s``, ``p`` and ``dp``, seven products a tile), which sum
the same terms in the same order — ring attention's long blocks, a 128k
row.  No argument chooses.  The tile edge is sized as before, against
the default scoped VMEM (:func:`auto_block_size`), the resident rows
counted beside it.  A traced call says which side it got:
``flash/bwd_fused`` and ``flash/bwd_resident_bytes`` gauges, a
``flash/bwd_fused_calls`` counter beside ``flash/calls``, the
``bwd_fused`` and ``bwd_resident_bytes`` fields of the ``flash_geometry``
row (:func:`_publish_geometry`).  Device times on a TPU v5e are in
PERF.md (§5 and §6, PR 25; the one pass against the two, PR 48).

Optional segment-id masks support packed-sequence training: tokens attend
only within their own segment, and padding rows produce zero output and
zero gradients in both passes.

Runs in interpreter mode off-TPU (tests run the same kernel code on the
CPU mesh).  Shapes the kernel does not cover (head_dim > 256 or unaligned
sequence lengths) go to plain XLA attention with a warning.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability import reporter as _reporter
from chainermn_tpu.observability import step_log as _step_log
from chainermn_tpu.observability.spans import (
    TILE_FIELDS,
    named_scope,
    telemetry_active,
    tiles_scope,
)

_NEG_INF = -1e30


def default_interpret() -> bool:
    """Whether the kernels run in Pallas interpret mode: everywhere but
    on a TPU.  The one selection, shared with ``parallel.ring_attention``;
    nothing else demotes a compiled kernel."""
    return jax.default_backend() != "tpu"


def _block_mask(shape, causal, q_start, k_start, qs_ref, ks_ref,
                window=None):
    """Combined (block_q, block_k) boolean mask for one grid tile — the
    causal triangle, the sliding-window band (query attends only its
    ``window`` most recent positions, itself included — Mistral-style
    local attention), AND segment-id equality (packed sequences attend
    only within their own segment).  None when nothing masks."""
    m = None
    if causal or window is not None:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        m = q_pos >= k_pos
        if window is not None:
            m = m & (q_pos - k_pos < window)
    if qs_ref is not None:
        seg = qs_ref[0] == ks_ref[0].reshape(1, -1)   # (bq,1) == (1,bk)
        m = seg if m is None else (m & seg)
    return m


def _band_live(causal, window, q_start, block_q, k_start, block_k):
    """Whole-block skip condition: does this (q block, k block) tile
    intersect the attention band at all?  Causal bound above (no k after
    the last query), window bound below (no k more than ``window - 1``
    positions before the first live query of the block)."""
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = run & (k_start + block_k - 1 >= q_start - (window - 1))
    return run


def _tile_cuts(causal, window, q_start, block_q, k_start, block_k):
    """``(diagonal, edge)`` of a LIVE tile of a rectangle or a band, from
    the numbers :func:`_band_live` reads "live" from: whether the causal
    diagonal cuts it (some key of the tile lies after some query) and
    whether the window's far edge does (some query lies ``window`` or
    more past some key).  A tile neither cuts is interior: every pair of
    it attends.  Python's False where the call has no such edge; a
    scalar each in a kernel, arrays in the census."""
    diagonal = edge = False
    if causal:
        diagonal = k_start + block_k - 1 > q_start
    if window is not None:
        edge = q_start + block_q - 1 - k_start >= window
    return diagonal, edge


def _by_halves(causal, window, block_q, block_k, segmented):
    """Whether a BACKWARD kernel runs the tiles on the causal diagonal BY
    HALVES (:func:`_run_tiles`).  A square tile's live diagonal tile
    starts at ``q_start == k_start`` (both are multiples of the one
    edge), so its upper rows reach the first half of its keys only and
    three quarters of it hold every live pair; under a window no
    narrower than the edge the window does not cut it.  By the device's
    own times (PERF.md §6, PR 50; ``benchmarks/flash_sweep.py --kinds``):
    the backward's parts are independent and their five products fill
    the matrix unit, so a 1024-edge tile by halves takes three quarters
    of its time (5.3 µs against 7.0 at D = 128), while 256-edge parts of
    a 512-edge tile gain nothing (3.24 against 3.27) — a half keeps an
    edge of 512; the FORWARD's parts each wait on their rows' running
    maximum and sum, and its halved 1024-edge tile takes 5.0 µs where
    the whole one takes 3.7, so the forward halves none.  A segment mask
    keeps the whole tile (its refs are not cut)."""
    half = block_q // 2
    return (causal and not segmented and block_q == block_k
            and half % 128 == 0 and half >= 512
            and (window is None or window >= block_q))


def _kv_live_range(iq, block_q, block_k, n_k, causal, window, xp=jnp):
    """``(first, last)`` K block that q block ``iq``'s band reaches — the
    same bounds as :func:`_band_live`, solved for the block index — each
    held inside ``[0, n_k - 1]``.  ``xp`` is ``jnp`` in an index map and
    ``numpy`` in the tile census."""
    lo, hi = 0, n_k - 1
    if causal:
        hi = xp.minimum(hi, (iq * block_q + block_q - 1) // block_k)
    if window is not None:
        lo = xp.clip((iq * block_q - (window - 1)) // block_k, 0, n_k - 1)
    return lo, hi


def _q_live_range(ik, block_q, block_k, n_q, causal, window, xp=jnp):
    """``(first, last)`` Q block whose band reaches k block ``ik`` (the
    dk/dv kernel streams the query side), inside ``[0, n_q - 1]``."""
    lo, hi = 0, n_q - 1
    if causal:
        lo = xp.minimum(n_q - 1, (ik * block_k) // block_q)
    if window is not None:
        hi = xp.minimum(
            hi, (ik * block_k + block_k - 1 + window - 1) // block_q
        )
    return lo, hi


def _banded(live_range, causal):
    """``clamp(outer, inner)`` for an index map of a call WITHOUT a
    window: the streamed operand's block index held inside the band of
    the resident one.  Pallas copies a block only when its index changes
    between consecutive grid steps, so a tile outside the causal bound
    (which ``pl.when`` skips anyway) moves no data.  Identity when
    nothing bands the attention.  A call with a window has no such
    steps: its grid is the band (:func:`_band_steps`) and its index map
    is :func:`_band_block`."""
    if not causal:
        return lambda outer, inner: inner

    def clamp(outer, inner):
        lo, hi = live_range(outer)
        return jnp.clip(inner, lo, hi)

    return clamp


def _band_steps(live_range, n_outer):
    """The inner grid axis of a call with a window: the most tiles the
    band of any resident block reaches — a static number, computed with
    numpy from the same bounds the index maps use."""
    lo, hi = live_range(np.arange(n_outer), xp=np)
    return int(np.max(hi - lo + 1))


def _band_block(live_range, outer, step, xp=jnp):
    """``(block, in_band)`` of band step ``step`` beside resident block
    ``outer``: the streamed operand's block ``lo + step`` while that lies
    in the band, else the band's last block again (a resident block near
    the sequence's start reaches fewer tiles than the widest one: the
    repeat copies nothing, and the kernels skip it by ``in_band`` so that
    no tile is summed twice)."""
    lo, hi = live_range(outer, xp=xp)
    return xp.minimum(lo + step, hi), lo + step <= hi


def _streamed_block(live_range, outer, step, window):
    """``(block, in_band)`` of the streamed operand at inner grid step
    ``step``, as a kernel reads it: the step itself without a window
    (the grid is the whole axis, :func:`_band_live` alone decides and
    ``in_band`` is None), :func:`_band_block` with one — the very block
    the index map named."""
    if window is None:
        return step, None
    return _band_block(live_range, outer, step)


def _streamed_axis(live_range, n_outer, n_inner, causal, window):
    """``(index_map, steps)`` of the streamed operand's grid axis beside
    a resident axis of ``n_outer`` blocks: without a window all
    ``n_inner`` blocks with the :func:`_banded` clamp, with one the
    band's :func:`_band_steps` and :func:`_band_block`."""
    if window is None:
        return _banded(live_range, causal), n_inner
    return (lambda outer, step: _band_block(live_range, outer, step)[0],
            _band_steps(live_range, n_outer))


def _live_ranges(Sq, Sk, block_q, block_k, causal, window):
    """``(kv_range, q_range)``: :func:`_kv_live_range` of a q block and
    :func:`_q_live_range` of a k block at this geometry, each a function
    of the resident block's index (and ``xp``) alone — what the index
    maps, the kernels and the census all read the band from."""
    geometry = dict(block_q=block_q, block_k=block_k, causal=causal,
                    window=window)
    return (functools.partial(_kv_live_range, n_k=Sk // block_k, **geometry),
            functools.partial(_q_live_range, n_q=Sq // block_q, **geometry))


def blockdiff_pairs(L, B):
    """(query, key) pairs one head row attends under the block-diffusion
    mask over ``[clean ; noisy]``: a clean row its own block and every
    block before, a noisy row every clean block before its own and its
    own noisy block — ``L (L + B) / 2 + L (L - B) / 2 + L B``."""
    return L * L + L * B


def _blockdiff_tiles(L, B, block_q, block_k):
    """``(live, full)`` boolean ``(2 L / block_q, 2 L / block_k)``: the
    tiles of the ``[clean ; noisy]`` rectangle in which the
    block-diffusion mask (:func:`blockdiff_mask`) lets any pair attend,
    and those among them in which it lets every pair (an interior tile:
    clean keys wholly before the queries' first block).  From the first
    and last block ``B`` a tile's rows and columns lie in."""
    n_q, n_k = L // block_q, L // block_k
    iq = np.arange(2 * n_q)[:, None]
    ik = np.arange(2 * n_k)[None, :]
    q_noisy, k_noisy = iq >= n_q, ik >= n_k
    q0 = (iq % n_q) * block_q // B
    q1 = ((iq % n_q) * block_q + block_q - 1) // B
    k0 = (ik % n_k) * block_k // B
    k1 = ((ik % n_k) * block_k + block_k - 1) // B
    live = np.where(k_noisy, q_noisy & (k0 <= q1) & (q0 <= k1),
                    k0 <= q1 - q_noisy)
    return live, ~k_noisy & (k1 <= q0 - q_noisy)


@functools.lru_cache(maxsize=None)
def _blockdiff_walk(L, B, block_q, block_k, streamed):
    """The live tiles of the block-diffusion mask as the three int32
    tables a kernel's grid walks (scalar-prefetched: the index maps and
    the kernel read step ``t``'s row): the q tile, the k tile, and flags
    — 1 the first tile of its resident block, 2 the last, 4 a tile the
    mask cuts (an interior tile pays no compare).  ``streamed="kv"``:
    q-major, a q tile's clean K tiles in order and then, for a noisy
    tile, its diagonal noisy ones (forward and dq); ``"q"``: k-major, the
    transposed statement (dk/dv).  Dead tiles are in no table: none is
    visited.  (A pure function of its arguments, asked for by the census
    and by each kernel's wrapper at every trace: cached.)"""
    live, full = _blockdiff_tiles(L, B, block_q, block_k)
    if streamed == "q":
        tk, tq = np.nonzero(live.T)
        resident = tk
    else:
        tq, tk = np.nonzero(live)
        resident = tq
    edge = np.flatnonzero(np.diff(resident)) + 1
    flags = np.where(full[tq, tk], 0, 4)
    flags[np.r_[0, edge]] |= 1
    flags[np.r_[edge - 1, len(flags) - 1]] |= 2
    return tuple(np.asarray(a, np.int32) for a in (tq, tk, flags))


def _blockdiff_step(tables, t, L, B, block_q, block_k):
    """What a kernel under the block-diffusion mask reads of its tables
    at walk step ``t``: ``(iq, ik, first, last, cut, mask_of)`` —
    ``cut`` whether the mask cuts the tile and ``mask_of(shape)`` its
    (block_q, block_k) boolean then.  The rows of a tile lie on one side
    (``block`` divides ``L``), so the side is a scalar and the mask one
    pair of comparisons between the queries' blocks, a column, and the
    keys', a row: clean keys ``blk(k) <= blk(q) - [q noisy]``, noisy keys
    ``blk(k) == blk(q)``."""
    tq, tk, fl = tables
    iq, ik, flags = tq[t], tk[t], fl[t]
    n_q, n_k = L // block_q, L // block_k

    def blocks(tile, n, block, shape, axis):
        pos = (tile % n) * block + jax.lax.broadcasted_iota(
            jnp.int32, shape, axis)
        if B & (B - 1) == 0:
            return jax.lax.shift_right_logical(
                pos, jnp.int32(B.bit_length() - 1))
        return jax.lax.div(pos, jnp.int32(B))

    def mask_of(shape):
        qb = blocks(iq, n_q, block_q, (shape[0], 1), 0)
        kb = blocks(ik, n_k, block_k, (1, shape[1]), 1)
        q_noisy = (iq >= n_q).astype(jnp.int32)
        k_noisy = (ik >= n_k).astype(jnp.int32)
        return (kb >= qb * k_noisy) & (kb <= qb - q_noisy * (1 - k_noisy))

    return (iq, ik, (flags & 1) != 0, (flags & 2) != 0, (flags & 4) != 0,
            mask_of)


def blockdiff_mask(L, B):
    """The block-diffusion mask itself, ``(2 L, 2 L)`` boolean over the
    row layout ``[clean ; noisy]``, by comparison (the dense path's and
    the tests'): with ``blk(i) = (i mod L) // B``, clean q and clean k
    ``blk(k) <= blk(q)``; noisy q and clean k ``blk(k) < blk(q)``; noisy
    q and noisy k ``blk(k) == blk(q)``; clean q and noisy k never."""
    pos = jnp.arange(2 * L)
    noisy, blk = pos >= L, (pos % L) // B
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(kn, qn & (kb == qb), kb <= qb - qn)


def tile_census(Sq, Sk, block_q, block_k, causal, window, blockdiff=None,
                segmented=False):
    """What one head row's grid does at this geometry — ``{"fwd", "dq",
    "dkv"}``, each ``{block_q, block_k, live, visited, copied, cut,
    halved}``: tiles that intersect the band (they run the matmuls), grid
    steps, fetches of the streamed operand (K/V in forward and dq, the
    query side in dk/dv: a step whose block index repeats the previous
    step's copies nothing), of the live tiles those an edge of the mask
    cuts (:func:`_tile_cuts`; the others are interior: their mask is all
    true) and of the cut ones those a backward kernel runs by halves
    (:func:`_by_halves`, which a call with segment ids — ``segmented`` —
    never does; a halved tile is one live, visited tile).
    Computed with the index maps' own bounds: without a window
    ``visited`` is the whole ``n_q x n_k`` rectangle, the steps past the
    causal bound entered and skipped; with one it is the band grid's
    ``n_q x`` :func:`_band_steps` (``n_k x`` the query side's in dk/dv),
    which is ``live`` and the few repeats of the blocks near the
    sequence's start.  Under the block-diffusion mask
    (``blockdiff`` = ``(L, B)``) the grid is the list of live tiles
    itself (:func:`_blockdiff_walk`): ``visited`` is ``live``, ``cut``
    the tiles its flags mark, none halved."""

    def fetches(steps):
        return 1 + int(np.count_nonzero(np.diff(steps.ravel())))

    if blockdiff is not None:
        _, tk, flags = _blockdiff_walk(*blockdiff, block_q, block_k, "kv")
        tq, _, _ = _blockdiff_walk(*blockdiff, block_q, block_k, "q")
        base = {"block_q": block_q, "block_k": block_k, "live": len(tk),
                "visited": len(tk),
                "cut": int(np.count_nonzero(flags & 4)), "halved": 0}
        kv = dict(base, copied=fetches(tk))
        return {"fwd": kv, "dq": kv, "dkv": dict(base, copied=fetches(tq))}
    n_q, n_k = Sq // block_q, Sk // block_k
    iq = np.arange(n_q)[:, None]
    ik = np.arange(n_k)[None, :]
    kv_range, q_range = _live_ranges(Sq, Sk, block_q, block_k, causal,
                                     window)
    tile = (causal, window, iq * block_q, block_q, ik * block_k, block_k)
    live, diagonal, edge = (
        np.broadcast_to(np.asarray(a, bool), (n_q, n_k))
        for a in (_band_live(*tile), *_tile_cuts(*tile)))
    halved = 0
    if _by_halves(causal, window, block_q, block_k, segmented):
        halved = int((live & diagonal).sum())
    if window is None:
        kv_steps = np.broadcast_to(np.clip(ik, *kv_range(iq, xp=np)),
                                   (n_q, n_k))
        q_steps = np.broadcast_to(np.clip(iq, *q_range(ik, xp=np)),
                                  (n_q, n_k)).T
    else:
        kv_steps, _ = _band_block(
            kv_range, iq, np.arange(_band_steps(kv_range, n_q))[None, :],
            xp=np)
        q_steps, _ = _band_block(
            q_range, ik.T, np.arange(_band_steps(q_range, n_k))[None, :],
            xp=np)
    base = {"block_q": block_q, "block_k": block_k, "live": int(live.sum()),
            "cut": int((live & (diagonal | edge)).sum())}
    kv = dict(base, visited=kv_steps.size, copied=fetches(kv_steps))
    return {"fwd": dict(kv, halved=0), "dq": dict(kv, halved=halved),
            "dkv": dict(base, visited=q_steps.size, copied=fetches(q_steps),
                        halved=halved)}


def _tiles_scope(tiles):
    """:func:`tiles_scope` of one kernel's census: the five fields the
    scope path spells (``spans.TILE_FIELDS``)."""
    return tiles_scope(**{field: tiles[field] for field in TILE_FIELDS})


def _walked(refs, blockdiff, block_q, block_k):
    """``(walk, refs)`` of a kernel's operands: under the
    block-diffusion mask the three scalar-prefetched tables lead them,
    and ``walk`` is :func:`_blockdiff_step` at this program's step of the
    flattened tile axis; else None and the operands as they are."""
    if blockdiff is None:
        return None, refs
    return _blockdiff_step(refs[:3], pl.program_id(1), *blockdiff,
                           block_q, block_k), refs[3:]


def _edges(walk, step, steps):
    """``(first, last)``, each a function of nothing: whether this
    program opens or closes its resident block's accumulation — the ends
    of the inner grid axis, or what the walk's flags say.  (Functions,
    so that a call without the mask traces each comparison where it
    always did: ``tests/golden/flash_no_window.json`` holds the order of
    a kernel's equations.)"""
    if walk is None:
        return (lambda: step == 0), (lambda: step == steps - 1)
    return (lambda: walk[2]), (lambda: walk[3])


def _band_run(causal, window, q_start, block_q, k_start, block_k, in_band):
    """Whole-block skip of a call whose grid is a rectangle or a band: K
    block past the causal bound OR entirely before the sliding window's
    reach (a window's grid is its band: only the repeats of a short
    band's last block are left to skip)."""
    run = _band_live(causal, window, q_start, block_q, k_start, block_k)
    if window is not None:
        run = run & in_band
    return run


#: A tile's rows, or its columns, whole.
_WHOLE = slice(None)


def _reached(stat):
    """A row statistic — the running maximum in the forward, the saved
    log-sum-exp in the backward — as the exponent's shift under a
    window: 0 in place of ``_NEG_INF`` for a row that has met no key
    (its window starts past the tile, or past every key), so that
    ``exp(s - shift)`` of its masked scores is ``exp(_NEG_INF)``, 0, and
    not ``exp(0)`` — one select a ROW where the bodies with a segment
    mask spend one an element (``p = where(mask, p, 0)``: 1.0 µs of a
    1024-edge forward tile, PERF.md §6, PR 50).  The bits of every other
    row are what they were."""
    return jnp.where(stat < 0.5 * _NEG_INF, 0.0, stat)


def _halves(walk, causal, window, segmented, q_start, block_q, k_start,
            block_k):
    """What a backward kernel hands :func:`_run_tiles` as ``halves``:
    None, or where :func:`_by_halves` holds ``(on_diagonal, half)`` —
    whether the causal diagonal cuts this tile (:func:`_tile_cuts`) and
    half the tile's edge."""
    if walk is not None or not _by_halves(causal, window, block_q, block_k,
                                          segmented):
        return None
    on_diagonal, _ = _tile_cuts(causal, window, q_start, block_q, k_start,
                                block_k)
    return on_diagonal, block_q // 2


def _run_tiles(tile, walk, run, mask_of, halves=None):
    """Enter a kernel's tile body: where the grid is a rectangle or a
    band, when ``run()`` says the tile is live, under ``mask_of``; where
    it is the walk of the block-diffusion mask's live tiles every step
    is live, and a tile the mask cuts runs the body with the mask, an
    interior one the body without.

    Under ``halves`` (:func:`_halves`; the body is then ``tile(mask_of,
    rows, cols)``, the part ``rows x cols`` of the tile) a live tile on
    the diagonal runs BY HALVES: its upper rows against its first keys
    (the diagonal again, at half the edge), its lower rows against the
    first keys without a mask and against the last under the diagonal,
    in that order.  The quarter left out holds no live pair."""
    if walk is not None:
        *_, cut, cut_mask = walk
        pl.when(cut)(lambda: tile(cut_mask))
        pl.when(jnp.logical_not(cut))(lambda: tile(lambda shape: None))
    elif halves is None:
        pl.when(run())(lambda: tile(mask_of))
    else:
        on_diagonal, half = halves
        first, last = slice(0, half), slice(half, 2 * half)

        def triangle(shape):    # (the part's rows and keys start level)
            return _block_mask(shape, True, 0, 0, None, None)

        live = run()

        @pl.when(live & on_diagonal)
        def _():
            tile(triangle, first, first)
            tile(lambda shape: None, last, first)
            tile(triangle, last, last)

        pl.when(live & jnp.logical_not(on_diagonal))(lambda: tile(mask_of))


def _attn_kernel(
    *refs,
    scale: float, causal: bool, segmented: bool, block_q: int, block_k: int,
    kv_range, window=None, blockdiff=None,
):
    walk, refs = _walked(refs, blockdiff, block_q, block_k)
    if segmented:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qs_ref = ks_ref = None
    j = n_j = None
    if walk is None:
        iq = pl.program_id(1)
        j = pl.program_id(2)
        n_j = pl.num_programs(2)
    first, last = _edges(walk, j, n_j)

    @pl.when(first())
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    if walk is None:
        q_start = iq * block_q
        ik, in_band = _streamed_block(kv_range, iq, j, window)
        k_start = ik * block_k

    def tile(mask_of):
        # MXU-native matmuls: operands stay in their input dtype (bf16 on
        # the training path — one MXU pass) with fp32 accumulation via
        # preferred_element_type; only the softmax runs in fp32.
        q = q_ref[0]                              # (block_q, D)
        k = k_ref[0]                              # (block_k, D)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        mask = mask_of(s.shape)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, 0]
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_blk)
        # A row fully masked in this block has m_new == _NEG_INF == its
        # masked scores, making exp(s - m_new) = 1 where it must be 0,
        # so that padding rows accumulate nothing.  (Causal-only running
        # blocks always have >= 1 valid entry per row; a low-k windowed
        # block is admitted because the q block's EARLY rows still reach
        # it, while its LATE rows — whose window starts later — can be
        # fully masked on this, their first visited block, so the window
        # path needs a guard too.  Under the block-diffusion mask a
        # noisy row of the first block sees no clean key at all, and its
        # first tile is a clean one.)  With a segment mask, or under the
        # block-diffusion mask, the entries are zeroed; under a window
        # alone such a row is shifted by 0 (:func:`_reached`).
        zeroed = segmented or blockdiff is not None
        shift = m_new
        if window is not None and not zeroed:
            shift = _reached(m_new)
        p = jnp.exp(s - shift[:, None])
        if mask is not None and zeroed:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)

        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[:, 0] = m_new

    _run_tiles(tile, walk, lambda: _band_run(
        causal, window, q_start, block_q, k_start, block_k, in_band),
        lambda shape: _block_mask(shape, causal, q_start, k_start, qs_ref,
                                  ks_ref, window))

    @pl.when(last())
    def _():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[:] / denom[:, None]).astype(o_ref.dtype)
        # Row log-sum-exp — the single per-row statistic the backward needs
        # to recompute exact probabilities blockwise.
        lse_ref[0] = (m_ref[:, 0] + jnp.log(denom))[:, None]


#: What Mosaic lets one kernel use of a v5e core's 128 MiB VMEM unasked
#: (its default scoped limit on this toolchain) — the budget the static
#: default geometry is chosen within — and the most this module asks for
#: when a pinned geometry needs more.
VMEM_SCOPED_DEFAULT = 16 * 1024 * 1024
VMEM_LIMIT_MAX = 96 * 1024 * 1024


def _lanes(d: int) -> int:
    """Mosaic pads the lane (last) dim to a multiple of 128."""
    return max(128, -(-int(d) // 128) * 128)


def bwd_resident_bytes(rows: int, D: int, D_v: int) -> int:
    """What the one-pass backward keeps in VMEM from a KV row's first
    query head to its last: the row's ``dk`` and ``dv`` in float32."""
    return rows * (_lanes(D) + _lanes(D_v)) * 4


def flash_vmem_bytes(block_q: int, block_k: int, D: int, itemsize: int,
                     which: str = "fwd", segmented: bool = False,
                     D_v: Optional[int] = None,
                     rows: Optional[int] = None) -> int:
    """VMEM bytes one grid program of the flash kernels holds, for heads
    whose queries and keys are ``D`` wide and whose values — and so the
    output, its cotangent and ``dv`` — are ``D_v`` wide (None: ``D``).

    Streamed blocks and outputs count twice (the pipeline fetches tile
    ``t+1`` while ``t`` computes), scratch once, a ``(block, 1)`` column
    (lse, delta, the running max and sum, segment ids) as the 128 lanes
    it is padded to, and the ``(block_q, block_k)`` fp32 intermediates of
    the body (``s``, ``p``, the mask; ``dp``, ``ds`` and the transposes
    in the backward) as the share of them Mosaic keeps whole: two tiles,
    three with a segment mask.  That share is read off the compiler —
    the least scoped limit under which each kernel compiles for a v5e at
    128 head rows, over D 128-256, bf16 and fp32, with and without
    segment ids (PERF.md §6, PR 25): the rule never picks a tile that
    fails to compile inside the default there.  ``which``: ``"fwd"``,
    ``"bwd"`` for the larger of the dq and dk/dv kernels (two
    ``pallas_call``s at the same blocks: what the tile edge is sized
    against), or ``"bwd_fused"`` for the one-pass backward over KV rows
    ``rows`` long: the streamed operands and ``dq`` as in the dq kernel,
    and beside them the RESIDENT rows — the float32 ``dk`` and ``dv`` of
    the whole KV row in scratch, ``rows x (lanes(D) + lanes(D_v)) x 4``
    bytes (2 MiB at S = 2,048 and D = 128, 16 at S = 16,384, 24 at
    192 / 128), and the two whole-row outputs they are cast into, twice
    as every output is — and the same share of a tile's intermediates
    (read off the compiler the same way at the nine cells' backward
    geometries, PERF.md §6, PR 48: the least limit it compiles under is
    1.2-1.5 tiles past the operands, and under the rule's sum at every
    one)."""
    D_v = D if D_v is None else D_v
    qd = block_q * _lanes(D)
    kd = block_k * _lanes(D)
    od = block_q * _lanes(D_v)          # o, do
    vd = block_k * _lanes(D_v)          # v, dv
    q_col = block_q * 128 * 4
    seg = 2 * (q_col + block_k * 128 * 4) if segmented else 0
    tiles = (3 if segmented else 2) * block_q * block_k * 4
    if which == "fwd":
        streamed = 2 * (qd + kd + vd) * itemsize + seg
        outputs = 2 * (od * itemsize + q_col)
        scratch = od * 4 + 2 * q_col
        return streamed + outputs + scratch + tiles
    # q, k, v, do and the lse and delta columns stream in both kernels.
    streamed = 2 * (qd + od + kd + vd) * itemsize + 2 * 2 * q_col + seg
    dq = streamed + 2 * qd * itemsize + qd * 4
    if which == "bwd_fused":
        resident = bwd_resident_bytes(rows, D, D_v)
        return dq + resident + 2 * (resident // 4) * itemsize + tiles
    dkv = streamed + 2 * (kd + vd) * itemsize + (kd + vd) * 4
    return max(dq, dkv) + tiles


def _compiler_params(footprint: int):
    """Mosaic parameters for a kernel of this footprint: the scoped-VMEM
    limit is raised to what the blocks need, and a quarter more, when
    that is more than the default (a pinned 2048 x 2048 tile);
    never lowered."""
    if footprint <= VMEM_SCOPED_DEFAULT:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(footprint + footprint // 4, VMEM_LIMIT_MAX)
    )


def _kv_group(BHq: int, BHk: int) -> int:
    """Query-heads-per-KV-head group size, derived purely from the leading
    (batch*heads) dims — GQA/MQA need no extra static arguments.

    Layout contract: the (B, S, H, D) -> (B*H, S, D) flattening is
    batch-major with query head ``h = hk * G + g`` (the natural
    ``transpose(0,2,1,3).reshape`` order), so q row ``b``'s KV row is
    exactly ``b // G``."""
    if BHq % BHk:
        raise ValueError(
            f"query head rows {BHq} not a multiple of kv head rows {BHk}"
        )
    return BHq // BHk


def _masked(blockdiff):
    """The kernels' ``blockdiff`` keyword where the call has the mask: a
    call without it builds its kernel from the keywords it always had."""
    return {} if blockdiff is None else {"blockdiff": blockdiff}


def _kv_walk(kv_range, n_q, n_k, causal, window, blockdiff, block_q,
             block_k):
    """``(q_at, k_at, inner, tables)`` of a call that keeps a Q block
    resident and streams K and V (forward, dq): the two block indices as
    functions of what an index map gets after the head row, the grid's
    inner axes, and the tables to prefetch.  Without the block-diffusion
    mask the axes are ``(q block, step)`` and :func:`_streamed_axis` maps
    the step; with it one axis walks :func:`_blockdiff_walk`'s tables,
    which the index maps get after the step."""
    if blockdiff is None:
        kv_j, n_j = _streamed_axis(kv_range, n_q, n_k, causal, window)
        return (lambda i, j: i), kv_j, (n_q, n_j), ()
    tables = _walk_operands(blockdiff, block_q, block_k, "kv")
    return ((lambda t, tq, tk, fl: tq[t]), (lambda t, tq, tk, fl: tk[t]),
            (len(tables[0]),), tables)


def _walk_operands(blockdiff, block_q, block_k, streamed):
    """:func:`_blockdiff_walk`'s tables as a ``pallas_call``'s leading
    operands."""
    return tuple(jnp.asarray(a) for a in _blockdiff_walk(
        *blockdiff, block_q, block_k, streamed))


#: The kernel wrappers are jitted in their own right: a model calls
#: them once a layer with the same shapes, and a jitted callee is traced
#: once and lowered to one function that every layer calls — Mosaic's
#: lowering of a 1024 x 1024 tile, paid at every process start even with
#: the compile cache warm, is then paid once a kernel and not once a
#: kernel and layer.
_KERNEL_STATICS = ("scale", "causal", "block_q", "block_k", "interpret",
                   "window", "blockdiff")


def _pallas(kernel, tables, *, grid, in_specs, out_specs, scratch_shapes,
            **params):
    """``pl.pallas_call`` of one of the three kernels: as it always was
    where no table leads the operands; with ``tables`` (the
    block-diffusion walk's) scalar-prefetched, so that the index maps and
    the kernel read them by the step."""
    if not tables:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes, **params)
    return pl.pallas_call(
        kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes), **params)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_bh_fwd(q, k, v, *, scale, causal, block_q, block_k, interpret,
                  q_seg=None, kv_seg=None, window=None, blockdiff=None):
    """(BH, S, D) flash attention forward; returns (o, lse).  ``v`` may
    be narrower or wider than ``q`` and ``k`` (BHk, Sk, D_v): ``o`` is
    then (BH, Sq, D_v).

    ``k``/``v`` may carry FEWER head rows than ``q`` (GQA/MQA): with
    ``G = BHq // BHk``, q row ``b`` attends to kv row ``b // G`` — pure
    index-map arithmetic, the shared KV block is streamed once per query
    head with no materialized repeat.

    ``q_seg``/``kv_seg``: optional (BH, S, 1) int32 segment ids for packed
    sequences — attention is masked to segment-id equality.

    ``blockdiff``: ``(L, B)``, the block-diffusion mask over ``S = 2 L``
    rows ``[clean ; noisy]`` (:func:`blockdiff_mask`): the grid's inner
    axis is then the q-major list of the mask's live tiles."""
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    G = _kv_group(BH, k.shape[0])
    n_q = Sq // block_q
    kv_range, _ = _live_ranges(Sq, Sk, block_q, block_k, causal, window)
    q_at, k_at, inner, tables = _kv_walk(
        kv_range, n_q, Sk // block_k, causal, window, blockdiff, block_q,
        block_k)
    grid = (BH, *inner)
    segmented = q_seg is not None

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, segmented=segmented,
        block_q=block_q, block_k=block_k, kv_range=kv_range, window=window,
        **_masked(blockdiff),
    )
    scratch = [
        pltpu.VMEM((block_q, Dv), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, *g: (b, q_at(*g), 0)),
        pl.BlockSpec((1, block_k, D),
                     lambda b, *g: (b // G, k_at(*g), 0)),
        pl.BlockSpec((1, block_k, Dv),
                     lambda b, *g: (b // G, k_at(*g), 0)),
    ]
    args = [q, k, v]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, *g: (b, q_at(*g), 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda b, *g: (b // G, k_at(*g), 0)),
        ]
        args += [q_seg, kv_seg]
    tiles = tile_census(Sq, Sk, block_q, block_k, causal, window,
                        blockdiff)["fwd"]
    with named_scope("flash-fwd"), _tiles_scope(tiles):
        return _pallas(
            kernel, tables,
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
                jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
            ],
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, Dv),
                             lambda b, *g: (b, q_at(*g), 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, *g: (b, q_at(*g), 0)),
            ],
            scratch_shapes=scratch,
            compiler_params=_compiler_params(flash_vmem_bytes(
                block_q, block_k, D, q.dtype.itemsize, "fwd", segmented,
                Dv)),
            interpret=interpret,
            name="flash-fwd",
        )(*tables, *args)


def _dq_kernel(
    *refs,
    scale: float, causal: bool, segmented: bool, block_q: int, block_k: int,
    kv_range, window=None, blockdiff=None,
):
    walk, refs = _walked(refs, blockdiff, block_q, block_k)
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        qs_ref = ks_ref = None
    j = n_j = None
    if walk is None:
        iq = pl.program_id(1)
        j = pl.program_id(2)
        n_j = pl.num_programs(2)
    first, last = _edges(walk, j, n_j)

    @pl.when(first())
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = k_start = None
    if walk is None:
        q_start = iq * block_q
        ik, in_band = _streamed_block(kv_range, iq, j, window)
        k_start = ik * block_k

    def tile(mask_of, rows=_WHOLE, cols=_WHOLE):
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = mask_of(s.shape)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        # A FULLY-masked row (padding, or a query past the last key's
        # reach under a window: it lies only in tiles the window cuts)
        # has lse ~ _NEG_INF, making exp(s - lse) = 1 at masked entries:
        # with a segment mask they are zeroed explicitly, under a window
        # alone the row is shifted by 0 (:func:`_reached`).
        lse = lse_ref[0, rows, :]
        if window is not None and not segmented:
            lse = _reached(lse)
        p = jnp.exp(s - lse)
        if segmented:
            p = jnp.where(mask, p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, rows, :]) * scale).astype(k.dtype)
        dq_acc[rows] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _run_tiles(tile, walk, lambda: _band_run(
        causal, window, q_start, block_q, k_start, block_k, in_band),
        lambda shape: _block_mask(shape, causal, q_start, k_start, qs_ref,
                                  ks_ref, window),
        _halves(walk, causal, window, segmented, q_start, block_q, k_start,
                block_k))

    @pl.when(last())
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    *refs,
    scale: float, causal: bool, segmented: bool, block_q: int, block_k: int,
    n_q: int, q_range, window=None, blockdiff=None, group: int = 1,
):
    # (Under the block-diffusion mask the walk is the k-major list of
    # live tiles, every query head of the group at each: tile ``t //
    # group``, and a K block opens with its first tile's first head and
    # closes with its last tile's last.)
    walk = None
    if blockdiff is not None:
        t = pl.program_id(1)
        g = t % group
        iq, ik, opens, closes, cut, cut_mask = _blockdiff_step(
            refs[:3], t // group, *blockdiff, block_q, block_k)
        walk = (iq, ik, opens & (g == 0), closes & (g == group - 1), cut,
                cut_mask)
        refs = refs[3:]
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    i = n_i = None
    if walk is None:
        ik = pl.program_id(1)   # grid: (BHk, n_k, G*n_q) — (head, q) innermost
        # The innermost axis enumerates (g, iq) pairs: for GQA every query
        # head of the group contributes to this KV row's dk/dv, so the
        # accumulator runs over all G * n_q steps and flushes once.  (With
        # a window ``n_q`` is the band's width in q blocks, not the axis'.)
        i = pl.program_id(2)
        iq, in_band = _streamed_block(q_range, ik, i % n_q, window)
        n_i = pl.num_programs(2)
    first, last = _edges(walk, i, n_i)

    @pl.when(first())
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = k_start = None
    if walk is None:
        q_start = iq * block_q
        k_start = ik * block_k

    # (The rectangle's and the band's skip: when the whole Q block
    # precedes the whole K block (causal) or lies entirely beyond the K
    # block's window reach.)
    def tile(mask_of, rows=_WHOLE, cols=_WHOLE):
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = mask_of(s.shape)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        lse = lse_ref[0, rows, :]
        if window is not None and not segmented:
            lse = _reached(lse)
        p = jnp.exp(s - lse)
        if segmented:
            p = jnp.where(mask, p, 0.0)  # see _dq_kernel
        pt = p.astype(do.dtype).T
        dv_acc[cols] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, rows, :]) * scale).astype(q.dtype)
        dk_acc[cols] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    _run_tiles(tile, walk, lambda: _band_run(
        causal, window, q_start, block_q, k_start, block_k, in_band),
        lambda shape: _block_mask(shape, causal, q_start, k_start, qs_ref,
                                  ks_ref, window),
        _halves(walk, causal, window, segmented, q_start, block_q, k_start,
                block_k))

    @pl.when(last())
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(
    *refs,
    scale: float, causal: bool, segmented: bool, block_q: int, block_k: int,
    kv_range, group: int, window=None, blockdiff=None,
):
    """The whole backward in one pass over :func:`_dq_kernel`'s grid:
    ``s``, the mask, ``p``, ``dp`` and ``ds`` once a tile, ``dq`` summed
    over the row's K tiles in its ``(block_q, D)`` scratch as there, and
    ``dk`` / ``dv`` summed into float32 buffers that hold the KV row
    WHOLE, resident over the ``group`` query heads of the KV row (they
    are consecutive head rows, :func:`_kv_group`): zeroed at the group's
    first step, a tile's rows added to at each step, cast and written at
    its last.  A K tile so receives its terms in ``(head, q block)``
    order, :func:`_dkv_kernel`'s on a rectangle and on a band."""
    walk, refs = _walked(refs, blockdiff, block_q, block_k)
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    j = n_j = None
    if walk is None:
        iq = pl.program_id(1)
        j = pl.program_id(2)
        n_j = pl.num_programs(2)
    first, last = _edges(walk, j, n_j)
    # The group's ends: its first head row's first step, its last head
    # row's last — every inner axis of the grid at its end.
    g = pl.program_id(0) % group
    inner = range(1, 2 if walk is not None else 3)
    opens = functools.reduce(
        jnp.logical_and, [pl.program_id(a) == 0 for a in inner], g == 0)
    closes = functools.reduce(
        jnp.logical_and,
        [pl.program_id(a) == pl.num_programs(a) - 1 for a in inner],
        g == group - 1)
    n_k = dk_acc.shape[0] // block_k

    def k_rows(ik, cols=_WHOLE):
        """K tile ``ik``'s rows of the resident buffers, or the part
        ``cols`` of them."""
        first, end, _ = cols.indices(block_k)
        start = ik * block_k
        if first:
            start = start + first
        return pl.ds(pl.multiple_of(start, end - first), end - first)

    @pl.when(opens)
    def _():
        def zero(ik, carry):
            dk_acc[k_rows(ik), :] = jnp.zeros((block_k, dk_acc.shape[1]),
                                              jnp.float32)
            dv_acc[k_rows(ik), :] = jnp.zeros((block_k, dv_acc.shape[1]),
                                              jnp.float32)
            return carry

        jax.lax.fori_loop(0, n_k, zero, 0)

    @pl.when(first())
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = k_start = None
    if walk is None:
        q_start = iq * block_q
        ik, in_band = _streamed_block(kv_range, iq, j, window)
        k_start = ik * block_k
    else:
        ik = walk[1]

    def tile(mask_of, rows=_WHOLE, cols=_WHOLE):
        # (``dq`` is per query row and ``dk`` / ``dv`` per key row: a
        # part of the tile adds its rows' terms at its keys' rows.)
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        keys = k_rows(ik, cols)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = mask_of(s.shape)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        lse = lse_ref[0, rows, :]
        if window is not None and not segmented:
            lse = _reached(lse)
        p = jnp.exp(s - lse)
        if segmented:
            p = jnp.where(mask, p, 0.0)  # see _dq_kernel
        pt = p.astype(do.dtype).T
        dv_acc[keys, :] += jnp.dot(pt, do,
                                   preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, rows, :]) * scale).astype(q.dtype)
        dk_acc[keys, :] += jnp.dot(ds.T, q,
                                   preferred_element_type=jnp.float32)
        dq_acc[rows] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _run_tiles(tile, walk, lambda: _band_run(
        causal, window, q_start, block_q, k_start, block_k, in_band),
        lambda shape: _block_mask(shape, causal, q_start, k_start, qs_ref,
                                  ks_ref, window),
        _halves(walk, causal, window, segmented, q_start, block_q, k_start,
                block_k))

    @pl.when(last())
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(closes)
    def _():
        def write(ik, carry):
            rows = k_rows(ik)
            dk_ref[0, rows, :] = dk_acc[rows, :].astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)
            return carry

        jax.lax.fori_loop(0, n_k, write, 0)


def _bwd_delta(o, do, dlse=None):
    """The rows' residual of the backward, ``delta_i = rowsum(dO ∘ O)``
    as a (BH, Sq, 1) float32 column — cheap elementwise, XLA handles it.
    ``dlse``: the cotangent of the row log-sum-exp where that is an
    output (ring attention merges blocks by it): since
    ∂lse_i/∂s_ij = p_ij, the whole contribution folds into the column,
    ds = p·(dp − (δ − dlse))."""
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[..., None]                                   # (BH, Sq, 1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)[..., None]
    return delta


def bwd_fused_vmem_bytes(Sk: int, block_q: int, block_k: int, D: int,
                         itemsize: int, segmented: bool = False,
                         D_v: Optional[int] = None) -> Optional[int]:
    """The footprint of the one-pass backward over KV rows ``Sk`` long
    (:func:`flash_vmem_bytes`, ``which="bwd_fused"``) where that is
    within :data:`VMEM_LIMIT_MAX`, else None: THE rule by which
    :func:`_flash_bh_bwd` runs one kernel or two.  By shape alone."""
    footprint = flash_vmem_bytes(block_q, block_k, D, itemsize, "bwd_fused",
                                 segmented, D_v, rows=Sk)
    return footprint if footprint <= VMEM_LIMIT_MAX else None


def _kv_streamed(q, k, v, do, lse, delta, q_seg, kv_seg, *, block_q,
                 block_k, causal, window, blockdiff):
    """What a backward call on the forward's grid — a Q block resident,
    K and V streamed (:func:`_kv_walk`): dq alone, or the fused pass —
    is built from: ``(kv_range, tables, inner, q_spec(d), in_specs,
    args)``."""
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    G = _kv_group(BH, k.shape[0])
    kv_range, _ = _live_ranges(Sq, Sk, block_q, block_k, causal, window)
    q_at, k_at, inner, tables = _kv_walk(
        kv_range, Sq // block_q, Sk // block_k, causal, window, blockdiff,
        block_q, block_k)

    def q_spec(d):
        return pl.BlockSpec((1, block_q, d), lambda b, *g: (b, q_at(*g), 0))

    def k_spec(d):
        return pl.BlockSpec((1, block_k, d),
                            lambda b, *g: (b // G, k_at(*g), 0))

    in_specs = [q_spec(D), k_spec(D), k_spec(Dv), q_spec(Dv), q_spec(1),
                q_spec(1)]
    args = [q, k, v, do, lse, delta]
    if q_seg is not None:
        in_specs += [q_spec(1), k_spec(1)]
        args += [q_seg, kv_seg]
    return kv_range, tables, inner, q_spec, in_specs, args


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_bwd_fused(q, k, v, o, lse, do, *, scale, causal, block_q,
                     block_k, interpret, dlse=None, q_seg=None, kv_seg=None,
                     window=None, blockdiff=None):
    """(dq, dk, dv) from ONE ``pallas_call`` (:func:`_bwd_kernel`) under
    the scope and the name ``flash-bwd-dkv``: five products a live tile
    and one fetch of each operand, where the pair below runs seven and
    two.  ``dk`` and ``dv`` leave as whole-row blocks indexed by the KV
    row alone, so each is written once, when the row's last query head
    is done.  (The operands are :func:`_flash_bh_bwd`'s.)"""
    delta = _bwd_delta(o, do, dlse)
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    BHk = k.shape[0]
    G = _kv_group(BH, BHk)
    segmented = q_seg is not None
    kv_range, tables, inner, q_spec, in_specs, args = _kv_streamed(
        q, k, v, do, lse, delta, q_seg, kv_seg, block_q=block_q,
        block_k=block_k, causal=causal, window=window, blockdiff=blockdiff)
    dk_spec, dv_spec = (pl.BlockSpec((1, Sk, d), lambda b, *g: (b // G, 0, 0))
                        for d in (D, Dv))
    tiles = tile_census(Sq, Sk, block_q, block_k, causal, window,
                        blockdiff, segmented)["dq"]
    with named_scope("flash-bwd-dkv"), _tiles_scope(tiles):
        return _pallas(
            functools.partial(
                _bwd_kernel, scale=scale, causal=causal, segmented=segmented,
                block_q=block_q, block_k=block_k, kv_range=kv_range,
                group=G, window=window, **_masked(blockdiff),
            ), tables,
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                jax.ShapeDtypeStruct((BHk, Sk, D), k.dtype),
                jax.ShapeDtypeStruct((BHk, Sk, Dv), v.dtype),
            ],
            grid=(BH, *inner),
            in_specs=in_specs,
            out_specs=[q_spec(D), dk_spec, dv_spec],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((Sk, D), jnp.float32),
                pltpu.VMEM((Sk, Dv), jnp.float32),
            ],
            compiler_params=_compiler_params(flash_vmem_bytes(
                block_q, block_k, D, q.dtype.itemsize, "bwd_fused",
                segmented, Dv, rows=Sk)),
            interpret=interpret,
            name="flash-bwd-dkv",
        )(*tables, *args)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_bwd_pair(q, k, v, o, lse, do, *, scale, causal, block_q,
                    block_k, interpret, dlse=None, q_seg=None, kv_seg=None,
                    window=None, blockdiff=None):
    """(dq, dk, dv) from two ``pallas_call``s, ``flash-bwd-dq`` and
    ``flash-bwd-dkv``, each computing ``s``, ``p`` and ``dp`` for itself:
    the backward of a KV row too long for :func:`_flash_bwd_fused`'s
    resident accumulators (:func:`bwd_fused_vmem_bytes`), the same sums
    in the same order.  (The operands are :func:`_flash_bh_bwd`'s.)"""
    delta = _bwd_delta(o, do, dlse)
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    BHk = k.shape[0]
    G = _kv_group(BH, BHk)
    segmented = q_seg is not None
    n_q = Sq // block_q
    n_k = Sk // block_k
    params = _compiler_params(flash_vmem_bytes(
        block_q, block_k, D, q.dtype.itemsize, "bwd", segmented, Dv))
    _, q_range = _live_ranges(Sq, Sk, block_q, block_k, causal, window)
    kv_range, tables, inner, q_spec, dq_in, dq_args = _kv_streamed(
        q, k, v, do, lse, delta, q_seg, kv_seg, block_q=block_q,
        block_k=block_k, causal=causal, window=window, blockdiff=blockdiff)
    tiles = tile_census(Sq, Sk, block_q, block_k, causal, window, blockdiff,
                        segmented)
    with named_scope("flash-bwd-dq"), _tiles_scope(tiles["dq"]):
        dq = _pallas(
            functools.partial(
                _dq_kernel, scale=scale, causal=causal, segmented=segmented,
                block_q=block_q, block_k=block_k, kv_range=kv_range,
                window=window, **_masked(blockdiff),
            ), tables,
            out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            grid=(BH, *inner),
            in_specs=dq_in,
            out_specs=q_spec(D),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
            name="flash-bwd-dq",
        )(*tables, *dq_args)

    # dkv grid walks (BHk, n_k, G*n_q): one program chain per KV row with
    # every query head of its group innermost — the group's contributions
    # accumulate in the scratch and flush once, so GQA's dk/dv reduction
    # needs no extra pass.  Query-side rows for (kv row b, inner step i)
    # live at q row b*G + i // n_q, q block i % n_q.
    # The query side is the streamed one here: its block index stops at
    # the band of k block j, within each query head of the group (with
    # a window the group's inner axis IS that band, ``n_i`` blocks wide).
    # Under the block-diffusion mask the axis after the KV row is the
    # k-major walk of the live tiles with the group's heads innermost:
    # step ``t`` is tile ``t // G`` under query head ``t % G``.
    if blockdiff is None:
        q_i, n_i = _streamed_axis(q_range, n_k, n_q, causal, window)
        tables, inner = (), (n_k, G * n_i)

        def q_rows(b, j, i):
            return (b * G + i // n_i, q_i(j, i % n_i), 0)

        def k_rows(b, j, i):
            return (b, j, 0)
    else:
        n_i = None
        tables = _walk_operands(blockdiff, block_q, block_k, "q")
        inner = (G * len(tables[0]),)

        def q_rows(b, t, tq, tk, fl):
            return (b * G + t % G, tq[t // G], 0)

        def k_rows(b, t, tq, tk, fl):
            return (b, tk[t // G], 0)

    qT_spec, doT_spec = (pl.BlockSpec((1, block_q, d), q_rows)
                         for d in (D, Dv))
    kT_spec, vT_spec = (pl.BlockSpec((1, block_k, d), k_rows)
                        for d in (D, Dv))
    rT_spec = pl.BlockSpec((1, block_q, 1), q_rows)
    dkv_in = [qT_spec, kT_spec, vT_spec, doT_spec, rT_spec, rT_spec]
    dkv_args = [q, k, v, do, lse, delta]
    if segmented:
        dkv_in += [
            rT_spec,
            pl.BlockSpec((1, block_k, 1), k_rows),
        ]
        dkv_args += [q_seg, kv_seg]
    walked = {} if blockdiff is None else {"blockdiff": blockdiff,
                                           "group": G}
    with named_scope("flash-bwd-dkv"), _tiles_scope(tiles["dkv"]):
        dk, dv = _pallas(
            functools.partial(
                _dkv_kernel, scale=scale, causal=causal,
                segmented=segmented, block_q=block_q, block_k=block_k,
                n_q=n_i, q_range=q_range, window=window, **walked,
            ), tables,
            out_shape=[
                jax.ShapeDtypeStruct((BHk, Sk, D), k.dtype),
                jax.ShapeDtypeStruct((BHk, Sk, Dv), v.dtype),
            ],
            grid=(BHk, *inner),
            in_specs=dkv_in,
            out_specs=[kT_spec, vT_spec],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, Dv), jnp.float32),
            ],
            compiler_params=params,
            interpret=interpret,
            name="flash-bwd-dkv",
        )(*tables, *dkv_args)
    return dq, dk, dv


def _flash_bh_bwd(q, k, v, o, lse, do, *, block_q, block_k, q_seg=None,
                  **call):
    """(BH, S, D) flash attention backward: (dq, dk, dv) — in one pass
    over the tiles (:func:`_flash_bwd_fused`) where the KV row's float32
    ``dk`` and ``dv`` fit in VMEM beside the tile
    (:func:`bwd_fused_vmem_bytes`: every row up to 16,384 at D <= 256),
    else by the two kernels (:func:`_flash_bwd_pair`).  Either side is
    jitted in its own right, as the forward is, and takes this
    function's operands and keywords (``scale``, ``causal``,
    ``interpret``, ``kv_seg``, ``window``, ``blockdiff``).

    ``dlse``: optional cotangent of the row log-sum-exp output (used when
    the LSE itself feeds downstream math, e.g. cross-block merging in ring
    attention), folded into the rows' residual (:func:`_bwd_delta`).
    """
    fused = bwd_fused_vmem_bytes(
        k.shape[1], block_q, block_k, q.shape[2], q.dtype.itemsize,
        q_seg is not None, v.shape[2]) is not None
    return (_flash_bwd_fused if fused else _flash_bwd_pair)(
        q, k, v, o, lse, do, block_q=block_q, block_k=block_k, q_seg=q_seg,
        **call)


#: The name (``jax.ad_checkpoint.checkpoint_name``) of what the backward
#: kernels read of the forward kernel: its output ``o`` and the rows'
#: log-sum-exp ``lse``.  It is put on them INSIDE the forward rules of
#: :func:`_flash_bh` and :func:`_flash_bh_seg`, on the very values that
#: go into the residual tuple: a ``jax.checkpoint`` whose policy saves
#: this name then runs ``flash-fwd`` once a layer-step, where without it
#: the backward pass runs the kernel again from the recomputed ``q, k,
#: v`` only to have these two back (a name on a copy of ``o`` outside the
#: ``custom_vjp`` saves nothing: ``lse`` exists nowhere else).  ``q, k,
#: v`` stay unnamed, recomputed with their projections.  Outside a
#: ``jax.checkpoint`` the names are identities the compiler drops.
#: ``flash_attention_with_lse[_seg]`` (ring attention's) is left as it
#: is: there ``lse`` is an output with a cotangent of its own.
FLASH_RESIDUALS = "flash-residuals"


def _named_residuals(o, lse):
    """The forward kernel's ``o`` and ``lse`` as the forward rules hand
    them on, named.  ``lse`` leaves the kernel as a (BH, S, 1) column,
    which the chip holds one number a 128-lane row (268 MB for 2 MB of
    data at 64 head rows of 8192 tokens: ``f32[64,8192,1]{2,1,0:T(8,128)}``
    in the step compiled for a v5e); it is kept between the passes as
    (BH, S), the tokens on the lanes, and :func:`_lse_column` gives the
    backward kernels their column back.  ``o`` is handed on from a
    barrier it shares with the flat ``lse``: nothing reads ``o`` before
    the column has been turned (left to itself the scheduler put that off
    until the layer's backward pass in two cells of three, the column
    alive all the while).  Where nothing is rematerialised the two
    reshapes meet and cancel and the barrier's other half is dead: such
    a step compiles to what it was."""
    lse = lse.reshape(lse.shape[:2])
    o, _ = jax.lax.optimization_barrier((o, lse))
    return (checkpoint_name(o, FLASH_RESIDUALS),
            checkpoint_name(lse, FLASH_RESIDUALS))


def _lse_column(lse):
    return lse.reshape(lse.shape + (1,))


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_bh(q, k, v, scale, causal, block_q, block_k, interpret,
              window=None, block_q_bwd=None, block_k_bwd=None,
              blockdiff=None):
    """(BH, S, D) flash attention, differentiable (FlashAttention-2-style
    explicit backward: recompute probabilities blockwise from the saved row
    LSE, never materializing the S×S matrix in either pass).

    ``block_q_bwd``/``block_k_bwd``: optional separate geometry for the
    backward (its tile economics differ — two extra streamed
    operands, five products a tile); None means reuse the forward blocks.
    ``blockdiff``: ``(L, B)`` of the block-diffusion mask, or None."""
    o, _ = _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window, **_masked(blockdiff),
    )
    return o


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                   window=None, block_q_bwd=None, block_k_bwd=None,
                   blockdiff=None):
    o, lse = _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window, **_masked(blockdiff),
    )
    o, lse = _named_residuals(o, lse)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, window,
                   block_q_bwd, block_k_bwd, blockdiff, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bh_bwd(
        q, k, v, o, _lse_column(lse), do, scale=scale, causal=causal,
        block_q=block_q_bwd or block_q, block_k=block_k_bwd or block_k,
        interpret=interpret, window=window, **_masked(blockdiff),
    )
    return dq, dk, dv


_flash_bh.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _float0_like(x):
    """Cotangent for integer primal inputs (jax's float0 convention)."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_bh_seg(q, k, v, q_seg, kv_seg, scale, causal, block_q, block_k,
                  interpret, window=None, block_q_bwd=None,
                  block_k_bwd=None):
    """Segment-masked (BH, S, D) flash attention (packed sequences):
    tokens attend only within their own segment id.  Same explicit
    FlashAttention-2 backward (with its own optional block geometry, see
    :func:`_flash_bh`); fully-masked (padding) rows produce zero output
    and zero gradients."""
    o, _ = _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        q_seg=q_seg, kv_seg=kv_seg, window=window,
    )
    return o


def _flash_seg_vjp_fwd(q, k, v, q_seg, kv_seg, scale, causal, block_q,
                       block_k, interpret, window=None, block_q_bwd=None,
                       block_k_bwd=None):
    o, lse = _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        q_seg=q_seg, kv_seg=kv_seg, window=window,
    )
    o, lse = _named_residuals(o, lse)
    return o, (q, k, v, o, lse, q_seg, kv_seg)


def _flash_seg_vjp_bwd(scale, causal, block_q, block_k, interpret, window,
                       block_q_bwd, block_k_bwd, res, do):
    q, k, v, o, lse, q_seg, kv_seg = res
    dq, dk, dv = _flash_bh_bwd(
        q, k, v, o, _lse_column(lse), do, scale=scale, causal=causal,
        block_q=block_q_bwd or block_q, block_k=block_k_bwd or block_k,
        interpret=interpret, q_seg=q_seg, kv_seg=kv_seg, window=window,
    )
    return dq, dk, dv, _float0_like(q_seg), _float0_like(kv_seg)


_flash_bh_seg.defvjp(_flash_seg_vjp_fwd, _flash_seg_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(q, k, v, scale, causal, block_q, block_k,
                             interpret):
    """(BH, S, D) flash attention returning ``(o, lse)`` — both
    differentiable.  For composition layers (ring/zigzag) that merge
    blocks via the row log-sum-exp: the LSE cotangent folds into the
    backward kernels' residual (see :func:`_flash_bh_bwd`)."""
    return _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _flash_lse_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, lse = _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(scale, causal, block_q, block_k, interpret, res, cots):
    q, k, v, o, lse = res
    do, dlse = cots
    # lse output is (BH, S, 1) from the kernel; normalize cotangent shape.
    dlse2 = dlse[..., 0] if dlse.ndim == 3 else dlse
    dq, dk, dv = _flash_bh_bwd(
        q, k, v, o, lse, do, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret, dlse=dlse2,
    )
    return dq, dk, dv


flash_attention_with_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def segment_mask(q_segment_ids, kv_segment_ids):
    """(B, Sq) × (B, Sk) int ids → (B, Sq, Sk) boolean equality mask —
    THE packed-sequence mask rule, shared by the XLA fallback, ring, and
    zigzag paths (one definition to evolve, e.g. a future 'padding id
    matches nothing' convention)."""
    return q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention_with_lse_seg(q, k, v, q_seg, kv_seg, scale, causal,
                                 block_q, block_k, interpret):
    """Segment-masked :func:`flash_attention_with_lse` — ``(o, lse)``
    with both cotangents folding into the explicit backward, plus the
    packed-sequence masks.  The composition form for segmented
    ring/zigzag inners."""
    return _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        q_seg=q_seg, kv_seg=kv_seg,
    )


def _flash_lse_seg_vjp_fwd(q, k, v, q_seg, kv_seg, scale, causal, block_q,
                           block_k, interpret):
    o, lse = _flash_bh_fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        q_seg=q_seg, kv_seg=kv_seg,
    )
    return (o, lse), (q, k, v, o, lse, q_seg, kv_seg)


def _flash_lse_seg_vjp_bwd(scale, causal, block_q, block_k, interpret, res,
                           cots):
    q, k, v, o, lse, q_seg, kv_seg = res
    do, dlse = cots
    dlse2 = dlse[..., 0] if dlse.ndim == 3 else dlse
    dq, dk, dv = _flash_bh_bwd(
        q, k, v, o, lse, do, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret, dlse=dlse2,
        q_seg=q_seg, kv_seg=kv_seg,
    )
    return dq, dk, dv, _float0_like(q_seg), _float0_like(kv_seg)


flash_attention_with_lse_seg.defvjp(
    _flash_lse_seg_vjp_fwd, _flash_lse_seg_vjp_bwd
)


def _xla_attention(q, k, v, scale, causal, q_segment_ids=None,
                   kv_segment_ids=None, window=None, blockdiff=None):
    if k.shape[2] != q.shape[2]:
        # GQA/MQA fallback: broadcast KV heads to the query head count.
        # jnp.repeat's transpose sums the group's dk/dv — exactly the
        # grouped reduction the Pallas dkv kernel does in its scratch.
        G = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    Sq, Sk = q.shape[1], k.shape[1]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))[None]
    if window is not None:
        band = (
            jnp.arange(Sq)[:, None] - jnp.arange(Sk)[None, :] < window
        )[None]
        mask = band if mask is None else (mask & band)
    if blockdiff is not None:
        # (the block-diffusion mask stands alone: its "causal" is between
        # blocks, and flash_attention admits neither window nor segments)
        mask = blockdiff_mask(*blockdiff)[None]
    if q_segment_ids is not None:
        seg = segment_mask(q_segment_ids, kv_segment_ids)
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        logits = jnp.where(mask[:, None], logits, _NEG_INF)
    w = jax.nn.softmax(logits)
    if q_segment_ids is not None:
        # Fully-masked (padding) rows: softmax of all -inf is uniform
        # garbage; zero them so output AND gradients vanish, matching the
        # Pallas kernel's behavior.
        any_valid = mask.any(axis=-1)  # (B, Sq)
        w = jnp.where(any_valid[:, None, :, None], w, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(q.dtype)


def _sublane(dtype) -> int:
    """Rows of one tile of this dtype: a compiled block's second-to-last
    dim must be a multiple of it."""
    return 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8


def auto_block_size(S: int, D: int, dtype, which: str = "fwd",
                    segmented: bool = False,
                    window: Optional[int] = None,
                    D_v: Optional[int] = None,
                    blockdiff: Optional[tuple] = None) -> int:
    """The STATIC default block edge along a length-``S`` axis: the
    largest multiple of 128 that divides ``S`` and whose square tile fits
    Mosaic's default scoped VMEM (:data:`VMEM_SCOPED_DEFAULT`) by
    :func:`flash_vmem_bytes` for this width of queries and keys ``D``
    and of values ``D_v`` (None: ``D``), dtype, kernel (``which``:
    ``"fwd"`` or ``"bwd"``) and segment mask: 1024 at D=128 in bf16, 512
    at D=256 in fp32 or with segment ids.  A grid step has a fixed cost
    and the kernels come near the MXU only at large tiles, so few full
    steps beat many small ones (PERF.md §6, PR 25: the sweep at B=8,
    H=16, S=2048 took the three kernels from 41.9 ms a layer at
    128 x 128 to 6.4 at 1024 x 1024).  Under a sliding window the
    forward's edge is no wider than the band, which a wider tile would
    only fill with masked work, and the backward's no wider than half of
    it while that leaves 512: the grid of a windowed call is its band,
    so an edge trades masked area (fill = window / (edge + window): 1/2
    at the window's width, 2/3 at half of it) against steps alone, and
    the backward's five products a tile make the masked area the dearer
    of the two, the forward's rescaling of its accumulator a step the
    steps (PERF.md §6, PR 40, ``benchmarks/flash_window_probe.py``: at
    32/4 head rows, D=128, S=16,384 under a window of 1024 in bf16 the
    backward pair takes 11.96 ms at 1024 x 1024 and 10.42 at 512 x 512,
    the forward 5.55 and 6.11; 256-edge tiles lose everywhere; at
    S=8,192 under 4,096 half the window is past what VMEM takes and 1024
    wins both passes).  Each axis is sized alone: the footprint grows with either edge, so a
    pair of fitting edges fits.  A length no multiple of 128 divides
    keeps the old answer (``min(128, S)``: whole when short, else a
    block that does not divide and sends the call to XLA) — an auto pick
    must not demote a shape that compiles.  Under the block-diffusion
    mask (``blockdiff`` = ``(L, B)``, ``S = 2 L``) an edge divides ``L``,
    so that a tile's rows are all clean or all noisy; the mask's dead
    tiles are not visited and its cut tiles are the diagonals', as the
    triangle's are, so the largest fitting edge wins as it does there."""
    if blockdiff is not None:
        S = blockdiff[0]
    edges = [b for b in range(128, S + 1, 128) if S % b == 0]
    if not edges:
        return min(128, S)
    if window is not None:
        widest = window
        if which == "bwd":
            widest = max(window // 2, min(window, 512))
        edges = [b for b in edges if b <= max(widest, edges[0])]
    itemsize = jnp.dtype(dtype).itemsize
    fits = [b for b in edges
            if flash_vmem_bytes(b, b, D, itemsize, which, segmented, D_v)
            <= VMEM_SCOPED_DEFAULT]
    return max(fits, default=edges[0])


def _publish_blockdiff(blockdiff, record) -> None:
    """The block-diffusion mask's own record beside ``flash_geometry``,
    through the publisher the other ops use (``ops.ssd.publish_geometry``):
    a ``blockdiff_geometry`` row, ``blockdiff/*`` gauges and a
    ``blockdiff/calls`` counter — ``L``, ``B``, the rows a call runs, the
    pairs a head row attends, and ``<kernel>/live|visited|copied``, each
    kernel's tiles a head row."""
    from chainermn_tpu.ops.ssd import publish_geometry

    L, B = blockdiff
    publish_geometry("blockdiff_geometry", "blockdiff", {
        "L": L, "B": B, "rows": 2 * L, "live_pairs": blockdiff_pairs(L, B),
        **{f"{kernel}/{field}": tiles[field]
           for kernel, tiles in record.items()
           for field in ("live", "visited", "copied")}})


def shape_key(heads: int, kv_heads: int, window: Optional[int]) -> str:
    """The name a call's ROW SHAPE goes by in the published geometry:
    its query and key/value heads and its window (0: none).  The rows of
    one block table may differ in any of the three."""
    return f"h{heads}-kv{kv_heads}-w{window or 0}"


def _publish_geometry(fwd: dict, bwd: dict, blockdiff=None,
                      resident_bytes: Optional[int] = None,
                      shape: Optional[tuple] = None) -> None:
    """One record a :func:`flash_attention` call that reaches the kernels
    (at TRACE time: a jitted step publishes again only when retraced):
    the blocks each kernel was given and the tiles it finds live, visits
    and copies a head row, and which backward the footprint rule gave it
    — ``resident_bytes`` the float32 ``dk`` / ``dv`` rows the one-pass
    backward holds in VMEM (its tiles are then ``flash-bwd-dkv``'s, on
    dq's grid, and there is no ``flash-bwd-dq`` entry), None the two
    kernels.  To the installed sinks: a ``flash_geometry`` row of the
    StepRecorder (the kernels' entries — :func:`tile_census`'s, ``cut``
    and ``halved`` among them —, ``bwd_fused`` and
    ``bwd_resident_bytes``), ``flash/<kernel>/<field>`` gauges,
    ``flash/bwd_fused`` (1 or 0) and ``flash/bwd_resident_bytes`` gauges
    and the ``flash/calls`` and ``flash/bwd_fused_calls`` counters of the
    Reporter.  The ``flash/<kernel>/<field>`` gauges are the LAST traced
    call's; ``shape`` (``(heads, kv_heads, window)``) says whose they are:
    the row gains ``heads``, ``kv_heads``, ``group`` and ``window``, and
    the same census goes a second time under the row shape's own name
    (:func:`shape_key`), ``flash/shape/<key>/<kernel>/<field>`` gauges
    with ``flash/shape/<key>/heads|kv_heads|group|window`` and a
    ``flash/shape/<key>/calls`` counter, so that a step whose attention
    rows differ in shape publishes every one of them."""
    fused = resident_bytes is not None
    record = {"flash-fwd": fwd}
    if fused:
        record["flash-bwd-dkv"] = bwd["dq"]
    else:
        record.update({"flash-bwd-dq": bwd["dq"],
                       "flash-bwd-dkv": bwd["dkv"]})
    row = {}
    if shape is not None:
        heads, kv_heads, window = shape
        row = {"heads": heads, "kv_heads": kv_heads,
               "group": heads // kv_heads, "window": window or 0}
    rec = _step_log.current_recorder()
    if rec is not None:
        rec.record("flash_geometry", **record, bwd_fused=fused,
                   bwd_resident_bytes=resident_bytes or 0, **row)
    rep = _reporter.get_reporter()
    if rep is not None:
        rep.count("flash/calls")
        if fused:
            rep.count("flash/bwd_fused_calls")
        rep.gauge("flash/bwd_fused", int(fused))
        rep.gauge("flash/bwd_resident_bytes", resident_bytes or 0)
        prefixes = ["flash"]
        if shape is not None:
            prefixes.append(f"flash/shape/{shape_key(*shape)}")
            rep.count(f"{prefixes[1]}/calls")
            for field, value in row.items():
                rep.gauge(f"{prefixes[1]}/{field}", value)
        for prefix in prefixes:
            for kernel, tiles in record.items():
                for field, value in tiles.items():
                    rep.gauge(f"{prefix}/{kernel}/{field}", value)
    if blockdiff is not None:
        _publish_blockdiff(blockdiff, record)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    block_diffusion: Optional[int] = None,
):
    """Flash attention over (B, S, H, D) tensors (layout matches the
    transformer layers in ``chainermn_tpu.models``).  ``q`` and ``k`` are
    ``D`` wide, the width the scores are taken over; ``v`` is (B, Sk,
    H_kv, D_v) with a ``D_v`` of its own (latent attention scores over
    192 and sums values of 128), and the result (B, Sq, H, D_v): the
    kernels stream ``v``, ``o``, ``do`` and ``dv`` at ``D_v`` and make
    ``p v`` and ``do v^T`` at that width, nothing is padded.  The default
    ``scale`` is ``1/sqrt(D)`` either way.

    ``window``: optional sliding-window size (Mistral-style local
    attention, causal only): query ``i`` attends keys ``[i - window + 1,
    i]``, intersected with the segment masks.  Whole tiles outside the
    band are never entered, in forward AND backward: a
    windowed call's inner grid axis is the band's width in tiles
    (:func:`_band_steps`), not the sequence's, so compute AND grid steps
    scale O(S * window) instead of O(S²/2) (PERF.md §6, PR 40: a step
    that is entered and skipped costs 0.24 µs on a v5e, and 225 of a head
    row's 256 were at S = 16,384 under a window of 1024).

    Uses the Pallas kernel when shapes allow (D, D_v ≤ 256, S divisible by the
    block sizes after clamping); otherwise falls back to XLA attention.
    The compiled path handles any D ≤ 256 (Mosaic pads the lane dim;
    verified on a v5e-class chip against the XLA oracle at D ∈ {16..128}
    and at the wide-head points D ∈ {160, 192, 256}).

    GQA/MQA: ``k``/``v`` may carry ``H_kv`` heads with ``H_kv`` dividing
    ``H`` (``H_kv == 1`` is MQA).  Query head ``h`` attends to kv head
    ``h // (H / H_kv)``; the kernels stream the SHARED kv block via index
    maps (no materialized repeat) and reduce the group's dk/dv inside the
    backward kernel's accumulator.

    ``q_segment_ids``/``kv_segment_ids``: optional (B, S) int32 segment
    ids for PACKED sequences — tokens attend only within their own
    segment (combined with the causal mask), the packed-long-context
    training shape.  Rows whose segment matches nothing (padding, e.g.
    segment id -1 against all-nonnegative kv ids) produce zero output
    and zero gradients.

    ``block_diffusion``: the block length ``B`` of block-diffusion
    training.  The ``S = 2 L`` rows are a document's clean copy followed
    by its noised one, and the mask is :func:`blockdiff_mask`'s, stated
    by ``(L, B)`` and nothing else: a clean row sees the clean blocks up
    to its own, a noisy row the clean blocks before its own and its own
    noisy block.  Causal between blocks (``causal=True``), alone (no
    window, no segment ids).  The three kernels' grids walk the mask's
    live tiles only — a q tile's clean prefix and, for a noisy tile, its
    diagonal noisy tile; the dk/dv side the transposed statement — from
    a scalar-prefetched list (:func:`_blockdiff_walk`), and a tile the
    mask does not cut pays no compare.  At ``L`` = 8192 and 1024 x 1024
    tiles that is 80 of the rectangle's 256 tiles a head row and
    ``L (L + B)`` pairs, half of the causal triangle's at the same rows.

    ``block_q``/``block_k`` default to the rule of
    :func:`auto_block_size`: along each axis the largest multiple of
    128 that divides it and whose square tile fits Mosaic's default
    scoped VMEM by the kernels' own footprint — 1024 at D=128 in bf16.
    It rests on the ledger's PR 24 line of ``cgpt-train-1chip`` (the old
    S/16 rule's 128 x 128 at S=2048: ``kernel.flash_ms`` 336.5 at 5.0%
    of the roofline) and on the block sweep of PERF.md §6, PR 25.

    ``block_q_bwd``/``block_k_bwd``: optional separate geometry for the
    backward (it streams two extra operands and holds more per tile,
    so its optimum can differ).  With nothing pinned they
    default to the rule's answer for the backward's footprint; blocks
    pinned for the forward carry over.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Hk = k.shape[2]
    Dv = v.shape[3]
    if k.shape[3] != D:
        raise ValueError(
            f"queries ({D}) and keys ({k.shape[3]}) are scored against "
            f"each other: one width")
    if H % Hk or v.shape[2] != Hk:
        raise ValueError(
            f"kv heads ({Hk}, v {v.shape[2]}) must be equal and divide "
            f"the query head count ({H})"
        )
    if scale is None:
        scale = 1.0 / (D**0.5)
    if window is not None:
        if not causal:
            raise ValueError(
                "window (sliding-window attention) requires causal=True — "
                "a non-causal local band has no in-tree consumer and "
                "would silently differ from every oracle"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError(
            "q_segment_ids and kv_segment_ids must be passed together"
        )
    blockdiff = None
    if block_diffusion is not None:
        B_blk = int(block_diffusion)
        if (not causal or window is not None or q_segment_ids is not None
                or Sq != Sk or Sq % 2 or B_blk < 1
                or (Sq // 2) % B_blk):
            raise ValueError(
                f"block_diffusion={block_diffusion}: the mask is over "
                f"2 L rows [clean ; noisy] of queries and keys alike "
                f"(got Sq={Sq}, Sk={Sk}), L a multiple of the block, "
                f"causal between blocks, and takes no window and no "
                f"segment ids (packed documents under this mask are not "
                f"built)")
        blockdiff = (Sq // 2, B_blk)

    if interpret is None:
        interpret = default_interpret()

    segmented = q_segment_ids is not None
    pinned = block_q is not None or block_k is not None

    def static(S, which):
        return auto_block_size(S, D, q.dtype, which, segmented, window,
                               None if Dv == D else Dv, blockdiff)

    if not pinned and block_q_bwd is None and block_k_bwd is None:
        # Nothing pinned: the backward gets the rule's own
        # answer (it holds more per tile).  Blocks a caller pinned for
        # the forward carry over to the backward, as they always did.
        block_q_bwd, block_k_bwd = static(Sq, "bwd"), static(Sk, "bwd")
    if block_q is None:
        block_q = static(Sq, "fwd")
    if block_k is None:
        block_k = static(Sk, "fwd")
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # Sublane tiling constraint on compiled TPU kernels: the block's
    # second-to-last dim must be a multiple of the dtype's sublane count.
    # The lane (last) dim need not be a multiple of 128 — Mosaic pads it —
    # so any head_dim ≤ 128 compiles.  Interpret mode has no tiling, so
    # the CPU harness can exercise smaller shapes.
    sublane = _sublane(q.dtype)
    tile_ok = interpret or (
        block_q % sublane == 0 and block_k % sublane == 0
    )
    # Wide heads: Mosaic pads the lane dim, so any D ≤ 256 compiles
    # (verified on-chip at D ∈ {160, 192, 256} against the oracle);
    # beyond 256 the VMEM block economics favor the XLA fallback.
    d_ok = max(D, Dv) <= 256
    # (under the block-diffusion mask a tile lies in one copy)
    Lq, Lk = (Sq, Sk) if blockdiff is None else (Sq // 2, Sk // 2)
    usable = (
        d_ok
        and Lq % block_q == 0
        and Lk % block_k == 0
        and tile_ok
    )
    if not usable:
        warnings.warn(
            f"flash_attention: the Pallas kernel does not cover Sq={Sq}, "
            f"Sk={Sk}, D={D}, D_v={Dv} at blocks ({block_q}, {block_k}); "
            "using XLA "
            "attention, which materializes the Sq x Sk logits",
            stacklevel=2,
        )
        return _xla_attention(
            q, k, v, scale, causal,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            window=window, blockdiff=blockdiff,
        )

    # Backward geometry rides the same gate as the forward's: an invalid
    # pair (a block that does not divide, a caller typo) reverts to the
    # forward blocks rather than demoting the whole call to the XLA path.
    if block_q_bwd is not None or block_k_bwd is not None:
        bq_b = block_q_bwd or block_q
        bk_b = block_k_bwd or block_k
        bwd_ok = (
            Lq % bq_b == 0 and Lk % bk_b == 0
            and (interpret or (bq_b % sublane == 0 and bk_b % sublane == 0))
        )
        block_q_bwd, block_k_bwd = (bq_b, bk_b) if bwd_ok else (None, None)

    if telemetry_active():
        bq_b, bk_b = block_q_bwd or block_q, block_k_bwd or block_k
        fused = bwd_fused_vmem_bytes(Sk, bq_b, bk_b, D, q.dtype.itemsize,
                                     segmented, Dv) is not None
        _publish_geometry(
            tile_census(Sq, Sk, block_q, block_k, causal, window,
                        blockdiff)["fwd"],
            tile_census(Sq, Sk, bq_b, bk_b, causal, window, blockdiff,
                        segmented),
            blockdiff,
            bwd_resident_bytes(Sk, D, Dv) if fused else None,
            (H, Hk, window),
        )

    # (B, S, H, D) → (B*H, S, D); kv keep their own (possibly smaller)
    # head count — the batch-major flattening makes q row b's kv row
    # exactly b // (H // Hk) (see _kv_group).
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, Dv)
    if q_segment_ids is not None:
        qs = seg_to_bh(q_segment_ids, H)
        ks = seg_to_bh(kv_segment_ids, Hk)
        out = _flash_bh_seg(
            qt, kt, vt, qs, ks, scale, causal, block_q, block_k, interpret,
            window, block_q_bwd, block_k_bwd,
        )
    else:
        out = _flash_bh(
            qt, kt, vt, scale, causal, block_q, block_k, interpret, window,
            block_q_bwd, block_k_bwd, blockdiff,
        )
    return out.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)


def flash_block_plan(S: int, D: int, dtype, interpret: bool):
    """(usable, block_size) for running the kernel over length-``S``
    chunks — the single block-policy used by composition layers
    (ring/zigzag).  Mirrors :func:`flash_attention`'s gating: D ≤ 256
    compiled, blocks always DIVIDING S (a
    non-dividing block floors the grid and silently drops tail rows —
    interpret mode included), sized by :func:`auto_block_size`: the
    largest tile that fits the default scoped VMEM in the forward AND
    the backward, which share the one block here."""
    if interpret:
        # Interpreter-mode block policy: a full-S block materializes the
        # S×S matrix (defeating the O(S) property), while a degenerate
        # block means (S/b)² interpreter invocations — an effective hang.
        # So: smallest aligned divisor keeping the grid ≤ 64 per axis,
        # else the largest divisor ≤ 512 under the same grid cap, else
        # refuse and let the caller fall back / raise, as the compiled
        # branch does.
        cands = [b for b in (128, 256, 512) if S % b == 0 and S <= b * 64]
        if cands:
            return True, min(cands)
        b = max(d for d in range(1, min(S, 512) + 1) if S % d == 0)
        if b * 64 < S:
            return False, 0
        return True, b
    if D > 256:
        return False, 0
    if S % 128 == 0:
        # One block serves the forward and the backward here.
        return True, min(auto_block_size(S, D, dtype, "fwd"),
                         auto_block_size(S, D, dtype, "bwd"))
    if S <= 512 and S % _sublane(dtype) == 0:
        return True, S
    return False, 0


def to_bh(x):
    """(B, S, H, D) → (B*H, S, D), the kernel layout."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def from_bh(x, B: int, H: int):
    """(B*H, S, D) → (B, S, H, D)."""
    _, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def seg_to_bh(ids, H: int):
    """(B, S) segment ids → the kernel's (B*H, S, 1) layout (head index
    minor, matching :func:`to_bh`'s flattening)."""
    return jnp.repeat(ids.astype(jnp.int32), H, axis=0)[..., None]


def row_mask(handed: dict) -> dict:
    """What an attention row hands its ``attention_fn`` beside ``(q, k,
    v, mask)``, as :func:`flash_attention`'s keywords: its ``window``
    where it has one (:func:`row_window` settles it against the
    adapter's own), the ``block_diffusion`` block where its table trains
    under that mask.  A row with neither hands nothing."""
    return {k: v for k, v in handed.items() if v is not None}


def row_window(own, handed):
    """The window an ``attention_fn`` adapter runs under: the one the
    calling row hands over (``MultiHeadAttention.window``), else the
    adapter's own default."""
    return own if handed is None else handed


def make_flash_attention_fn(causal: bool = True, q_segment_ids=None,
                            kv_segment_ids=None, window=None,
                            block_q=None, block_k=None,
                            block_q_bwd=None, block_k_bwd=None,
                            scale: Optional[float] = None):
    """Adapter for the transformer layers' ``attention_fn`` slot (mask
    argument ignored; causality is the kernel's).

    ``window``: one sliding window for every layer that calls the
    adapter; a row of a block table that has its own hands it over at
    the call (``fn(q, k, v, mask, window=...)``), so the rows of one
    model can differ (:func:`row_window`); a row of a table trained by
    block diffusion hands its block the same way (``block_diffusion=``).

    ``scale``: the softmax scale, passed through to
    :func:`flash_attention` (None = ``1/sqrt(D)``) and kept as the
    adapter's ``scale`` attribute, by which a layer that states its own
    scale checks it was given the matching adapter.

    ``block_q``/``block_k``/``block_q_bwd``/``block_k_bwd``: optional
    pinned kernel geometry; None defers to :func:`flash_attention`'s
    rule (:func:`auto_block_size`).

    ``q_segment_ids``/``kv_segment_ids`` (optional int32) bind
    packed-sequence segment masks at CONSTRUCTION — the layers call
    ``attention_fn(q, k, v, mask)``, so per-batch metadata enters as a
    closure.  Two shapes are accepted:

    * ``(S,)`` — one row's ids, broadcast to every batch row.  This is
      the DATA-PARALLEL-SAFE form: under ``shard_map`` the closure is
      replicated while ``q`` is a local shard, so only row-uniform ids
      can be correct without knowing which global rows a device holds.
    * ``(B, S)`` — per-row ids; ``B`` must EQUAL the batch the adapter
      sees (a mismatch raises rather than silently masking shard 1+ with
      shard 0's rows)."""

    own_window = window

    def _match(ids, batch):
        if ids.ndim == 1:
            import jax.numpy as _jnp

            return _jnp.broadcast_to(ids[None], (batch, ids.shape[0]))
        if ids.shape[0] != batch:
            raise ValueError(
                f"segment_ids batch {ids.shape[0]} != attention batch "
                f"{batch}: under data-parallel sharding the adapter "
                "cannot know which global rows this shard holds — pass "
                "row-uniform (S,) ids, or thread per-row ids through "
                "flash_attention directly inside the sharded region"
            )
        return ids

    def fn(q, k, v, mask=None, window=None, block_diffusion=None):
        del mask
        qs = ks = None
        if q_segment_ids is not None:
            qs = _match(q_segment_ids, q.shape[0])
            ks = _match(
                kv_segment_ids if kv_segment_ids is not None
                else q_segment_ids,
                k.shape[0],
            )
        return flash_attention(
            q, k, v, causal=causal, q_segment_ids=qs, kv_segment_ids=ks,
            window=row_window(own_window, window),
            block_q=block_q, block_k=block_k,
            block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd, scale=scale,
            **row_mask({"block_diffusion": block_diffusion}),
        )

    fn.scale = scale
    return fn
