"""Low-precision scaling core — shared by gradient comms and the KV cache.

One quantization discipline, two consumers:

* **Training comms** (:meth:`CommunicatorBase.allreduce_grad` with
  ``comm_dtype=``): each packed gradient bucket is scaled by its global
  amax and cast to a narrow wire dtype (int8, or fp8-e4m3 where the
  backend supports it) before the sum collective, then cast back and
  unscaled after.  The blessed emission pattern is

      amax = pmax(max(|bucket|))          # one tiny f32 collective
      s    = amax / per_rank_qmax         # world headroom: the SUM fits
      q    = clip(round(bucket / s))      # narrow wire dtype
      out  = psum(q) * s / world          # sum collective + dequant mean

  ``per_rank_qmax`` is ``floor(qmax / world)`` for int8 (an INTEGER
  budget, so ``round(x/s) <= per_rank_qmax`` exactly — a fractional
  budget like ``127/8 = 15.875`` would round up to 16 and the summed
  wire value would wrap int8), and ``qmax / world`` with a 2**-3
  rounding-headroom divisor for fp8 (which saturates rather than wraps,
  but the headroom keeps the sum representable).  The collective needs
  no widening accumulator, and division by the world happens in f32 at
  dequant time, never in integer arithmetic.

* **Serving KV** (``kv_dtype="int8"`` on the engine): K/V pages are
  stored int8 with one f32 scale per written token per KV head (amax
  over ``d_head``), carried in page-shaped scale buffers that ride the
  same block table — so copy-on-write splits, defragmentation and
  migration snapshots move scales with their pages for free.

Error bounds (documented in docs/performance.md, enforced by
tests/test_quant.py): with ``A = pmax(amax)`` per bucket and ``n`` the
world size, the per-element error of the quantized *mean* vs the fp32
mean is at most

* int8: ``A / (2 * floor(127 / n))`` — each rank rounds to a grid of
  step ``s = A / floor(127/n)``, contributing ``s/2`` worst case; the
  mean divides the summed error back by ``n``.
* fp8 (e4m3): ``A * (n + 1) / 16`` — half-ulp relative error ``2**-4``
  per quantized element plus the fp8 summation's own rounding.  Loose by
  construction (fp8 is a *relative*-error format); observed error is far
  smaller on gradient-shaped data.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: Environment override for an unset constructor ``comm_dtype``.
#: Values: ``int8`` | ``fp8`` | ``none`` (explicit off).
ENV_COMM_DTYPE = "CHAINERMN_TPU_COMM_DTYPE"

#: Environment override for an unset engine ``kv_dtype``.
ENV_KV_DTYPE = "CHAINERMN_TPU_KV_DTYPE"

#: Canonical comm wire-dtype names accepted by ``comm_dtype=`` (plus
#: ``"none"`` for explicit off and ``None`` for "resolve env").
COMM_DTYPE_CHOICES = ("int8", "fp8")

#: Canonical KV cache storage dtypes accepted by ``kv_dtype=``.
KV_DTYPE_CHOICES = ("int8",)

_INT8_QMAX = 127.0

_NAME_ALIASES = {
    "": None,
    "none": "none",
    "off": "none",
    "0": "none",
    "float32": "none",
    "bfloat16": "none",
    "bf16": "none",
    "int8": "int8",
    "s8": "int8",
    "fp8": "fp8",
    "e4m3": "fp8",
    "float8_e4m3fn": "fp8",
    # e2m1 (fp4) has no backend support anywhere we run; the ISSUE's
    # "where the backend supports it, int8 fallback otherwise" contract
    # maps it to the fp8 resolution path, which falls back in turn.
    "e2m1": "fp8",
}


def canonical_comm_dtype(name: Any) -> Optional[str]:
    """Normalize a user spelling of ``comm_dtype``.

    Returns ``None`` for "unset" (resolve env -> off), the
    string ``"none"`` for an explicit off, or a canonical member of
    :data:`COMM_DTYPE_CHOICES`.  Raises on unknown names so typos fail
    at construction, not silently at full precision.
    """
    if name is None:
        return None
    key = str(name).strip().lower()
    if key in _NAME_ALIASES:
        return _NAME_ALIASES[key]
    raise ValueError(
        f"unknown comm_dtype {name!r}; choose from "
        f"{COMM_DTYPE_CHOICES} (or 'none' to disable)"
    )


def canonical_kv_dtype(name: Any) -> Optional[str]:
    """Normalize a ``kv_dtype`` spelling: ``None``/"none"/model-dtype
    names mean "store pages at the model dtype" (off); ``"int8"`` turns
    quantized pages on."""
    if name is None:
        return None
    key = str(name).strip().lower()
    if key in ("", "none", "off", "bf16", "bfloat16", "float32", "fp32"):
        return None
    if key in ("int8", "s8"):
        return "int8"
    raise ValueError(
        f"unknown kv_dtype {name!r}; choose from {KV_DTYPE_CHOICES} "
        "(or 'none' to store pages at the model dtype)"
    )


@functools.lru_cache(maxsize=None)
def fp8_supported() -> bool:
    """Whether this jax/backend pair can compile arithmetic on
    ``float8_e4m3fn`` (probed once; collectives on e4m3 follow where
    the elementwise ops compile — verified on the CPU and TPU backends
    this repo targets)."""
    if not hasattr(jnp, "float8_e4m3fn"):
        return False
    try:
        jax.jit(lambda x: x + x)(
            jnp.ones((2,), jnp.float8_e4m3fn)
        ).block_until_ready()
        return True
    except Exception:  # pragma: no cover - backend without fp8
        return False


def wire_dtype(comm_dtype: Optional[str]):
    """Canonical comm dtype name -> the jnp dtype that goes on the wire.

    ``"fp8"`` resolves to ``float8_e4m3fn`` where the backend supports
    it and **falls back to int8** otherwise (the ISSUE's contract);
    ``None``/``"none"`` -> ``None`` (quantization off).
    """
    if comm_dtype is None or comm_dtype == "none":
        return None
    if comm_dtype == "int8":
        return jnp.int8
    if comm_dtype == "fp8":
        return jnp.float8_e4m3fn if fp8_supported() else jnp.int8
    raise ValueError(f"unknown canonical comm_dtype {comm_dtype!r}")


def qmax(wire_dt) -> float:
    """Largest representable magnitude of a wire dtype."""
    wire_dt = jnp.dtype(wire_dt)
    if wire_dt == jnp.dtype(jnp.int8):
        return _INT8_QMAX
    return float(jnp.finfo(wire_dt).max)  # e4m3fn: 448


def quantizable(dtype) -> bool:
    """Only inexact (float) buckets are quantized; integer gradients
    (rare, but legal pytree leaves) pass through at full precision."""
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def _chunked(buf, chunk_elems: Optional[int]):
    """View a 1-D buffer as (n_chunks, chunk) when ``chunk_elems``
    divides it, else as one chunk.  Per-chunk scales tighten the error
    bound on buckets whose leaves have very different magnitudes."""
    n = buf.shape[0]
    if chunk_elems and chunk_elems < n and n % chunk_elems == 0:
        return buf.reshape(n // chunk_elems, chunk_elems)
    return buf.reshape(1, n)


def local_amax(buf, chunk_elems: Optional[int] = None):
    """Per-chunk max-abs of this rank's bucket, f32, shape (n_chunks,)."""
    x = _chunked(buf, chunk_elems).astype(jnp.float32)
    return jnp.max(jnp.abs(x), axis=1)


def per_rank_qmax(wire_dt, world: int) -> float:
    """Each rank's magnitude budget on the wire, such that the WORLD SUM
    stays representable.  int8: an integer budget (``round`` can never
    exceed an integer bound, see module docstring) — worlds beyond 127
    chips have no int8 budget left and must shard the sum (the 2-D /
    scatter legs) or stay at full precision.  fp8: ``qmax/world`` with a
    2**-3 divisor absorbing the format's relative rounding."""
    wire_dt = jnp.dtype(wire_dt)
    if wire_dt == jnp.dtype(jnp.int8):
        return max(1.0, float(np.floor(_INT8_QMAX / world)))
    return qmax(wire_dt) / world / (1.0 + 2.0 ** -3)


def scale_for(amax_global, wire_dt, world: int):
    """The shared scale ``s = amax / per_rank_qmax`` (f32, per chunk).

    The world headroom in :func:`per_rank_qmax` keeps every rank's
    quantized value small enough that the wire-dtype SUM cannot
    overflow.  Zero-amax chunks (all-zero gradients) get ``s = 1`` so
    the divide is finite and the round trip is exactly zero.
    """
    s = amax_global / per_rank_qmax(wire_dt, world)
    return jnp.where(amax_global > 0, s, jnp.ones_like(s))


def quantize(buf, scale, wire_dt, chunk_elems: Optional[int] = None):
    """Scale + cast one bucket buffer to the wire dtype."""
    x = _chunked(buf, chunk_elems).astype(jnp.float32) / scale[:, None]
    wire_dt = jnp.dtype(wire_dt)
    if wire_dt == jnp.dtype(jnp.int8):
        x = jnp.clip(jnp.round(x), -_INT8_QMAX, _INT8_QMAX)
    return x.astype(wire_dt).reshape(buf.shape)


def dequantize_mean(qsum, scale, world: int, out_dtype,
                    chunk_elems: Optional[int] = None):
    """Summed wire buffer -> the fp mean: ``qsum * s / world``.

    The division happens in f32 — never in the wire dtype, where integer
    division would truncate toward zero and bias every gradient.
    """
    x = _chunked(qsum, chunk_elems).astype(jnp.float32)
    x = x * (scale[:, None] / float(world))
    return x.reshape(qsum.shape).astype(out_dtype)


def quantize_for_allreduce(
    buf, wire_dt, axes, world: int, chunk_elems: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The comm half's pre-collective leg: local amax -> ``pmax`` over
    the world (so every rank agrees on the scale) -> quantize.

    Returns ``(q, scale)``; the caller runs its characteristic SUM
    collective on ``q`` and finishes with :func:`dequantize_mean`.
    Must be called inside ``shard_map`` over ``axes`` (the same contract
    as every traced collective).
    """
    amax = lax.pmax(local_amax(buf, chunk_elems), axes)
    scale = scale_for(amax, wire_dt, world)
    return quantize(buf, scale, wire_dt, chunk_elems), scale


def error_bound(comm_dtype: str, amax, world: int):
    """Documented per-dtype worst-case error of the quantized mean vs
    the fp32 mean (see module docstring; gated in tests/test_quant.py).

    ``comm_dtype`` is the canonical name; ``amax`` the global bucket
    amax (scalar or array).  fp8's bound covers the int8 fallback too
    (the int8 bound is strictly tighter at any world size >= 1).
    """
    amax = np.asarray(amax, np.float64)
    if comm_dtype == "int8":
        return amax / (2.0 * max(1.0, np.floor(_INT8_QMAX / world)))
    if comm_dtype == "fp8":
        # Covers the int8 fallback too: the int8 bound is tighter than
        # this for every world size the fallback can see.
        return amax * (world + 1) / 16.0
    raise ValueError(f"no error bound for comm_dtype {comm_dtype!r}")


# ----------------------------------------------------------------------
# Serving KV half: per-token-per-head scales over d_head
# ----------------------------------------------------------------------
def quantize_kv(x) -> Tuple[jax.Array, jax.Array]:
    """Quantize freshly-projected K or V for int8 page storage.

    ``x``: (B, T, Hkv, D).  Returns ``(q, scales)`` with ``q`` int8 of
    the same shape and ``scales`` f32 of shape (B, T, Hkv) — one amax
    scale per written token per KV head, the granularity that survives
    paging: token (page, slot) moves atomically with its scale through
    the same block table.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / _INT8_QMAX, jnp.ones_like(amax))
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -_INT8_QMAX, _INT8_QMAX
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scales, dtype):
    """Int8 pages (or a gathered context) back to the compute dtype.

    ``q``: (..., Hkv, D) int8; ``scales``: (..., Hkv) f32 broadcast over
    the trailing head dim.  Invalid/untouched slots hold zero payload
    AND zero scale, so they dequantize to exact zeros — the same value
    the unquantized cache's zero-init gives masked positions.
    """
    return (q.astype(jnp.float32) * scales[..., None]).astype(dtype)


# ----------------------------------------------------------------------
# Host-side measurement (Reporter gauges are host-plane: in-jit
# publishing is impossible, so error is measured eagerly on demand)
# ----------------------------------------------------------------------
def measure_comm_quant_error(comm, tree, publish: bool = True) -> float:
    """Max-abs error of ``comm``'s quantized allreduce vs its own
    full-precision path on ``tree`` (rank-stacked by replication, so the
    true mean is the tree itself).

    Publishes the ``comm/quant_abs_err`` gauge when telemetry is active
    and ``publish`` is set.  Returns the error as a Python float — the
    number bench's A/B column and the verify-skill probe print.
    """
    cd = comm.resolve_comm_dtype()
    if cd is None:
        raise ValueError(
            "measure_comm_quant_error needs a communicator with a "
            "resolved comm_dtype (ctor or CHAINERMN_TPU_COMM_DTYPE)"
        )
    stacked = jax.tree.map(
        lambda l: jnp.broadcast_to(
            l[None], (comm.device_size,) + tuple(l.shape)
        ),
        tree,
    )
    out_q = comm.eager_allreduce_grad(stacked)
    saved = comm.comm_dtype
    try:
        comm.comm_dtype = "none"
        out_ref = comm.eager_allreduce_grad(stacked)
    finally:
        comm.comm_dtype = saved
    err = 0.0
    for a, b in zip(jax.tree.leaves(out_q), jax.tree.leaves(out_ref)):
        d = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
        err = max(err, float(d))
    if publish:
        from chainermn_tpu.observability import reporter as _reporter
        from chainermn_tpu.observability import spans as _spans

        if _spans.telemetry_active():
            rep = _reporter.get_reporter()
            if rep is not None:
                rep.gauge("comm/quant_abs_err", err)
    return err
