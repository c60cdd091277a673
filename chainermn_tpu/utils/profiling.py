"""Timing helpers and the compile-cache switch.

SURVEY §5.1: the reference relied on Chainer's TimerHook + external nvprof.
Here: ``slope_time`` / ``sync`` are the host-clock timing of
``benchmarks/allreduce_bench.py``,
``allreduce_bus_bandwidth_gbs`` the ``allreduce bus-bw GB/s``
arithmetic BASELINE.json tracks, and
``setup_compilation_cache`` what every entry point calls first.  Profiler
captures and named regions live in ``chainermn_tpu.observability``
(``device_trace.capture``, ``spans.annotate`` / ``span`` / ``named_scope``).
"""

from __future__ import annotations

from typing import Optional

import jax


def setup_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set in code.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
    the cache key and a directory that moves never hits.  The step
    programs of the flagships take minutes to compile; every entry point
    (``chip_smoke.py``, ``benchmarks/``, ``tools.serve``, the examples)
    calls this before its first jit so a second run starts in
    seconds."""
    import os

    from chainermn_tpu.observability import startup

    startup.mark("setup_compilation_cache")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"
        )
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir


def slope_time(run, n1: int, n2: Optional[int] = None) -> float:
    """Per-iteration time via the two-point slope ``(T₂−T₁)/(n₂−n₁)``.

    ``run(n)`` must execute ``n`` iterations (chained, or relying on the
    device's FIFO program order) and end with ONE :func:`sync`.  The
    dispatch of the first program and the final readback are a constant
    per run, so a single run over-reports per-iteration time by
    constant/n — the slope between two run lengths cancels it exactly.
    """
    if n2 is None:
        n2 = 5 * n1
    t1, t2 = run(n1), run(n2)
    return (t2 - t1) / (n2 - n1)


def sync(tree):
    """Execution barrier: force every array in ``tree`` to finish
    executing by reading one element back to the host.

    A device→host transfer of an output element cannot complete before
    the producing program does, and — unlike a whole-array
    ``device_get`` — it works on sharded arrays in multi-process runs,
    where remote shards are not addressable: only one element of one
    locally-addressable shard is fetched, which is enough to order this
    host behind the producing program.
    """
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            jax.device_get(shards[0].data.ravel()[:1])
        elif hasattr(leaf, "ravel"):
            jax.device_get(leaf.ravel()[:1])
    return tree


def allreduce_bus_bandwidth_gbs(
    nbytes: int, n_devices: int, seconds_per_allreduce: float
) -> float:
    """Ring-allreduce bus bandwidth: each chip moves 2(n-1)/n of the buffer
    over its links per allreduce — the standard bus-bw formula, reported in
    GB/s as BASELINE.json asks."""
    if seconds_per_allreduce <= 0:
        return 0.0
    moved = 2 * (n_devices - 1) / max(n_devices, 1) * nbytes
    return moved / seconds_per_allreduce / 1e9
