"""Persistent tune cache — measured-best kernel configs, remembered.

One JSON file maps cache keys (``kernel|device_kind|dtype|shape
bucket|flags``) to the winning config plus its measured time and enough
provenance to audit a pick later.  The file lives OUTSIDE the repo
(default ``/tmp/chainermn_tpu/tune_cache.json``; override with
``CHAINERMN_TPU_TUNE_CACHE``) so no test or bench run can dirty the
working tree, and writes are atomic (tempfile + ``os.replace``) so a
crashed tuner never leaves a torn file.  A corrupt or unreadable file
degrades to an empty cache — the ops then use their static defaults, the
same behavior as a miss.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Any, Dict, Optional

ENV_CACHE_PATH = "CHAINERMN_TPU_TUNE_CACHE"
ENV_AUTOTUNE = "CHAINERMN_TPU_AUTOTUNE"
DEFAULT_CACHE_PATH = "/tmp/chainermn_tpu/tune_cache.json"
CACHE_VERSION = 1


def cache_path() -> str:
    """Cache file path: ``$CHAINERMN_TPU_TUNE_CACHE`` or the /tmp default
    — never a path inside the repository."""
    return os.environ.get(ENV_CACHE_PATH) or DEFAULT_CACHE_PATH


def autotune_enabled() -> bool:
    """May the measurement harness run at all?

    False under pytest (``PYTEST_CURRENT_TEST`` — the tier-1 determinism
    guard: a test run must never time kernels or write cache files) and
    when ``CHAINERMN_TPU_AUTOTUNE`` is ``0``/``off``/``false``.
    """
    if os.environ.get(ENV_AUTOTUNE, "").lower() in ("0", "off", "false"):
        return False
    if "PYTEST_CURRENT_TEST" in os.environ:
        return False
    return True


def runtime_lookup_enabled() -> bool:
    """May the ops consult the cache at trace time?

    Everything :func:`autotune_enabled` requires, plus a real TPU
    backend: off-TPU (CPU interpret mode, tests) the ops must be
    bit-identical to the static-default behavior, so the cache is never
    even read there.
    """
    if not autotune_enabled():
        return False
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - backend init failure
        return False


def device_kind() -> str:
    """First device's kind string (e.g. ``TPU v5e``) — part of every
    cache key, so configs tuned on one chip generation never leak onto
    another."""
    try:
        import jax

        return str(jax.devices()[0].device_kind)
    except Exception:
        return "unknown"


def dtype_name(dtype) -> str:
    """Canonical dtype string for cache keys (``bfloat16``, ``float32``)."""
    import numpy as np

    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(getattr(dtype, "name", dtype))


def bucket_pow2(n: int) -> int:
    """Shape bucket: the next power of two >= ``n``.  Kernel timing is
    insensitive within a ~2x size band, and bucketing keeps one tuned
    entry serving the whole band instead of fragmenting the cache per
    exact shape."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def make_key(kernel: str, dev_kind: str, dtype, shape_bucket, flags) -> str:
    """Canonical cache key.  ``shape_bucket``: sequence of (name, int)
    pairs, already bucketed by the caller; ``flags``: dict of static
    kernel options (causal/window/...).  Deterministic: flags are sorted,
    bools rendered as 0/1."""
    shape_s = "x".join(f"{k}{int(v)}" for k, v in shape_bucket)
    flag_s = ",".join(
        f"{k}={int(v) if isinstance(v, bool) else v}"
        for k, v in sorted(dict(flags).items())
    )
    return "|".join([kernel, dev_kind, dtype_name(dtype), shape_s, flag_s])


class TuneCache:
    """The persistent JSON cache.  Thread-safe; loads lazily; all write
    paths are atomic.  ``get``/``put`` speak plain config dicts."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or cache_path()
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._loaded = False
        self._lock = threading.Lock()

    def load(self) -> "TuneCache":
        """Read the file; missing/corrupt/wrong-version degrades to an
        empty cache (a miss everywhere — static defaults apply)."""
        with self._lock:
            self._entries = {}
            self._loaded = True
            try:
                with open(self.path) as f:
                    data = json.load(f)
                if (
                    isinstance(data, dict)
                    and data.get("version") == CACHE_VERSION
                    and isinstance(data.get("entries"), dict)
                ):
                    self._entries = {
                        str(k): dict(v)
                        for k, v in data["entries"].items()
                        if isinstance(v, dict)
                    }
            except (OSError, ValueError):
                pass
        return self

    def _ensure_loaded(self):
        if not self._loaded:
            self.load()

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        self._ensure_loaded()
        with self._lock:
            e = self._entries.get(key)
            return dict(e) if e is not None else None

    def put(self, key: str, config: Dict[str, Any]) -> None:
        self._ensure_loaded()
        with self._lock:
            self._entries[str(key)] = dict(config)

    def __len__(self) -> int:
        self._ensure_loaded()
        return len(self._entries)

    def keys(self):
        self._ensure_loaded()
        with self._lock:
            return sorted(self._entries)

    def save(self) -> str:
        """Atomic write (tempfile in the destination dir + ``os.replace``)
        so concurrent readers never observe a torn file."""
        self._ensure_loaded()
        with self._lock:
            payload = {"version": CACHE_VERSION, "entries": self._entries}
            d = os.path.dirname(os.path.abspath(self.path)) or "."
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(prefix=".tune_cache.", dir=d)
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        return self.path


_shared: Optional[TuneCache] = None
_shared_lock = threading.Lock()


def shared_cache() -> TuneCache:
    """Process-wide cache singleton, re-resolved if the env-var path
    changes (tests point it at tmp dirs)."""
    global _shared
    with _shared_lock:
        if _shared is None or _shared.path != cache_path():
            _shared = TuneCache().load()
        return _shared
