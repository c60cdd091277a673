"""chainermn_tpu — a TPU-native distributed training framework with the
capabilities of ChainerMN (reference: keisukefukuda/chainermn), built
idiomatically on jax/XLA rather than ported.

Facade mirroring REF:chainermn/__init__.py's re-exports: the communicator
factory, the data-parallel trio (multi-node optimizer / dataset scatter /
multi-node evaluator), and the model-parallel API (differentiable
point-to-point and collective functions, ``MultiNodeChainList``).
"""

import time as _time

_IMPORT_FIRST = _time.perf_counter()   # the start-up ledger's `import` span
import jax as _jax  # noqa: E402,F401  (timed apart: `import_jax`)

_IMPORT_JAX = (_IMPORT_FIRST, _time.perf_counter())

from chainermn_tpu.communicators import (  # noqa: E402,F401
    CommunicatorBase,
    create_communicator,
    build_mesh,
)

from chainermn_tpu.observability import startup as _startup  # noqa: E402

__version__ = "0.1.0"


def __getattr__(name):
    # Lazy facade for the higher layers so `import chainermn_tpu` stays
    # cheap and cycle-free while the package grows.
    if name in (
        "create_multi_node_optimizer",
        "MultiNodeOptimizer",
    ):
        from chainermn_tpu import optimizers

        return getattr(optimizers, name)
    if name in ("scatter_dataset", "create_empty_dataset"):
        from chainermn_tpu import datasets

        return getattr(datasets, name)
    if name in ("create_multi_node_evaluator", "create_multi_node_checkpointer"):
        from chainermn_tpu import extensions

        return getattr(extensions, name)
    if name in ("MultiNodeChainList",):
        from chainermn_tpu import links

        return getattr(links, name)
    if name in ("analysis", "functions", "observability", "elastic"):
        import importlib

        return importlib.import_module(f"chainermn_tpu.{name}")
    if name in (
        "create_multi_node_iterator",
        "create_synchronized_iterator",
        "create_prefetch_iterator",
    ):
        from chainermn_tpu import iterators

        return getattr(iterators, name)
    if name in ("global_except_hook",):
        # importlib, NOT `from chainermn_tpu import ...`: the from-import
        # re-enters this __getattr__ before the submodule is bound and
        # recurses forever.
        import importlib

        return importlib.import_module("chainermn_tpu.global_except_hook")
    raise AttributeError(f"module 'chainermn_tpu' has no attribute {name!r}")


_startup.finish_import(_IMPORT_FIRST, _IMPORT_JAX)
