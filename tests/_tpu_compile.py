"""The described TPU v5e that ``tests/test_tpu_compile_*.py`` compile for.

Interpret mode cannot show what Mosaic refuses: a block that is not
aligned to the tiling, or more scoped VMEM than a kernel may use.  The
TPU's compiler is installed here and compiles for a chip that is
described and not attached (``jax.experimental.topologies``), a few
seconds a kernel, up to a minute a layer.

The topology is described inside a fixture (never at import), and every
compile runs in the test's own process.  The files are split by what
compiles — the kernels by kind, the layers by family — so that
``--dist loadfile`` can spread them over workers: its queue is ordered
by a file's NUMBER of tests, so a file of few, long tests starts last
and is the run's tail.  Keep a file of layers (half a minute each) under
two minutes, and above two tests: a worker is handed its next file when
it has two tests left, so a smaller file waits behind a long one.  Each worker that gets a file loads the TPU's library,
which the driver's ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` permits.  Without it
only the first worker to ask gets the library and the other files'
tests skip: run them in one process then
(``pytest tests/test_tpu_compile_*.py``).
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding


def _described():
    """The described topology's devices, with the persistent compile
    cache off while a test file uses them."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: the next run would warn
    # on every entry.  Off for this file's tests.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip():
    for devices in _described():
        yield SingleDeviceSharding(devices[0])


@pytest.fixture(scope="module")
def four_chips():
    """The four described devices of the 2x2, for a step that spans
    them (``tests/test_tpu_compile_exchange.py``)."""
    yield from _described()
