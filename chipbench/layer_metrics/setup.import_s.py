"""Seconds from the process's start (the OS's, `/proc/self/stat`) to the
end of ``import chainermn_tpu`` (``import jax`` nested in it): the
interpreter, ``run.py``'s own imports, a fresh checkout's cold files.
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "import_s")
