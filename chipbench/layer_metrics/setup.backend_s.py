"""Seconds from the end of ``import chainermn_tpu`` to the program's first
call, ``setup_compilation_cache`` (the documented first call of every
entry point): where ``run.py`` brings the backend up (``jax.devices()``).
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "backend_s")
