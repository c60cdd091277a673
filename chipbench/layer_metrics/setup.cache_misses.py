"""Compilations inside set-up that asked the persistent cache, were not
found, compiled and were written (JAX's ``cache_misses``; a program too
quick to keep is neither a hit nor a miss): 0 on a truly warm run.
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "cache_misses")
