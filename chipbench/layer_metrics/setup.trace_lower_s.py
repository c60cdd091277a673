"""Seconds inside set-up covered by a ``trace`` or ``lower`` span of the
program's ledger (the union: spans nest and overlap), every program's:
Python tracing of the layers one by one and the lowering to MLIR.
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "trace_lower_s")
