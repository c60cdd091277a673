"""Seconds inside set-up covered by a ``compile`` span of the program's
ledger (the union), cache retrieval and executable load included: what
a warm cache takes away and an evicted entry brings back.
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "compile_s")
