"""Seconds of the first ``train_step`` call, host side: tracing the
layers, lowering, compiling or loading from the cache, dispatch.
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "first_call_s")
