"""Seconds from the program's first call to the first ``train_step`` call:
mesh, communicator, model, optimizer, the seeded weights and the state
on the device (their programs' compile spans nest here).
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "build_s")
