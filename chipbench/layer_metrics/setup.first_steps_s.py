"""Seconds from the first ``train_step`` call's return to the window: the
first steps on the device and what ``correct`` reads back (the first
moment's norms, the parameters' change and their programs).
``chipbench/setup_reduce.py`` cuts the program's start-up ledger where
the runner cuts ``setup_s``."""

from chipbench import setup_reduce


def read(ctx):
    return setup_reduce.reading(ctx, "first_steps_s")
