"""Scheduler loop: mean lanes decoded per step in the window (count)."""

from chipbench.harness import readers


def read(ctx):
    return readers.batch(ctx)
