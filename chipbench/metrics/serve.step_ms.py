"""Scheduler loop: the window over the loop steps taken in it (host clock)."""

from chipbench.harness import readers


def read(ctx):
    return readers.step_ms(ctx)
