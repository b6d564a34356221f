"""Step programs: mean device time of one decode step (trace)."""

from chipbench.harness import readers

PROGRAM = r'decode_step'


def read(ctx):
    return readers.program_dev_ms(ctx, PROGRAM)
