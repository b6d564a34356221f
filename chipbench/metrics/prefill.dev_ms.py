"""Step programs: mean device time of one admission prefill (trace)."""

from chipbench.harness import readers

PROGRAM = r'prefill_step'


def read(ctx):
    return readers.program_dev_ms(ctx, PROGRAM)
