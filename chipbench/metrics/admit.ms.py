"""Scheduler loop: mean time of one admission (``serve.admit``: prefill
dispatch, its tail-zero and merge, and the wait for the first token), from
the program's spans in the trace."""

from chipbench.harness import spans


def read(ctx):
    return spans.admit_ms(spans.load(ctx))
